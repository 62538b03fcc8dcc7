"""On-chip sweep of the Q40 matmul kernels (honest slope timing).

Two sweeps over Q40 planes (int8 codes + block scales, one byte a weight on
the device) against a v5e's 819 GB/s, reported as GB/s of quantized bytes:
the ROW SWEEP (default; below) and, with ``--variants``, the older M = 1
exploration that sized the kernels: the (n, k)-tiled Pallas kernel at
several (bn, bk) block choices against the decode-shaped FUSED
dequant-GEMV (ops/quant_matmul._decode_kernel — one full-K pass per N
stripe, dequant in VMEM), the XLA dequant+dot fallback (f32- and
bf16-stored scales), a dense bf16 matmul (the no-quantization reference
point), a raw s8xs8 MXU dot -> s32 (rate bound for a w8a8 mode),
manually packed 4-bit codes unpacked on the VPU (halved code HBM vs
shift/mask cost), and multi-row activations (M=8 verify / M=256
prefill-chunk shapes).

Timing methodology: the host->device round trip measured ~67 ms in the
2026-07-31 capture and per-dispatch host enqueue ~1 ms, so sub-millisecond
kernels cannot be timed by host-side rep loops at all.  Each variant instead runs
inside ONE dispatch as a ``lax.fori_loop`` whose carry perturbs the
activation every iteration (the weights — the bytes being measured — stay
loop-invariant, exactly like real decode; the carry dependency stops XLA
from hoisting the matmul).  Wall time is taken at two iteration counts and
the per-op cost is the SLOPE, which cancels the RTT and any fixed
dispatch/loop overhead.

Usage:  python tools/gemv_sweep.py [n_lo] [n_hi] [--json] [--variants]
                                    [--rows 1,2,4,8,16,32,64,128,256]
                                    [--models mistral-7b,qwen3-4b,olmo-hybrid-7b]

The default run is the ROW SWEEP, the evidence under ``auto``'s fast-mode
rule (ops/quant_matmul.pallas_mode_gate; tables in PERF.md): for every Q40
plane shape of a layer of Mistral-7B-v0.3, Qwen3-4B and Olmo-Hybrid-7B and
every M in ``--rows`` (1-16: a decode step; 32-256: a prefill chunk's
buckets), the XLA dequant + dot against the fused full-K kernel, each both
ways a layer scan can hand it the weight: one 2-D plane pair, or a stack of
layers — XLA slicing layer ``i % L`` out of it (what a scan's ``xs`` slice
is; before a custom call it is a copy), the kernel taking the stack and the
index (quant_matmul's ``layer`` entry). **Read the stack rows**: a 2-D
plane is loop-invariant here, so XLA hoists its dequant out of the timing
loop and the plain ``xla`` row times a dense bf16 dot — a floor no model
program reaches, since a layer scan hands it another layer every time.
Rows of 32 and more also print TFLOP/s. ``--variants`` runs the older
M = 1 exploration instead (tile picks, packed codes, s8 x s8, ...).

``--json`` prints ONE machine-readable JSON line: ``{"tool": "gemv_sweep",
"device_kind": ..., "rows": [{"shape", "label", "us", "gbps"}, ...]}`` —
scriptable kernel A/Bs.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# the Q40 planes of one layer, [K, N], at the benchmark's dense and hybrid
# configurations (benchmark/configs): wq | wo, wk | wv, w1 | w3, w2
# (Qwen3-4B's q width is 4096 over a 2560 model dim, so its wq and wo
# differ); the hybrid's: the mixer's packed q k v z plane (17280 = 135 x
# 128) and its output plane, q | k | v | wo of a full layer, w1 | w3, w2
LAYER_SHAPES = {
    "mistral-7b": ((4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)),
    "qwen3-4b": ((2560, 4096), (2560, 1024), (4096, 2560), (2560, 9728),
                 (9728, 2560)),
    "olmo-hybrid-7b": ((3840, 17280), (5760, 3840), (3840, 3840),
                       (3840, 11008), (11008, 3840)),
}
STACK_LAYERS = 4  # enough that no layer's planes stay in VMEM between uses
VARIANT_SHAPES = ((2048, 8192), (4096, 14336), (2048, 128256))  # --variants


def main() -> None:
    argv = sys.argv[1:]
    opts = {}
    for flag in ("--rows", "--models"):
        if flag in argv:
            i = argv.index(flag)
            opts[flag] = argv[i + 1]
            del argv[i:i + 2]
    args = [a for a in argv if a not in ("--json", "--variants")]
    as_json = "--json" in argv
    variants = "--variants" in argv
    n_lo = int(args[0]) if len(args) > 0 else 64
    n_hi = int(args[1]) if len(args) > 1 else 448
    sweep_rows = [int(m) for m in opts.get(
        "--rows", "1,2,4,8,16,32,64,128,256").split(",")]
    models = opts.get("--models", ",".join(LAYER_SHAPES)).split(",")
    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.ops.linear import (LayerSlice, QuantizedWeight,
                                       dequantize_weight)

    rows: list = []

    def say(*a, **kw):
        if not as_json:
            print(*a, **kw)

    def fetch(x):
        jax.device_get(jnp.ravel(x)[0])

    key = jax.random.PRNGKey(0)

    def make_w(K, N):
        kc, ks = jax.random.split(jax.random.fold_in(key, N))
        codes = (jax.random.bits(kc, (K, N), jnp.uint8) & jnp.uint8(0x0F)
                 ).astype(jnp.int8) - 8
        scales = jax.random.uniform(ks, (K // 32, N), jnp.float32,
                                    minval=0.001, maxval=0.011)
        return QuantizedWeight(scales=scales, codes=codes)

    shape_label = [""]  # current "K=..,N=.." tag for the JSON rows

    def bench(label, op, x, *wargs, bytes_moved: int, indexed: bool = False,
              flops: int = 0):
        """op(x, *wargs) -> y [M, N]; loop it on device, slope-time it.
        ``indexed`` ops take the iteration number first (a layer index);
        ``flops`` (a chunk-wide dispatch) adds a TFLOP/s column."""

        @jax.jit
        def looped(n, x, *wargs):
            def body(i, carry):
                x, acc = carry
                y = op(i, x, *wargs) if indexed else op(x, *wargs)
                acc = acc + jnp.sum(y, dtype=jnp.float32)
                # perturb the activation so no iteration is hoistable; the
                # scale keeps values finite over hundreds of iterations
                x = x * (1.0 + 1e-12 * acc).astype(x.dtype)
                return x, acc

            x, acc = jax.lax.fori_loop(0, n, body, (x, jnp.float32(0.0)))
            return acc

        row = {"shape": shape_label[0], "label": label, "us": None,
               "gbps": None}
        rows.append(row)
        try:
            times = {}
            for n in (n_lo, n_hi):
                fetch(looped(n, x, *wargs))  # compile + warm
                t0 = time.perf_counter()
                fetch(looped(n, x, *wargs))
                times[n] = time.perf_counter() - t0
            per_op = (times[n_hi] - times[n_lo]) / (n_hi - n_lo)
            if per_op <= 0:
                say(f"  {label:<28} not resolvable (slope <= 0)")
                row["error"] = "slope <= 0"
                return None
            gbps = bytes_moved / per_op / 1e9
            tflops = flops / per_op / 1e12
            say(f"  {label:<28} {1e6 * per_op:9.1f} us  {gbps:7.1f} GB/s"
                + (f"  {tflops:6.1f} TFLOP/s" if flops else ""))
            row["us"] = round(1e6 * per_op, 2)
            row["gbps"] = round(gbps, 1)
            if flops:
                row["tflops"] = round(tflops, 1)
            return per_op
        except Exception as e:  # noqa: BLE001
            say(f"  {label:<28} {type(e).__name__}: {str(e)[:70]}")
            row["error"] = f"{type(e).__name__}: {str(e)[:120]}"
            return None

    def row_sweep():
        """XLA dequant + dot against the fused kernel, per shape and M."""
        L = STACK_LAYERS
        for model in models:
            for K, N in LAYER_SHAPES[model]:
                w = make_w(K, N)
                w = QuantizedWeight(scales=w.scales.astype(jnp.bfloat16),
                                    codes=w.codes)  # as a fast-mode load holds it
                stack = QuantizedWeight(*(
                    jnp.stack([jnp.roll(p, j, axis=-1) for j in range(L)])
                    for p in w))
                nbytes = K * N + (K // 32) * N * 2
                for M in sweep_rows:
                    x = jax.random.normal(jax.random.fold_in(key, K + M),
                                          (M, K), jnp.bfloat16)
                    shape_label[0] = f"{model},K={K},N={N},M={M}"
                    say(f"\n{model} [{M},{K}] x [{K},{N}]  "
                        f"({nbytes / 1e6:.1f} MB quant)", flush=True)

                    def xla(x, w):
                        return x @ dequantize_weight(w, dtype=jnp.bfloat16)

                    def take(i, s):
                        return LayerSlice(s, i % L).take()

                    fused = functools.partial(qm.quant_matmul, fast=True,
                                              fused=True)
                    # the gate's own name for the regime: fused | chunk
                    name = qm.fused_path((M, K), w, True)
                    kw = {"bytes_moved": nbytes,
                          "flops": 2 * M * K * N if M > qm.FUSED_MAX_M else 0}
                    bench("xla", xla, x, w, **kw)
                    bench("xla, stack slice",
                          lambda i, x, s: xla(x, take(i, s)), x, stack,
                          indexed=True, **kw)
                    if name is None:
                        continue
                    bench(name, fused, x, w, **kw)
                    bench(f"{name}, stack slice",
                          lambda i, x, s: fused(x, take(i, s)), x, stack,
                          indexed=True, **kw)
                    bench(f"{name}, stack + index",
                          lambda i, x, s: fused(
                              x, s, layer=(i % L).astype(jnp.int32)),
                          x, stack, indexed=True, **kw)

    if not variants:
        row_sweep()
    for K, N in (VARIANT_SHAPES if variants else ()):
        w = make_w(K, N)
        x = jax.random.normal(jax.random.fold_in(key, K), (1, K), jnp.bfloat16)
        nbytes = K * N + (K // 32) * N * 4  # codes + f32 scales
        shape_label[0] = f"K={K},N={N}"
        say(f"\nGEMV [1,{K}] x [{K},{N}]  ({nbytes / 1e6:.0f} MB quant)",
            flush=True)

        for bn, bk in ((512, 512), (1024, 512), (2048, 512), (512, 1024),
                       (1024, 1024), (2048, 1024), (1024, 2048)):
            if N % bn or K % bk:
                continue
            bench(f"pallas bn={bn} bk={bk}",
                  functools.partial(qm.quant_matmul, fast=True, bn=bn, bk=bk),
                  x, w, bytes_moved=nbytes)
        bench("pallas default picks",
              functools.partial(qm.quant_matmul, fast=True), x, w,
              bytes_moved=nbytes)
        # the decode-shaped fused dequant-GEMV candidate (one full-K pass
        # per N stripe; DLLAMA_TPU_QUANT_KERNEL=fused) — fast (serving) and
        # exact (parity) numerics
        if qm.supports_decode((1, K), w, True):
            bench("pallas fused (fast)",
                  functools.partial(qm.quant_matmul, fast=True, fused=True),
                  x, w, bytes_moved=nbytes)
        if qm.supports_decode((1, K), w, False):
            bench("pallas fused (exact)",
                  functools.partial(qm.quant_matmul, fused=True),
                  x, w, bytes_moved=nbytes)

        bench("xla dequant+dot (fast)",
              lambda x, w: x @ dequantize_weight(w, dtype=jnp.bfloat16),
              x, w, bytes_moved=nbytes)

        bench("xla dequant bf16-scales",
              lambda x, w: x @ (w.codes.astype(jnp.bfloat16)
                                * jnp.repeat(w.scales.astype(jnp.bfloat16),
                                             32, axis=0)),
              x, w, bytes_moved=K * N + (K // 32) * N * 2)

        c4 = w.codes.astype(jnp.int4)  # packed: 0.5 B/weight in HBM
        s16 = w.scales.astype(jnp.bfloat16)
        bench("xla dequant s4 codes",
              lambda x, c, s: x @ (c.astype(jnp.bfloat16)
                                   * jnp.repeat(s, 32, axis=0)),
              x, c4, s16, bytes_moved=K * N // 2 + (K // 32) * N * 2)

        # grouped dot: batched [G,32]x[G,32,N] dots then one scale multiply
        # per (group, col) — 32x less VPU scale work than per-element
        # dequant, exact same math (sum regrouped by quant block)
        def grouped_mv(x, w):
            G = K // 32
            xg = x.reshape(G, 32).astype(jnp.bfloat16)  # [G, 32]
            cg = w.codes.reshape(G, 32, N).astype(jnp.bfloat16)
            part = jax.lax.dot_general(  # [G, N]
                xg, cg, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return jnp.sum(part * w.scales.astype(jnp.float32),
                           axis=0)[None, :]

        bench("grouped-dot + scale", grouped_mv, x, w, bytes_moved=nbytes)

        wd = w.codes.astype(jnp.bfloat16)
        bench("dense bf16 (2B/weight)", lambda x, w: x @ w, x, wd,
              bytes_moved=2 * K * N)
        # s8 x s8 -> s32 directly on the MXU (no converts the compiler could
        # hoist): the per-op rate bounds a w8a8 quant mode
        xq = jnp.clip(jnp.round(x.astype(jnp.float32) * 16.0),
                      -127, 127).astype(jnp.int8)
        bench("s8xs8 MXU dot -> s32",
              lambda xq, c: jax.lax.dot_general(
                  xq, c, dimension_numbers=(((1,), (0,)), ((), ())),
                  preferred_element_type=jnp.int32), xq, w.codes,
              bytes_moved=K * N)
        # manually packed 4-bit codes (two per byte along K), unpacked on the
        # VPU in-graph: halves code HBM at the price of shift/mask VPU work
        packed = ((w.codes[0::2] + 8).astype(jnp.uint8)
                  | ((w.codes[1::2] + 8).astype(jnp.uint8) << 4))

        def unpack_mv(x, p, s):
            lo = (p & jnp.uint8(0x0F)).astype(jnp.int8) - 8
            hi = (p >> 4).astype(jnp.int8) - 8
            c = jnp.stack([lo, hi], axis=1).reshape(K, N)
            wd = c.astype(jnp.bfloat16) * jnp.repeat(s, 32, axis=0)
            return x @ wd

        bench("packed-u4 dequant+dot", unpack_mv, x, packed,
              w.scales.astype(jnp.bfloat16),
              bytes_moved=K * N // 2 + (K // 32) * N * 2)

        # multi-row activations: the verify (M=8) and prefill-chunk (M=256)
        # shapes — how the fused dequant amortizes over rows
        for M in (8, 256):
            xm = jax.random.normal(jax.random.fold_in(key, 7 * M),
                                   (M, K), jnp.bfloat16)
            bench(f"xla dequant M={M}",
                  lambda x, w: x @ dequantize_weight(w, dtype=jnp.bfloat16),
                  xm, w, bytes_moved=K * N + (K // 32) * N * 4)
            if M <= qm.FUSED_MAX_M and qm.supports_decode((M, K), w, True):
                bench(f"pallas fused M={M}",
                      functools.partial(qm.quant_matmul, fast=True,
                                        fused=True),
                      xm, w, bytes_moved=K * N + (K // 32) * N * 4)

    if as_json:
        try:
            kind = jax.devices()[0].device_kind
        except Exception:  # noqa: BLE001 — the line must still emit
            kind = ""
        print(json.dumps({"tool": "gemv_sweep", "device_kind": kind,
                          "n_lo": n_lo, "n_hi": n_hi, "rows": rows}))


if __name__ == "__main__":
    main()
