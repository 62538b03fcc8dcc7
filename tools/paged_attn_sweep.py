"""On-chip timing of ``paged_ragged_attention`` alone (honest slope timing).

The paged-attention kernel (ops/paged_attention.py) at the benchmark's four
geometries, over how much of the table is live: occupancy {1 row, a quarter
of the rows, all rows} x length {1/8, 1/2, the whole table}. Dead rows
carry an all-null table and a stale position, as a retired slot does
(runtime/serving.py). One line a geometry and point: microseconds a call
and GB/s of LIVE K/V bytes against a v5e's 819 GB/s, beside the jitted
gather + XLA oracle (the fallback path) and any other version of the
kernel file named with ``--against`` (another checkout's
``dllama_tpu/ops/paged_attention.py``, or a variant of it: PR 31 timed its
parent's kernel and that kernel's epilogue alone this way). The kernel
takes the whole pool ``[L, n_blocks, ..]`` and a layer index: the pools
here hold ``LAYERS`` layers and every variant reads layer ``LAYER``; a copy
of the file from before PR 33 (no ``layer`` parameter) is handed that
layer's slice, cut once outside the timed loop.

Then parity, at ragged lengths with a dead row in the batch: the kernel
against the jitted gather + oracle under ``highest`` (``rtol = atol =
2e-5`` asserted: the exit code is 1 where it fails) and, for the record,
under the default precision, where the MXU rounds both sides' float32
operands to bfloat16 and the two softmax orders round different
probabilities: each side is also held against the oracle under
``highest`` there, which is what says that the error is of one size.

Timing methodology (tools/gemv_sweep.py's): each variant runs inside ONE
dispatch as a ``lax.fori_loop`` whose carry perturbs the query every
iteration (the pools, the bytes being measured, stay loop-invariant, as in
a decode step); wall time is taken at two iteration counts and a call's
cost is the SLOPE, which cancels the round trip and any fixed dispatch
overhead.

Usage:  python tools/paged_attn_sweep.py [--geometries mistral,qwen3,...]
            [--against NAME=path/to/paged_attention.py ...]
            [--group-tokens 128,256] [--iters 8,40] [--json-out FILE]

``--group-tokens`` times the kernel at other fetch-group sizes than the
module's ``_GROUP_TOKENS`` (an exploration: the program has no such knob).
Off a TPU nothing is timed: the tool checks parity at a toy size in
interpret mode and prints ``not measured`` where a time would stand.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# rows, query heads, K/V heads, head dim, table entries (blocks of 16): the
# benchmark's cells (benchmark/configs; PERF.md section 4)
GEOMETRIES = {
    "mistral": (16, 32, 8, 128, 64),         # both 16-slot Mistral cells
    "qwen3": (16, 32, 8, 128, 80),           # qwen3-4b.chat
    "mistral-long": (4, 32, 8, 128, 256),    # mistral-7b-v0.3.long-prompt
    "hybrid": (4, 30, 30, 128, 256),         # olmo-hybrid-7b.long-prompt
}
TOY = {"toy": (4, 8, 2, 16, 12)}
BLOCK = 16
LAYERS, LAYER = 2, 1  # the pools' leading axis, and the layer every variant reads
HBM_GBPS = 819.0  # one v5e (benchmark/peaks.py)


def load_kernel(path: str):
    """``paged_ragged_attention`` of another copy of the kernel file."""
    spec = importlib.util.spec_from_file_location(
        "paged_attention_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_ragged_attention


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometries", default=None)
    ap.add_argument("--against", action="append", default=[],
                    metavar="NAME=PATH")
    ap.add_argument("--group-tokens", default="")
    ap.add_argument("--json-out", default="")
    ap.add_argument("--iters", default="8,40")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.ops.attention import attention

    on_chip = jax.default_backend() == "tpu"
    geometries = GEOMETRIES if on_chip else TOY
    if args.geometries:
        geometries = {g: {**GEOMETRIES, **TOY}[g]
                      for g in args.geometries.split(",")}
    n_lo, n_hi = (int(n) for n in args.iters.split(","))
    pool_dtype = jnp.bfloat16 if on_chip else jnp.float32
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}")

    # every variant: op(q, pools, tables, positions, hd), where ``pools`` is
    # (k, v) whole ``[LAYERS, ..]`` and then layer LAYER's slices of them
    def entry(f):
        if "layer" in inspect.signature(f).parameters:
            return lambda q, pools, t, p, hd: f(
                q, pools[0], pools[1], LAYER, t, p, hd, interpret=not on_chip)
        return lambda q, pools, t, p, hd: f(
            q, pools[2], pools[3], t, p, hd, interpret=not on_chip)

    variants = {"kernel": entry(pa.paged_ragged_attention)}
    for spec in args.against:
        name, path = spec.split("=", 1)
        variants[name] = entry(load_kernel(path))
    for gt in filter(None, args.group_tokens.split(",")):
        def at_group(q, pools, t, p, hd, gt=int(gt)):
            was = pa._GROUP_TOKENS
            pa._GROUP_TOKENS = gt
            try:  # jit's cache is keyed on the wrapper: trace a fresh one
                return jax.jit(pa.paged_ragged_attention.__wrapped__,
                               static_argnames=("head_dim", "interpret"))(
                    q, pools[0], pools[1], LAYER, t, p, hd,
                    interpret=not on_chip)
            finally:
                pa._GROUP_TOKENS = was
        variants[f"kernel@{gt}"] = at_group

    def oracle(q, pools, tables, positions, hd):
        B, M = tables.shape
        n_kv, bs = pools[0].shape[2], pools[0].shape[3]

        def view(pool):
            return jnp.moveaxis(pool[LAYER, tables], 2, 1).reshape(
                B, n_kv, M * bs, hd)

        return attention(q, view(pools[0]), view(pools[1]), positions, hd)

    def looped(op, hd):
        @jax.jit
        def run(n, q, pools, tables, positions):
            def body(_, q):
                # tie the table to the carry, or XLA hoists the oracle's
                # gather (the bytes being measured) out of the loop
                q, tbl, pos = jax.lax.optimization_barrier(
                    (q, tables, positions))
                return q + 1e-3 * op(q, pools, tbl, pos, hd)

            return jax.lax.fori_loop(0, n, body, q)

        return run

    def slope_us(run, *operands) -> float:
        def wall(n):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(run(n, *operands))
                best = min(best, time.perf_counter() - t0)
            return best

        wall(n_lo)  # compile, warm
        return (wall(n_hi) - wall(n_lo)) / (n_hi - n_lo) * 1e6

    results, failed = [], False
    rng = np.random.default_rng(31)
    for gname, (B, n_heads, n_kv, hd, M) in geometries.items():
        nb = B * M + 1
        key = jax.random.fold_in(jax.random.PRNGKey(31), nb + n_kv)
        kk, kv_, kq = jax.random.split(key, 3)
        kp = jax.random.normal(kk, (LAYERS, nb, n_kv, BLOCK, hd), pool_dtype)
        vp = jax.random.normal(kv_, (LAYERS, nb, n_kv, BLOCK, hd), pool_dtype)
        pools = (kp, vp, kp[LAYER], vp[LAYER])
        q = jax.random.normal(kq, (B, 1, n_heads, hd), jnp.float32)
        real = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
        block_bytes = 2 * n_kv * BLOCK * hd * kp.dtype.itemsize  # K and V
        print(f"\n{gname}: {B} rows, {n_heads}:{n_kv} heads x {hd}, "
              f"{M} table entries of {BLOCK} ({M * BLOCK} positions), "
              f"{np.dtype(pool_dtype).name} pools, plan (heads, blocks a "
              f"fetch) {pa._plan(n_kv, n_heads // n_kv, hd, M, BLOCK, kp.dtype.itemsize)}")

        runs = {name: looped(op, hd) for name, op in variants.items()}
        runs["xla gather+oracle"] = looped(oracle, hd)
        for occ in sorted({1, max(1, B // 4), B}):
            for frac in (0.125, 0.5, 1.0):
                length = int(M * BLOCK * frac)
                tables = np.zeros((B, M), np.int32)
                tables[:occ] = real[:occ]
                # dead rows keep a stale depth, as a retired slot does
                pos = rng.integers(1, M * BLOCK, (B, 1)).astype(np.int32)
                pos[:occ] = length - 1
                live_bytes = occ * -(-length // BLOCK) * block_bytes
                line = {"geometry": gname, "live_rows": occ, "rows": B,
                        "length": length, "table": M * BLOCK,
                        "live_mb": live_bytes / 1e6, "us": {}}
                for name, run in runs.items():
                    if not on_chip:
                        line["us"][name] = None
                        continue
                    line["us"][name] = slope_us(
                        run, q, pools, jnp.asarray(tables), jnp.asarray(pos))
                cells = "  ".join(
                    f"{name} " + ("not measured" if us is None else
                                  f"{us:8.1f} us {live_bytes / us / 1e3:6.1f} GB/s")
                    for name, us in line["us"].items())
                print(f"  live {occ:2d}/{B} x {length:4d}/{M * BLOCK}  "
                      f"({live_bytes / 1e6:6.1f} MB live, "
                      f"{live_bytes / HBM_GBPS / 1e3:6.1f} us at the roof)  {cells}")
                results.append(line)

        # parity at ragged lengths, one dead row with a stale depth
        lengths = rng.integers(1, M * BLOCK + 1, B)
        lengths[0], lengths[-1] = M * BLOCK, 1
        tables = real.copy()
        dead = B // 2
        tables[dead] = 0
        for b in range(B):
            tables[b, -(-int(lengths[b]) // BLOCK):] = 0
        pos = (lengths - 1).astype(np.int32)[:, None]
        live = np.arange(B) != dead
        operands = (q, pools, jnp.asarray(tables), jnp.asarray(pos))
        truth = None  # the oracle under ``highest``
        for precision in ("highest", "default"):
            with jax.default_matmul_precision(precision):
                want = np.asarray(jax.jit(
                    lambda *a: oracle(*a, hd))(*operands), np.float32)
                truth = want if truth is None else truth
                for name, op in variants.items():
                    got = np.asarray(jax.jit(
                        lambda *a, op=op: op(*a, hd))(*operands), np.float32)
                    err = float(np.abs(got[live] - want[live]).max())
                    off = float(np.abs(got[live] - truth[live]).max())
                    ok = bool(np.allclose(got[live], want[live],
                                          rtol=2e-5, atol=2e-5))
                    zero = bool(np.all(got[dead] == 0))
                    verdict = ""
                    if precision == "highest" and name.startswith("kernel"):
                        verdict = "PASS" if ok and zero else "FAIL"
                        failed |= not (ok and zero)
                    print(f"  parity [{precision}] {name}: max |diff| on live "
                          f"rows {err:.3e} against the oracle (2e-5 "
                          f"{'held' if ok else 'not held'}), {off:.3e} "
                          f"against the oracle under highest, dead row "
                          f"{'zero' if zero else 'not zero'} {verdict}")
                    results.append({"geometry": gname, "parity": name,
                                    "precision": precision, "max_abs": err,
                                    "max_abs_vs_highest": off,
                                    "within_2e-5": ok, "dead_row_zero": zero})
            if precision == "default":
                off = float(np.abs(want[live] - truth[live]).max())
                print(f"  parity [default] xla gather+oracle: {off:.3e} "
                      f"against itself under highest")

    if args.json_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.json_out)),
                    exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump({"tool": "paged_attn_sweep", "platform": dev.platform,
                       "device_kind": dev.device_kind, "rows": results}, f)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
