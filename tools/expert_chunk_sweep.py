"""On-chip sweep of the routed chunk kernel (``ops/expert_chunk.py``) alone,
against what it replaces: the fused Q40 chunk kernel called once a held plane
over all 256 rows of the chunk (``quant_matmul`` on the flattened ``[layers x
held]`` stack and an index, as ``models.share._experts_chunk_xla`` calls it).

For each expert plane shape of the two routed configurations (laguna-s-2.1:
3072 x 1024 gate / up, 1024 x 3072 down, 32 held; A.X-K1: 7168 x 2048 and
2048 x 7168, 12 held) and each run length in ``--rows`` (pairs that share an
expert), every held expert gets one run of that length: the time a call and a
PLANE (the call over the runs). The gate / up shapes take the kernel's gather
end, the down shapes its scatter end. ``--tiles`` and ``--stripes`` add rows
at other tile heights and stripe widths.

Timing is ``tools/gemv_sweep.py``'s: each variant runs inside ONE dispatch as
a ``lax.fori_loop`` whose carry perturbs one element of the activation, wall
time at two iteration counts, the cost a call the SLOPE.

Usage: python tools/expert_chunk_sweep.py [n_lo] [n_hi] [--json]
           [--rows 1,4,11,16,32,64] [--tiles 16,32,128] [--stripes]
           [--shapes 0,1,2,3] [--rehearse]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, held experts, router's k, K, N, scatter end)
SHAPES = (("laguna gate/up", 32, 10, 3072, 1024, False),
          ("laguna down", 32, 10, 1024, 3072, True),
          ("a.x-k1 gate/up", 12, 8, 7168, 2048, False),
          ("a.x-k1 down", 12, 8, 2048, 7168, True))
CHUNK = 256
LAYERS = 2  # the loop alternates: no plane stays in VMEM between calls


def main() -> None:
    argv = sys.argv[1:]
    opts = {}
    for flag in ("--rows", "--tiles", "--shapes"):
        if flag in argv:
            i = argv.index(flag)
            opts[flag] = [int(v) for v in argv[i + 1].split(",")]
            del argv[i:i + 2]
    as_json, stripes = "--json" in argv, "--stripes" in argv
    # off a TPU: the same control flow at toy shapes in interpret mode (not
    # a measurement)
    rehearse = "--rehearse" in argv
    shapes, chunk = SHAPES, CHUNK
    if "--shapes" in opts:
        shapes = tuple(SHAPES[i] for i in opts["--shapes"])
    if rehearse:
        shapes = tuple((n, 4, 2, K // 32, N // 8, s)
                       for n, _E, _k, K, N, s in shapes)
        chunk = 32
    nums = [int(a) for a in argv if not a.startswith("--")]
    n_lo, n_hi = nums[:2] if len(nums) >= 2 else (20, 60)
    run_rows = opts.get("--rows", [1, 4, 11, 16, 32, 64])

    import jax
    import jax.numpy as jnp

    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops.linear import QuantizedWeight
    from dllama_tpu.ops.quant_matmul import quant_matmul

    tiles = opts.get("--tiles", [ec.TILE_ROWS])
    out_rows: list = []

    def say(*a):
        if not as_json:
            print(*a, flush=True)

    def slope(op, x, planes):
        """``op(i, x, planes) -> y``; seconds a call. The planes are an
        ARGUMENT of the looped program (closed over, they would be 0.3 GB of
        constants in every executable)."""
        @jax.jit
        def looped(n, x, planes):
            def body(i, carry):
                x, acc = carry
                acc = acc + op(i, x, planes)[0, 0].astype(jnp.float32)
                return x.at[0, 0].add((1e-12 * acc).astype(x.dtype)), acc

            return jax.lax.fori_loop(0, n, body, (x, jnp.float32(0.0)))[1]

        times = {}
        for n in (n_lo, n_hi):
            jax.device_get(looped(n, x, planes))
            t0 = time.perf_counter()
            jax.device_get(looped(n, x, planes))
            times[n] = time.perf_counter() - t0
        return (times[n_hi] - times[n_lo]) / (n_hi - n_lo)

    def note(shape, label, per_call, planes, **more):
        row = {"shape": shape, "label": label,
               "call_us": round(1e6 * per_call, 1),
               "plane_us": round(1e6 * per_call / planes, 2), **more}
        out_rows.append(row)
        say(f"  {label:<34} {row['call_us']:9.1f} us a call "
            f"{row['plane_us']:8.2f} us a plane"
            + "".join(f"  {k} {v}" for k, v in more.items()))

    key = jax.random.PRNGKey(0)
    for name, E, k, K, N, scatter in shapes:
        kc, ks, kx, kr = jax.random.split(jax.random.fold_in(key, K), 4)
        codes = (jax.random.bits(kc, (LAYERS, E, K, N), jnp.uint8)
                 & jnp.uint8(0x0F)).astype(jnp.int8) - 8
        scales = jax.random.uniform(ks, (LAYERS, E, K // 32, N), jnp.float32,
                                    minval=0.001, maxval=0.011)
        stack = QuantizedWeight(scales=scales.astype(jnp.bfloat16),
                                codes=codes)
        flat = QuantizedWeight(*(a.reshape((-1,) + a.shape[2:])
                                 for a in stack))
        say(f"{name}: K={K} N={N}, {E} held, plane "
            f"{K * N * (1 + 2 / 32) / 1e6:.1f} MB "
            f"({K * N * (1 + 2 / 32) / 819e3:.1f} us at 819 GB/s)")
        x = jax.random.normal(kx, (chunk, K), jnp.float32).astype(jnp.bfloat16)
        every = slope(lambda i, x, flat: quant_matmul(
            x, flat, fast=True, fused=True, layer=i % (LAYERS * E),
            interpret=rehearse), x, flat)
        note(name, "every row: 256 rows a plane", every, 1)
        P = chunk * min(k, E)
        rows = jax.random.randint(kr, (P,), 0, chunk, jnp.int32)
        w = (jnp.arange(P, dtype=jnp.int32), jnp.full((P,), 0.125, jnp.float32))
        for tm in tiles:
            F = ec.fed_rows(P, E, tm)
            widths = [None]
            if stripes:
                auto = ec.stripe(chunk, F, K, N, True, scatter, tm=tm)
                widths += [N // i for i in range(1, N // 128 + 1)
                           if N % i == 0 and (N // i) % 128 == 0
                           and auto // 4 <= N // i < auto][:2] if auto else []
            for r in run_rows:
                if r * E > P:
                    continue
                e = jnp.arange(E, dtype=jnp.int32)
                runs = (jnp.int32(E), e, e * (-(-r // tm)), e * r,
                        jnp.full((E,), r, jnp.int32))
                for tn in widths:
                    if scatter:
                        xs = jnp.tile(x, (-(-F // chunk), 1))[:F, :K]
                        op = lambda i, a, stack, tn=tn: ec.expert_chunk(
                            a, stack, i % LAYERS, runs, rows, w,
                            rows_out=chunk, fast=True, tm=tm, tn=tn,
                            interpret=rehearse)
                    else:
                        xs = x
                        op = lambda i, a, stack, tn=tn: ec.expert_chunk(
                            a, stack, i % LAYERS, runs, rows, rows_out=F,
                            fast=True, tm=tm, tn=tn, interpret=rehearse)
                    try:
                        note(name, f"grouped: {r} rows a run, tm {tm}"
                             + (f", tn {tn}" if tn else ""),
                             slope(op, xs, stack), E, fed=E * -(-r // tm) * tm,
                             pairs=E * r)
                    except Exception as err:  # noqa: BLE001
                        say(f"  {r} rows, tm {tm}, tn {tn}: "
                            f"{type(err).__name__}: {str(err)[:200]}")
    if as_json:
        print(json.dumps({"tool": "expert_chunk_sweep",
                          "device_kind": jax.devices()[0].device_kind,
                          "rows": out_rows}))


if __name__ == "__main__":
    main()
