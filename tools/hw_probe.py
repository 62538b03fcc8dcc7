"""On-chip microbenchmarks that validate bench.py's methodology.

Round-3's verdict flagged `hbm_util_decode = 5.5` — a measured decode rate
5.5x above the HBM roofline computed from the chip's nameplate specs
(bench.py detect_specs), which is physically impossible if every Q40 byte
streams from HBM each step.  This probe separates the two possible causes:

* the device is faster than its "TPU v5 lite" label
  (fix: detect_specs constants), or
* the timing methodology (async dispatch chain + one block_until_ready)
  under-counts (fix: bench.py measurement).

Stages (each prints one JSON line; run standalone on the real chip):

  mem        device memory_stats — real HBM capacity
  dispatch   round-trip latency of a trivial jitted program (the RTT floor)
  hbm_bw     effective GB/s of a reduction over a 2 GiB int8 array,
             measured BOTH as an async chain and with per-rep blocking
  mxu        bf16 matmul TFLOP/s (4096^3, 8192-batched)
  decode     1b-preset greedy decode: async-chain timing (bench.py's way)
             vs per-step block_until_ready timing vs wall time for 2x steps
             (doubling test: real serial execution must double)
  chunked    per-dispatch wall time of greedy_steps K=32, timed one
             dispatch at a time (bench saw a model-size-independent
             ~1.1 s/dispatch — fixed overhead, not compute)

FINDING (first run on a chip, 2026-07-31, through a shared remote transport
that no longer exists): ``block_until_ready`` did NOT wait for device
execution there — it returned 2 GiB
reductions in 20 us ("86 TB/s"), 4096^3 matmuls at "9.7 PFLOP/s", and an
8B-shape decode FASTER than the 1B shape, while the first dispatch after a
burst absorbed a 2.17 s backlog drain.  Every stage therefore times through
``jax.device_get`` of a value that DEPENDS on the computation: the runtime
cannot hand back real bytes without executing the chain, so a small fetch
(4 B token, scalar sum) is the only trustworthy synchronization point.
bench.py uses the same fetch-based timing for the same reason.
"""

from __future__ import annotations

import json
import os
import sys
import time


_OUT_FILE = [None]  # --out FILE: tee every stage line (JSONL) for the
# roofline observatory's measured ceilings (runtime/roofline reads the
# hbm_bw/mxu stages via DLLAMA_HW_PROBE_FILE or HW_PROBE.json)


def emit(stage: str, **kw) -> None:
    line = json.dumps({"stage": stage, **kw})
    print(line, flush=True)
    if _OUT_FILE[0]:
        with open(_OUT_FILE[0], "a", encoding="utf-8") as f:
            f.write(line + "\n")


def main() -> None:
    argv = list(sys.argv[1:])
    if "--out" in argv:
        i = argv.index("--out")
        try:
            _OUT_FILE[0] = argv[i + 1]
        except IndexError:
            raise SystemExit("--out needs a file path") from None
        del argv[i:i + 2]
        # truncate up front: stale hbm_bw/mxu lines from a PREVIOUS probe
        # (possibly different silicon) must never survive into what the
        # roofline observatory serves as THIS chip's measured ceilings
        open(_OUT_FILE[0], "w").close()
    stages = set(argv) or {
        "mem", "dispatch", "hbm_bw", "mxu", "decode", "chunked"}
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    emit("device", platform=dev.platform, kind=dev.device_kind)

    if "mem" in stages:
        ms = dev.memory_stats() or {}
        emit("mem", **{k: v for k, v in ms.items()
                       if "bytes" in k or "limit" in k})

    if "dispatch" in stages:
        one = jnp.ones((8, 128), jnp.float32)
        f = jax.jit(lambda x: x.sum())
        jax.device_get(f(one))
        lat = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.device_get(f(one))
            lat.append(time.perf_counter() - t0)
        lat.sort()
        emit("dispatch", p50_ms=round(1e3 * lat[10], 3),
             min_ms=round(1e3 * lat[0], 3), max_ms=round(1e3 * lat[-1], 3))

    if "hbm_bw" in stages:
        n = 2 << 30  # 2 GiB of int8
        big = jax.block_until_ready(
            jax.jit(lambda k: jax.random.bits(k, (n,), jnp.uint8))(
                jax.random.PRNGKey(0)))
        red = jax.jit(lambda x, s: (x.astype(jnp.int32).sum() + s))
        s = jnp.int32(0)
        jax.device_get(red(big, s))  # compile + drain queue
        reps = 8
        t0 = time.perf_counter()
        for _ in range(reps):
            s = red(big, s)
        jax.device_get(s)  # forces the whole chain to have executed
        dt_chain = time.perf_counter() - t0
        per_sync = []
        for _ in range(reps):
            t0 = time.perf_counter()
            s = red(big, s)
            jax.device_get(s)
            per_sync.append(time.perf_counter() - t0)
        emit("hbm_bw", gib=2,
             chain_gbps=round(reps * n / dt_chain / 1e9, 1),
             sync_gbps=round(n / min(per_sync) / 1e9, 1),
             chain_ms_per_rep=round(1e3 * dt_chain / reps, 2),
             sync_ms_min=round(1e3 * min(per_sync), 2),
             sync_ms_max=round(1e3 * max(per_sync), 2))

    if "mxu" in stages:
        m = 4096
        a = jnp.ones((2 * m, m), jnp.bfloat16)
        b = jnp.ones((m, m), jnp.bfloat16)
        mm = jax.jit(lambda a, b: (a @ b))
        tot = jax.jit(lambda x: x.astype(jnp.float32).sum())
        jax.device_get(tot(mm(a, b)))  # compile + drain
        # chained reps (out feeds in) so the final fetch forces every matmul;
        # the 1/m rescale keeps ones-matrices at 1.0 (b is a runtime input,
        # XLA cannot fold the product away)
        mm2 = jax.jit(lambda x, b: (x @ b) * jnp.bfloat16(1.0 / m))
        jax.device_get(tot(mm2(a, b)))
        reps = 16
        t0 = time.perf_counter()
        out = a
        for _ in range(reps):
            out = mm2(out, b)
        jax.device_get(tot(out))  # depends on every rep in the chain
        dt = time.perf_counter() - t0
        emit("mxu", tflops=round(reps * 2 * (2 * m) * m * m / dt / 1e12, 1))

    if "decode" in stages or "chunked" in stages:
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        import bench as benchmod

        cfg = benchmod.model_cfg("1b")
        from dllama_tpu.models.llama import greedy_step, greedy_steps
        from dllama_tpu.runtime import KVCache

        params = benchmod.device_random_params(cfg)
        jax.block_until_ready(params)
        kv = KVCache.create(cfg, batch_size=1, dtype=jnp.bfloat16)
        greedy = jax.jit(greedy_step, static_argnums=1, donate_argnums=(4,))
        token = jnp.ones((1,), jnp.int32)
        token, kv = greedy(params, cfg, token[:, None], jnp.int32(0), kv)
        jax.device_get(token)
        pos = 1

        if "decode" in stages:
            for steps in (32, 64):  # doubling test
                t0 = time.perf_counter()
                for i in range(steps):
                    token, kv = greedy(params, cfg, token[:, None],
                                       jnp.int32(pos + i), kv)
                jax.device_get(token)  # 4 B fetch forces the chain
                dt = time.perf_counter() - t0
                emit("decode_chain", steps=steps,
                     ms_per_step=round(1e3 * dt / steps, 3),
                     tok_per_s=round(steps / dt, 1))
                pos += steps
            sync = []
            for i in range(32):
                t0 = time.perf_counter()
                token, kv = greedy(params, cfg, token[:, None],
                                   jnp.int32(pos + i), kv)
                jax.device_get(token)
                sync.append(time.perf_counter() - t0)
            pos += 32
            sync.sort()
            emit("decode_sync", ms_p50=round(1e3 * sync[16], 3),
                 ms_min=round(1e3 * sync[0], 3),
                 ms_max=round(1e3 * sync[-1], 3))

        if "chunked" in stages:
            gsteps = jax.jit(greedy_steps, static_argnums=(1, 5),
                             donate_argnums=(4,))
            K = 32
            t0 = time.perf_counter()
            toks, kv = gsteps(params, cfg, token, jnp.int32(pos), kv, K)
            jax.device_get(toks)
            emit("chunked_compile", s=round(time.perf_counter() - t0, 2))
            pos += K
            for r in range(4):
                t0 = time.perf_counter()
                toks, kv = gsteps(params, cfg, toks[:, -1],
                                  jnp.int32(pos), kv, K)
                jax.device_get(toks)
                dt = time.perf_counter() - t0
                emit("chunked_dispatch", r=r, ms=round(1e3 * dt, 1),
                     ms_per_tok=round(1e3 * dt / K, 2))
                pos += K


if __name__ == "__main__":
    main()
