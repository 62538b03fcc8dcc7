#!/usr/bin/env python
"""Benchmark: single-chip decode/prefill throughput on Llama-shaped Q40 models.

Prints exactly ONE JSON line:
    {"metric", "value", "unit", "vs_baseline", ...extras, "error"}

and always exits 0 with that line present, even when the TPU backend is down —
round 1 lost its whole capture window to a hanging backend init (rc=1, no
line), so this version:

1. probes backend init in a SUBPROCESS with a bounded wait (first jit/init on
   TPU is 20-40s; the probe allows 150s, retried up to 3x), and
2. wraps every stage in a deadline so a partial result still emits the line.

Headline metric: decode tok/s for the **Llama-3.1-8B shape** (the BASELINE
north-star model; Q40 planes ≈ 8.5 GB fit one 16 GB v5e chip). Physics
context for `vs_baseline`: the north star (>=1000 tok/s for 8B Q40) is an
8-chip v5e-8 aggregate-bandwidth target; a single chip's roofline is
~`hbm_GBps / weight_GB` ≈ 90-150 tok/s for this shape, so 1-chip values are
reported as-is and the roofline estimate ships in the extras for honest
comparison. Extras also carry prefill tok/s, prefill MFU, a batch-16 decode
aggregate (serving throughput; beyond the single-sequence reference), and a
secondary 1B-shape number (round-1 comparability).

The decode loop is the engine's production fast path: forward + on-device
argmax fused into one dispatch (models.llama.greedy_step), KV donated.

TIMING METHODOLOGY (round 4): in the 2026-07-31 capture
``jax.block_until_ready`` returned WITHOUT waiting for device execution
(tools/hw_probe.py measured a 2 GiB reduction "completing" in 20 us and an
8B decode "faster" than 1B — pure enqueue rates; the rounds-1-3 capture
numbers were invalid for this reason; chip_smoke.py re-checks the claim on
the chip it runs on).  Every measured region therefore ends with
``jax.device_get`` of a small value that data-depends on the computation —
the runtime cannot produce real bytes without executing the chain — and
subtracts the separately-measured host<->device round-trip (~67 ms in that
capture) once per region.  A region whose net time is smaller than the RTT itself is
reported as null (measurement floor) rather than as an inflated rate."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

NORTH_STAR_TOK_S = 1000.0  # BASELINE.json north star (8B Q40, v5e-8)
PROBE_TIMEOUT_S = float(os.environ.get("DLLAMA_BENCH_PROBE_TIMEOUT", "150"))
PROBE_RETRIES = int(os.environ.get("DLLAMA_BENCH_PROBE_RETRIES", "3"))
STAGE_DEADLINE_S = float(os.environ.get("DLLAMA_BENCH_STAGE_DEADLINE", "600"))

def _roofline_mod():
    """The roofline observatory's ceilings table + rate math
    (dllama_tpu/runtime/roofline.py), loaded BY FILE PATH: importing the
    package would pull jax (runtime/__init__ imports the KV cache), and
    the bench parent stays jax-free by design — a wedged PJRT import
    must not stall its emit path. The module's join functions import
    telemetry lazily, so the standalone load carries exactly the
    ceilings/rate surface the parent needs."""
    global _ROOFLINE_MOD
    try:
        return _ROOFLINE_MOD
    except NameError:
        pass
    import importlib.util

    p = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "dllama_tpu", "runtime", "roofline.py")
    spec = importlib.util.spec_from_file_location("_dllama_roofline", p)
    mod = importlib.util.module_from_spec(spec)
    # register BEFORE exec: dataclasses resolves string annotations via
    # sys.modules[cls.__module__] at class-creation time
    sys.modules["_dllama_roofline"] = mod
    spec.loader.exec_module(mod)
    _ROOFLINE_MOD = mod
    return mod


def detect_specs(device_kind: str) -> tuple[float, float]:
    """Nameplate (tflops, gbps) by device kind — ONE table for the whole
    repo (roofline.NAMEPLATE_SPECS; this wrapper keeps the historical
    bench signature)."""
    c = _roofline_mod().nameplate_ceilings(device_kind)
    return c.tflops, c.hbm_gbps


def emit(result: dict) -> None:
    print(json.dumps(result))
    sys.stdout.flush()


def _tail(b) -> str:
    if not b:
        return ""
    if isinstance(b, bytes):
        b = b.decode(errors="replace")
    return b[-600:]


def force_platform_from_env() -> str | None:
    """Apply the DLLAMA_BENCH_PLATFORM override in-process (jax snapshots
    JAX_PLATFORMS at import, so jax.config.update is what sticks whenever
    jax is already imported). For jax-importing processes ONLY — stage
    children and the profiling tools; the bench PARENT stays jax-free by
    design (it must never hold the chip its children need) and keeps its
    env-var write."""
    force = os.environ.get("DLLAMA_BENCH_PLATFORM")
    if force:
        import jax

        jax.config.update("jax_platforms", force)
    return force


def probe_once(platform: str | None, attempts: list) -> str | None:
    """One backend-probe subprocess; returns the device-info JSON line on
    success, None on failure. Every attempt's forensics (rc, duration,
    partial stdout/stderr — including a timed-out child's captured output)
    land in ``attempts`` so BENCH_rN.json can pin an environment-side hang
    even when nothing succeeds (VERDICT round-2 next #1).

    The platform override is applied INSIDE the child, env var and config
    both. ``subprocess.run`` returns only once the child has exited (killed
    and reaped on timeout), so the probe never holds the chip when the
    first stage child starts."""
    setenv = (
        f"import os; os.environ['JAX_PLATFORMS'] = {platform!r}; "
        f"import jax; jax.config.update('jax_platforms', {platform!r}); "
        if platform else "")
    code = (
        f"{setenv}import jax, json, sys; "
        "print('probe: importing done', file=sys.stderr, flush=True); "
        "d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'n': len(d)}))"
    )
    rec: dict = {"platform_arg": platform}
    t0 = time.monotonic()
    try:
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, timeout=PROBE_TIMEOUT_S)
        rec.update(rc=out.returncode, stdout=_tail(out.stdout),
                   stderr=_tail(out.stderr))
        lines = out.stdout.decode(errors="replace").strip().splitlines()
        if out.returncode == 0 and lines:
            rec["ok"] = True
            attempts.append(rec)
            return lines[-1]
    except subprocess.TimeoutExpired as e:
        # keep the timed-out child's partial output — the key forensic:
        # "importing done + silence" = backend init hang, not our code
        rec.update(timeout_s=PROBE_TIMEOUT_S, stdout=_tail(e.stdout),
                   stderr=_tail(e.stderr))
    rec["ok"] = False
    rec["duration_s"] = round(time.monotonic() - t0, 1)
    attempts.append(rec)
    return None


def probe_backend(platform: str | None, attempts: list) -> tuple[bool, str]:
    """Probe schedule: default platform x PROBE_RETRIES, then an explicit
    'tpu' pin (if the default resolution fails, an explicit pin may not).
    Returns (ok, detail): detail is the device-info JSON on success, else a
    summary string."""
    plans: list = [platform] * PROBE_RETRIES
    if platform is None:
        plans += ["tpu"]
    for p in plans:
        info = probe_once(p, attempts)
        if info is not None:
            return True, info
        time.sleep(5)
    fails = [a.get("stderr") or f"rc={a.get('rc')}" if "timeout_s" not in a
             else f"init exceeded {a['timeout_s']}s" for a in attempts]
    return False, f"{len(attempts)} probe attempts failed; last: {fails[-1]}"


# ---------------------------------------------------------------------------
# model shapes
# ---------------------------------------------------------------------------


# plain-int shape table: the parent process computes rooflines from these
# WITHOUT importing jax/dllama_tpu (a wedged PJRT plugin import would stall
# the parent's emit path — measurement is the children's job)
PRESETS = {
    "8b": dict(dim=4096, hidden_dim=14336, n_layers=32, n_heads=32,
               n_kv_heads=8, head_dim=128, vocab_size=128256, seq_len=1024),
    "1b": dict(dim=2048, hidden_dim=8192, n_layers=16, n_heads=32,
               n_kv_heads=8, head_dim=64, vocab_size=128256, seq_len=1024),
    "tiny": dict(dim=256, hidden_dim=512, n_layers=2, n_heads=4,
                 n_kv_heads=2, head_dim=64, vocab_size=2048, seq_len=256),
}


def model_cfg(preset: str):
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import ModelConfig

    return ModelConfig(
        arch=ArchType.LLAMA, norm_epsilon=1e-5,
        rope_theta=500000.0, rope_type=RopeType.LLAMA3_1,
        rope_scaling_factor=32.0, rope_scaling_low_freq_factor=1.0,
        rope_scaling_high_freq_factor=4.0, rope_scaling_orig_max_seq_len=8192,
        compute_dtype="bfloat16",
        # tools/perf_matrix.py sweeps kernel choices through these knobs
        attn_impl=os.environ.get("DLLAMA_BENCH_ATTN", "auto"),
        **PRESETS[preset])


def matmul_param_count(preset: str) -> int:
    """Weights touched per token (matmul planes; the HBM-bandwidth payload)."""
    p = PRESETS[preset]
    q_dim = p["n_heads"] * p["head_dim"]
    kv_dim = p["n_kv_heads"] * p["head_dim"]
    per_layer = (p["dim"] * q_dim + 2 * p["dim"] * kv_dim
                 + q_dim * p["dim"] + 3 * p["dim"] * p["hidden_dim"])
    return p["n_layers"] * per_layer + p["dim"] * p["vocab_size"]


def _codes_kernel():
    """Process-wide jitted Q40-code RNG (lazy: jax imports only on use).
    A per-call closure would recompile every code shape for each of the
    three bench_preset invocations — jit caches key on function identity."""
    global _CODES_JIT
    try:
        return _CODES_JIT
    except NameError:
        pass
    from functools import partial

    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=1)
    def _codes(k, shape):
        bits = jax.random.bits(k, shape, jnp.uint8)  # 1 B/elem of entropy
        return (bits & jnp.uint8(0x0F)).astype(jnp.int8) - 8  # [-8, 8)

    _CODES_JIT = _codes
    return _codes


def bench_weight_repr() -> str:
    """On-device weight representation for the bench stages: ``q40``
    (default — the production quantized planes) or ``bf16``
    (DLLAMA_BENCH_WEIGHTS=bf16: dense planes, the engine's
    ``--weight-mode bf16``). The dense row measures the NO-DEQUANT
    streaming ceiling — on the 1b preset it fits HBM and isolates how
    much of the decode gap is the fused dequant's VPU work."""
    w = os.environ.get("DLLAMA_BENCH_WEIGHTS", "q40")
    if w not in ("q40", "bf16"):
        raise ValueError(f"DLLAMA_BENCH_WEIGHTS must be q40|bf16, got {w!r}")
    return w


def device_random_params(cfg):
    """Random Q40-plane params generated ON DEVICE (no host RAM spike, no
    multi-GB host->device transfer: an 8B-shape Q40 stack is ~8.5 GB).

    Each tensor is built inside one jit so XLA fuses the RNG + mask + cast
    chain into the output buffer. The eager version OOM-wedged the chip:
    `randint` drew uint32 bits — a 7.5 GB intermediate for the stacked
    (32, 14336, 4096) ffn codes alone, on a 16 GB chip that already held
    earlier planes (the round-1/2 'backend hang' during the 8B stage)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models.llama import LayerParams, Params
    from dllama_tpu.ops.linear import QuantizedWeight, fast_numerics_resolved
    from dllama_tpu.runtime.weights import dense_logits_resolved

    key = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    _codes = _codes_kernel()
    # mirror the production load config (runtime.weights._StreamingLoader):
    # fast numerics store bf16 scales and a resident dense-bf16 logits head
    fast = fast_numerics_resolved(cfg.compute_dtype)
    scale_dtype = jnp.bfloat16 if fast else jnp.float32

    dense_w = bench_weight_repr() == "bf16"

    def qw(out, in_, stacked=True):
        if dense_w:
            # dense planes use the reference [out, in] orientation
            shape_d = (cfg.n_layers, out, in_) if stacked else (out, in_)
            return jax.random.uniform(next(key), shape_d, jnp.bfloat16,
                                      minval=-0.02, maxval=0.02)
        shape_s = (cfg.n_layers, in_ // 32, out) if stacked else (in_ // 32, out)
        shape_c = (cfg.n_layers, in_, out) if stacked else (in_, out)
        scales = jax.random.uniform(next(key), shape_s, scale_dtype,
                                    minval=0.001, maxval=0.011)
        codes = jax.block_until_ready(_codes(next(key), shape_c))
        return QuantizedWeight(scales=scales, codes=codes)

    ones = lambda *s: jnp.ones(s, dtype=jnp.float32)
    layers = LayerParams(
        wq=qw(cfg.q_dim, cfg.dim), wk=qw(cfg.kv_dim, cfg.dim),
        wv=qw(cfg.kv_dim, cfg.dim), wo=qw(cfg.dim, cfg.q_dim),
        w1=qw(cfg.hidden_dim, cfg.dim), w2=qw(cfg.dim, cfg.hidden_dim),
        w3=qw(cfg.hidden_dim, cfg.dim),
        norm_att=ones(cfg.n_layers, cfg.dim), norm_ffn=ones(cfg.n_layers, cfg.dim),
        norm_q=None, norm_k=None,
    )
    emb = (jax.random.uniform(next(key), (cfg.vocab_size, cfg.dim),
                              jnp.bfloat16, minval=-0.02, maxval=0.02))
    if dense_logits_resolved(cfg.compute_dtype):
        # dense head in the reference's [out, in] orientation (ops.linear)
        logits = jax.random.uniform(next(key), (cfg.vocab_size, cfg.dim),
                                    jnp.bfloat16, minval=-0.02, maxval=0.02)
    else:
        logits = qw(cfg.vocab_size, cfg.dim, stacked=False)
    return Params(embedding=emb, layers=layers, final_norm=ones(cfg.dim),
                  logits=logits)


# ---------------------------------------------------------------------------
# measured stages
# ---------------------------------------------------------------------------


class _PhaseDict(dict):
    """Stage-result dict that streams each phase transition to stdout as a
    JSON line, so the parent process can pin a wedge to its exact phase even
    when the child never returns."""

    def __setitem__(self, k, v):
        super().__setitem__(k, v)
        if k == "phase":
            print(json.dumps({"phase": v}), flush=True)


def stage_child(spec: str) -> None:
    """``bench.py --stage <spec>`` child entry: run ONE measurement stage in
    this process and print ``{"stage_result": ...}``. Isolation is the point:
    a chip wedge (the round-1/2 failure) kills this child, not the bench —
    the parent kills us at its per-stage budget and moves on.

    spec: preset name, optionally ``@b16`` (batched-serving variant) or
    ``@s8k`` (8192-token context: long-context decode is KV-bandwidth-bound,
    which is what ``--kv-dtype f8`` halves)."""
    force_platform_from_env()
    preset, _, mod = spec.partition("@")
    budget = float(os.environ.get("DLLAMA_BENCH_CHILD_BUDGET", STAGE_DEADLINE_S))
    deadline = time.monotonic() + budget
    kwargs = (dict(decode_steps=32, prefill_len=128, batch=16)
              if mod == "b16" else
              dict(seq_len=8192) if mod == "s8k" else {})
    st = _PhaseDict()
    try:
        if preset in SCENARIOS:
            SCENARIO_FNS[preset](deadline, out=st)
        else:
            bench_preset(preset, deadline, out=st, **kwargs)
    except Exception as e:  # noqa: BLE001 — the parent needs the line
        st["error"] = f"{type(e).__name__}: {e}"[:300]
    print(json.dumps({"stage_result": dict(st)}), flush=True)


CHIP_LOCK = "/tmp/dllama-chip.lock"
# stage children currently holding chip residency (the watchdog must kill
# them before force-exiting — a force-exit releases the chip lock while an
# orphan keeps the model staged: the double-residency the lock prevents)
_LIVE_CHILDREN: set = set()
# seconds spent WAITING for the chip lock this run: legitimate contention,
# not a wedge — main's watchdog extends its deadline by this
_LOCK_WAIT_TOTAL = [0.0]


class _chip_lock:
    """Exclusive cross-process lock around anything that stages a model on
    the chip. Two concurrent 8B residencies (the driver's end-of-round bench
    interleaving with the watcher's capture in the same healthy window)
    would OOM-wedge the backend for hours — the round-1/2/4 failure mode.
    Per-STAGE granularity so both holders make progress; falls through
    after ``timeout`` (measuring under contention beats not measuring)."""

    def __init__(self, timeout: float = 900.0):
        self._timeout = timeout
        self._fh = None

    def __enter__(self):
        import fcntl

        try:
            self._fh = open(CHIP_LOCK, "a+")
        except OSError as e:
            print(f"chip lock unavailable ({e}); proceeding UNLOCKED",
                  file=sys.stderr, flush=True)
            return self
        t0 = time.monotonic()
        while True:
            try:
                fcntl.flock(self._fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
                return self
            except OSError:
                if time.monotonic() - t0 > self._timeout:
                    print(f"chip lock not acquired in {self._timeout:.0f}s; "
                          f"proceeding UNLOCKED (contention beats silence)",
                          file=sys.stderr, flush=True)
                    return self
                time.sleep(2.0)

    def __exit__(self, *exc):
        if self._fh is not None:
            import fcntl

            try:
                fcntl.flock(self._fh, fcntl.LOCK_UN)
            except OSError:
                pass
            self._fh.close()
        return False


def run_stage(spec: str, budget: float) -> dict:
    """Run one stage in a subprocess with a hard kill at ``budget``
    (holding the chip lock: see _chip_lock)."""
    import threading
    from collections import deque

    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               DLLAMA_BENCH_CHILD_BUDGET=str(max(30.0, budget - 20.0)))
    rec: dict = {"phase": "spawn"}
    err_tail: deque = deque(maxlen=30)
    child = None
    threads: list = []
    t_lock = time.monotonic()
    with _chip_lock():
        # lock WAITING must not be charged to the wedge watchdog — the
        # accumulated wait extends the parent deadline (see main's watchdog)
        wait_s = time.monotonic() - t_lock
        _LOCK_WAIT_TOTAL[0] += wait_s
        if wait_s > 1.0:
            rec["lock_wait_s"] = round(wait_s, 1)
        child = subprocess.Popen(
            [sys.executable, os.path.join(here, "bench.py"), "--stage", spec],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=here)
        _LIVE_CHILDREN.add(child)

        def read_out():
            for line in child.stdout:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if "stage_result" in obj:
                    rec["result"] = obj["stage_result"]
                elif "phase" in obj:
                    rec["phase"] = obj["phase"]

        def read_err():  # drain: a full pipe would block the child
            for line in child.stderr:
                err_tail.append(line.rstrip())

        threads = [threading.Thread(target=read_out, daemon=True),
                   threading.Thread(target=read_err, daemon=True)]
        for th in threads:
            th.start()
        try:
            child.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            child.kill()
            rec["killed"] = f"stage killed at {budget:.0f}s budget"
            try:
                child.wait(timeout=10)  # reap; readers see EOF
            except subprocess.TimeoutExpired:
                pass
        finally:
            _LIVE_CHILDREN.discard(child)
    for th in threads:
        th.join(timeout=10)
    if "result" in rec:
        if "lock_wait_s" in rec and isinstance(rec["result"], dict):
            rec["result"]["lock_wait_s"] = rec["lock_wait_s"]
        return rec["result"]
    out = {"phase": rec.get("phase"),
           "error": rec.get("killed")
           or f"child rc={child.returncode} without a result"}
    if err_tail:
        out["stderr_tail"] = _tail("\n".join(list(err_tail)[-8:]))
    return out


def _make_sync():
    """Fetch-forced synchronization + the host round trip's floor.

    Returns ``(sync, rtt_s)``: ``sync(x)`` device_gets one element that
    data-depends on ``x`` (forcing every enqueued producer to actually run —
    see module docstring), and ``rtt_s`` is the median round-trip of such a
    fetch on an already-materialized buffer, to subtract once per timed
    region."""
    import jax
    import jax.numpy as jnp

    def sync(x):
        leaf = jax.tree_util.tree_leaves(x)[0]
        jax.device_get(jnp.ravel(leaf)[0])

    probe = jax.jit(lambda x: x + 1)(jnp.zeros((8,), jnp.int32))
    sync(probe)  # compile the ravel/index path
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        sync(probe)
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return sync, samples[2]


def _net(dt: float, rtt: float) -> float | None:
    """RTT-corrected region time, or None when the signal is smaller than
    the correction (a rate computed from it would be noise, not measurement
    — the round-1-3 failure mode this rework exists to kill)."""
    n = dt - rtt
    return n if n > rtt else None


# KV rows the post-prefill stages write (throwaways + decode + sampled +
# chunked + verify); prefill's position cycling stays below seq_len minus
# this so no stage writes past the cache. Stages that would still overrun
# (short-seq presets) are skipped with a row-budget check instead of
# silently clamping their writes onto stale tail rows.
_DECODE_REGION = 352


def bench_preset(preset: str, deadline: float, *, decode_steps: int = 64,
                 prefill_len: int = 256, batch: int = 1,
                 seq_len: int | None = None,
                 out: dict | None = None) -> dict:
    """Measure decode tok/s (+ prefill tok/s for batch=1) for one preset.

    ``out`` (when given) is filled INCREMENTALLY — including a ``phase``
    breadcrumb before every potentially-blocking jax call — so the watchdog's
    force-emitted JSON shows exactly where a wedged backend stopped
    (round-2's empty ``stages`` left that unanswerable)."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import forward
    from dllama_tpu.models.llama import greedy_step
    from dllama_tpu.runtime import KVCache

    out = {} if out is None else out
    out["phase"] = "budget_check"
    cfg = model_cfg(preset)
    if seq_len:
        from dataclasses import replace as _replace

        cfg = _replace(cfg, seq_len=seq_len)
    # record the quant numerics the stage ran so captures are attributable
    from dllama_tpu.ops.linear import quant_mode_label, turbo_mode

    out["quant_mode"] = quant_mode_label(cfg.compute_dtype == "bfloat16")
    out["weights"] = bench_weight_repr()
    if out["weights"] == "bf16" and turbo_mode() is not None:
        raise ValueError(
            "DLLAMA_BENCH_WEIGHTS=bf16 has no quantized planes to "
            "requantize — dense numerics would be mislabeled as turbo. "
            "If the turbo mode came from bench_promoted.json (the parent "
            "applies promotions), set DLLAMA_BENCH_NO_PROMO=1 for the "
            "dense-ceiling run")
    # pre-staging HBM guardrail (runtime.hbm): a preset that can't fit must
    # refuse HERE with a clean stage error — an OOM mid-staging wedges the
    # chip for hours (the round-1/2 outage; reference prints its own
    # required-memory estimate up front, nn-core.cpp:162-176)
    from dllama_tpu.runtime.hbm import check_budget, estimate_device_bytes

    _kv_map = {"bf16": jnp.bfloat16, "f8": jnp.float8_e4m3fn,
               "f32": jnp.float32}  # mirrors --kv-dtype (runtime/engine.py)
    kv_env = os.environ.get("DLLAMA_BENCH_KV", "bf16")
    if kv_env not in _kv_map:
        raise ValueError(
            f"DLLAMA_BENCH_KV must be one of {sorted(_kv_map)}, got {kv_env!r}")
    est = estimate_device_bytes(cfg, weight_repr=bench_weight_repr(),
                                kv_dtype_bytes=jnp.dtype(_kv_map[kv_env]).itemsize,
                                batch=batch)
    out["hbm_need_gb"] = round(est["need_per_device"] / 1024 ** 3, 2)
    limit = check_budget(est["need_per_device"], f"bench preset {preset}")
    if limit is not None:
        out["hbm_limit_gb"] = round(limit / 1024 ** 3, 2)

    out["phase"] = "params"
    sync, rtt = _make_sync()
    out["fetch_rtt_ms"] = round(1e3 * rtt, 1)
    params = device_random_params(cfg)
    jax.block_until_ready(params)  # staging is forced by the compile sync below
    if turbo_mode() is not None:
        # measure what the engine would serve: integer-dot planes (source
        # buffers freed leaf-by-leaf, same as the engine)
        from dllama_tpu.ops.turbo import turbo_params

        out["phase"] = "turbo_derive"
        params = turbo_params(params, a8=turbo_mode() == "a8")
        sync(params.layers.wq.w8)
    kv = KVCache.create(cfg, batch_size=batch, dtype=_kv_map[kv_env])

    step = jax.jit(forward, static_argnums=1, donate_argnums=(4,))
    greedy = jax.jit(greedy_step, static_argnums=1, donate_argnums=(4,))

    # prefill (chunked the way engine.prefill batches positions — the
    # production default's LARGEST bucket; the reference's fixed 32 would
    # idle the MXU)
    from dllama_tpu.runtime.engine import PREFILL_BUCKETS

    out["phase"] = "prefill_compile"
    # seq_len/4 cap keeps room for advancing measured chunks AND a decode
    # region after them on small presets (tiny: 256-seq -> 64-chunk)
    chunk = min(prefill_len, PREFILL_BUCKETS[0], cfg.seq_len // 4)
    prompt = jnp.ones((batch, chunk), dtype=jnp.int32)
    logits, kv = step(params, cfg, prompt, jnp.int32(0), kv)  # compile
    sync(logits)  # also warms the sync path for this shape
    if time.monotonic() > deadline:
        raise TimeoutError("deadline after prefill compile")
    # Measured dispatches advance positions like a real prefill (pos-0
    # repeats would let the flash kernel's causal block-skip drop the
    # attention over earlier chunks, inflating tok/s for multi-chunk
    # prompts). Enough dispatches ride one fetch to clear the RTT floor,
    # cycling through the positions the cache has; rows past
    # chunk*(cyc+1) stay free for the decode stages below.
    avail = cfg.seq_len // chunk - 1
    cyc = max(1, min(avail - 1, (cfg.seq_len - _DECODE_REGION) // chunk - 1))
    n_meas = 32
    out["phase"] = "prefill_measure"
    # one throwaway dispatch: the first dispatch after a compile absorbs
    # ~2 s of backlog even after a forced fetch (hw_probe, 2026-07-31)
    logits, kv = step(params, cfg, prompt, jnp.int32(chunk), kv)
    sync(logits)
    t0 = time.perf_counter()
    done = 0
    for i in range(n_meas):
        logits, kv = step(params, cfg, prompt,
                          jnp.int32(chunk * (1 + i % cyc)), kv)
        done += 1
        # enqueueing is cheap on TPU but each dispatch EXECUTES on the CPU
        # backend (bench self-test): respect the deadline mid-loop
        if done % 8 == 0 and time.monotonic() > deadline:
            break
    sync(logits)
    dt = _net(time.perf_counter() - t0, rtt)
    out["prefill_tok_per_s"] = round(batch * done * chunk / dt, 2) if dt else None
    pos = chunk * (cyc + 1)
    if done < n_meas:
        # deadline fired mid-prefill: stop HERE so the banked prefill number
        # reaches the parent (falling through to decode compile could eat
        # the child's kill headroom and lose the whole stage result)
        raise TimeoutError("deadline inside prefill measure")

    # decode (fused greedy step; token never leaves the device)
    out["phase"] = "decode_compile"
    token = jnp.ones((batch,), dtype=jnp.int32)
    token, kv = greedy(params, cfg, token[:, None], jnp.int32(pos), kv)  # compile
    sync(token)
    if time.monotonic() > deadline:
        raise TimeoutError("deadline after decode compile")
    out["phase"] = "decode_measure"
    pos += 1
    token, kv = greedy(params, cfg, token[:, None], jnp.int32(pos), kv)
    sync(token)  # throwaway: first-dispatch backlog (see prefill note)
    pos += 1
    t0 = time.perf_counter()
    for i in range(decode_steps):
        token, kv = greedy(params, cfg, token[:, None], jnp.int32(pos + i), kv)
    sync(token)
    dt = _net(time.perf_counter() - t0, rtt)
    out["decode_tok_per_s"] = round(batch * decode_steps / dt, 2) if dt else None
    out["decode_ms_per_step"] = round(1000.0 * dt / decode_steps, 3) if dt else None
    pos += decode_steps  # rows the loop above wrote

    # fused sampled decode (temperature/top-p on device, ops.sampling): the
    # serving path at temperature>0 — same dispatch budget as greedy
    if (batch == 1 and time.monotonic() < deadline
            and pos + 2 + max(8, decode_steps // 2) <= cfg.seq_len):
        from dllama_tpu.models.llama import sampled_step

        out["phase"] = "sampled_decode"
        sampled = jax.jit(sampled_step, static_argnums=1, donate_argnums=(4,))
        n = max(8, decode_steps // 2)
        token, kv = sampled(params, cfg, token[:, None], jnp.int32(pos), kv,
                            jnp.float32(0.8), jnp.float32(0.9), jnp.float32(0.5))
        sync(token)
        if time.monotonic() > deadline:
            return out  # keep the measured prefill/decode numbers
        pos += 1
        token, kv = sampled(params, cfg, token[:, None], jnp.int32(pos), kv,
                            jnp.float32(0.8), jnp.float32(0.9), jnp.float32(0.5))
        sync(token)  # throwaway
        pos += 1
        t0 = time.perf_counter()
        for i in range(n):
            token, kv = sampled(params, cfg, token[:, None],
                                jnp.int32(pos + i), kv, jnp.float32(0.8),
                                jnp.float32(0.9), jnp.float32(0.5))
        sync(token)
        dt = _net(time.perf_counter() - t0, rtt)
        out["sampled_decode_tok_per_s"] = round(n / dt, 2) if dt else None
        pos += n  # loop wrote rows [pos, pos + n); next free slot is pos + n

    # multi-step fused decode (decode_chunk): K tokens per dispatch — the
    # dispatch-overhead-free decode rate (engine --decode-chunk)
    if (batch == 1 and time.monotonic() < deadline
            and pos + 32 * (2 + max(1, decode_steps // 32)) <= cfg.seq_len):
        from dllama_tpu.models.llama import greedy_steps

        out["phase"] = "chunked_decode"
        gsteps = jax.jit(greedy_steps, static_argnums=(1, 5),
                         donate_argnums=(4,))
        K = 32
        toks, kv = gsteps(params, cfg, token, jnp.int32(pos), kv, K)  # compile
        sync(toks)
        if time.monotonic() > deadline:
            return out
        pos += K
        toks, kv = gsteps(params, cfg, toks[:, -1], jnp.int32(pos), kv, K)
        sync(toks)  # throwaway
        pos += K
        rounds = max(1, decode_steps // K)
        t0 = time.perf_counter()
        for r in range(rounds):
            toks, kv = gsteps(params, cfg, toks[:, -1], jnp.int32(pos + r * K),
                              kv, K)
        sync(toks)
        dt = _net(time.perf_counter() - t0, rtt)
        out["chunked_decode_tok_per_s"] = round(rounds * K / dt, 2) if dt else None

    # speculative verify cost: ms for a K=4 verify dispatch vs a plain decode
    # step. On an HBM-bound chip the ratio should approach 1.0 — that ratio
    # times the workload's acceptance rate is the --spec-lookup speedup.
    if (batch == 1 and time.monotonic() < deadline
            and pos + 5 * 19 <= cfg.seq_len):
        from dllama_tpu.models.llama import verify_step

        out["phase"] = "spec_verify"
        ver = jax.jit(verify_step, static_argnums=1, donate_argnums=(4,))
        vt = jnp.ones((1, 5), jnp.int32)
        _, preds0, kv = ver(params, cfg, vt, jnp.int32(pos), kv)  # compile
        sync(preds0)
        _, preds0, kv = ver(params, cfg, vt, jnp.int32(pos + 5), kv)
        sync(preds0)  # throwaway
        pos += 5
        if time.monotonic() < deadline:
            n = 16
            t0 = time.perf_counter()
            for i in range(n):
                n_acc, preds, kv = ver(params, cfg, vt,
                                       jnp.int32(pos + 5 * (i + 1)), kv)
            sync(preds)
            dt = _net(time.perf_counter() - t0, rtt)
            out["verify_k4_ms"] = round(1000.0 * dt / n, 3) if dt else None
            if out["verify_k4_ms"] and out.get("decode_ms_per_step"):
                out["verify_k4_over_decode"] = round(
                    out["verify_k4_ms"] / out["decode_ms_per_step"], 3)

    # paged decode (block-table KV, runtime/kvblocks.py): the continuous-
    # batching serving step measured on the SAME weights — one fused
    # dispatch through a block table, so the paged gather/kernel cost
    # becomes a ranked rate (and a roofline family below) instead of
    # staying invisible behind the --scenario path
    if batch == 1 and time.monotonic() < deadline:
        from dllama_tpu.models.llama import paged_forward
        from dllama_tpu.runtime.hbm import estimate_block_pool_bytes
        from dllama_tpu.runtime.kvblocks import PagedKVCache, blocks_per_seq

        out["phase"] = "paged_decode"
        bs_kv = 128
        m_blocks = blocks_per_seq(cfg.seq_len, bs_kv)
        kv_bytes = jnp.dtype(_kv_map[kv_env]).itemsize
        pool_bytes = estimate_block_pool_bytes(cfg, m_blocks + 1, bs_kv,
                                               kv_bytes)
        # the up-front guardrail priced weights + the DENSE cache only;
        # this stage's pool is extra residency, so it gets its own check
        # (conservative: the dense cache is deleted below but the probe
        # prices both) and a clean skip — never a mid-run OOM wedge
        try:
            check_budget(est["need_per_device"] + pool_bytes,
                         f"bench paged stage {preset}")
        except RuntimeError as e:
            out["paged_decode_skipped"] = str(e)[:200]
            out["phase"] = "done"
            return out
        del kv  # the dense pool: the paged stage holds its own
        pkv = PagedKVCache.create(cfg, n_blocks=m_blocks + 1,
                                  block_size=bs_kv, dtype=_kv_map[kv_env])
        tables = jnp.arange(1, m_blocks + 1, dtype=jnp.int32)[None, :]

        def paged_greedy(params, cfg, tokens, pos_vec, pkv, tables):
            logits, pkv = paged_forward(params, cfg, tokens, pos_vec, pkv,
                                        tables)
            return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), pkv

        pstep = jax.jit(paged_greedy, static_argnums=1, donate_argnums=(4,))
        ptok = jnp.ones((1,), jnp.int32)
        ptok, pkv = pstep(params, cfg, ptok[:, None],
                          jnp.zeros((1,), jnp.int32), pkv, tables)  # compile
        sync(ptok)
        if time.monotonic() < deadline:
            ptok, pkv = pstep(params, cfg, ptok[:, None],
                              jnp.ones((1,), jnp.int32), pkv, tables)
            sync(ptok)  # throwaway: first-dispatch backlog (see prefill note)
            n = max(8, decode_steps // 2)
            t0 = time.perf_counter()
            for i in range(n):
                ptok, pkv = pstep(params, cfg, ptok[:, None],
                                  jnp.full((1,), 2 + i, jnp.int32), pkv,
                                  tables)
            sync(ptok)
            dt = _net(time.perf_counter() - t0, rtt)
            out["paged_decode_tok_per_s"] = round(n / dt, 2) if dt else None
    out["phase"] = "done"
    return out


def _scn_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _pctl(sorted_vals: list, q: float):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def bench_continuous(deadline: float, *, out: dict | None = None) -> dict:
    """``--scenario continuous``: a mixed short/long staggered-arrival
    request stream through the paged continuous-batching scheduler
    (``--kv-block-size``, runtime/serving.PagedGenerator). The dense
    ``@b16`` stage measures raw batched dispatch rate on a full batch;
    this scenario measures what serving actually delivers under churn —
    sequences admit and retire mid-batch, chunked prefill interleaves
    with decode, and a third of the prompts share a 2-block prefix so
    block-level sharing is exercised. Reported fields (the ones
    tools/bench_compare.py diffs): aggregate ``agg_tok_per_s`` over the
    whole stream, TTFT percentiles (queue wait included — that IS the
    continuous-batching win), and block-pool occupancy/sharing peaks.

    The same wave then re-runs with speculative decoding on
    (``--spec-lookup``, runtime/serving.PagedGenerator's paged verify
    path) for a spec on/off A/B: ``accepted_tok_per_s`` (the spec-on
    wave's aggregate emitted tok/s — what acceptance actually bought),
    ``spec_accept_rate`` (accepted / drafted over the wave), and
    ``itl_p50_ms_delta`` (spec-on minus spec-off inter-token p50 —
    negative when speculation wins). tools/bench_compare.py ranks
    ``accepted_tok_per_s``; tools/perf_baseline.py guards it.

    A third wave exercises tiered KV memory (``--kv-host-blocks``): an
    idle/resume session stream over a device pool deliberately smaller
    than the sessions' combined KV, reporting ``sessions_per_chip``
    (idle sessions whose KV survived to resume via host spill +
    page-back) and ``resume_ttft_p95_ms`` — both ranked by
    tools/bench_compare.py and guarded by tools/perf_baseline.py
    (no_evidence until the next on-chip ``--baseline update``).

    Workload knobs (env): DLLAMA_BENCH_SCN_REQUESTS (24),
    DLLAMA_BENCH_SCN_SLOTS (4), DLLAMA_BENCH_KV_BLOCK (16),
    DLLAMA_BENCH_SCN_STAGGER (0.05 s), DLLAMA_BENCH_SCN_MAXTOK (16),
    DLLAMA_BENCH_SCN_SPEC (4 — the A/B's spec-lookup width),
    DLLAMA_BENCH_SCN_SESSIONS (10 — the tiered wave's session
    count)."""
    import shutil
    import tempfile
    import threading

    out = {} if out is None else out
    out["phase"] = "scenario_setup"
    here = os.path.dirname(os.path.abspath(__file__))
    # the scenario drives the REAL engine/scheduler stack, so it needs a
    # real .m/.t pair: synthesize the same tiny fixture the test tier uses
    sys.path.insert(0, os.path.join(here, "tests"))
    import numpy as np

    from helpers import (byte_vocab_tokenizer, tiny_header_params,
                         write_tiny_model)

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime import telemetry as tm
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    n_reqs = _scn_int("DLLAMA_BENCH_SCN_REQUESTS", 24)
    n_slots = _scn_int("DLLAMA_BENCH_SCN_SLOTS", 4)
    block = _scn_int("DLLAMA_BENCH_KV_BLOCK", 16)
    max_tok = _scn_int("DLLAMA_BENCH_SCN_MAXTOK", 16)
    stagger_s = float(os.environ.get("DLLAMA_BENCH_SCN_STAGGER", "0.05"))
    out.update(n_requests=n_reqs, n_slots=n_slots, kv_block_size=block)

    d = tempfile.mkdtemp(prefix="dllama-bench-scn-")
    try:
        mpath, tpath = os.path.join(d, "m.m"), os.path.join(d, "t.t")
        rng = np.random.default_rng(0xC0)
        write_tiny_model(mpath, tiny_header_params(
            dim=256, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=64, vocab_size=268, seq_len=256), rng)
        tfile.write_tfile(tpath, byte_vocab_tokenizer())

        # mixed workload: 1/3 long shared-prefix (RAG/system-prompt shape,
        # exercises block sharing + CoW), 1/3 short interactive, 1/3 long
        # distinct — arrivals staggered so admissions land mid-batch
        shared = [int(x) for x in rng.integers(1, 200, 2 * block)]
        prompts = []
        for i in range(n_reqs):
            if i % 3 == 0:
                prompts.append(shared
                               + [int(x) for x in rng.integers(1, 200, 48)])
            elif i % 3 == 1:
                prompts.append([int(x) for x in rng.integers(1, 200, 8)])
            else:
                prompts.append([int(x) for x in rng.integers(1, 200, 96)])

        out["phase"] = "scenario_engine"

        def wave(spec_k: int) -> dict:
            """One full staggered request wave through a fresh
            engine/scheduler at ``--spec-lookup=spec_k`` — the spec
            on/off A/B runs the IDENTICAL workload twice, so the two
            sides differ only in the verify path."""
            w: dict = {}
            eng = InferenceEngine(mpath, tpath, tp=1, kv_block_size=block,
                                  spec_lookup=spec_k)
            sched = BatchScheduler(eng, n_slots=n_slots)
            reg = tm.registry()
            g_total = reg.gauge(tm.KV_BLOCKS_TOTAL)
            g_used = reg.gauge(tm.KV_BLOCKS_USED)
            g_shared = reg.gauge(tm.KV_BLOCKS_SHARED)
            reuse = reg.counter(tm.PREFIX_REUSE_TOKENS)
            r0 = reuse.total()
            d0 = reg.counter(tm.SPEC_DRAFT_TOKENS).total()
            a0 = reg.counter(tm.SPEC_ACCEPTED_TOKENS).total()

            occ: list = []
            peaks = {"shared": 0.0}
            stop_sampling = threading.Event()

            def sample():
                while not stop_sampling.wait(0.05):
                    total = g_total.value() or 1
                    occ.append(g_used.value() / total)
                    peaks["shared"] = max(peaks["shared"],
                                          g_shared.value())

            sampler = threading.Thread(target=sample, daemon=True)
            sampler.start()

            t_sub: dict = {}
            t_toks: dict = {}  # per-request token stamps → real ITLs

            def mk_cb(i):
                def cb(tok, piece):
                    t_toks.setdefault(i, []).append(time.perf_counter())
                return cb

            try:
                t0 = time.perf_counter()
                reqs = []
                for i, ids in enumerate(prompts):
                    t_sub[i] = time.perf_counter()
                    reqs.append(sched.submit(ids, max_tok,
                                             stop_on_eos=False,
                                             on_token=mk_cb(i)))
                    time.sleep(stagger_s)
                for r in reqs:
                    if not r.done.wait(
                            timeout=max(5.0, deadline - time.monotonic())):
                        w["error"] = "deadline inside scenario wave"
                        break
                t_end = time.perf_counter()
            finally:
                stop_sampling.set()
                sampler.join(timeout=5)
                sched.close()
                eng.close()

            done = [r for r in reqs if r.done.is_set() and r.error is None]
            w["n_completed"] = len(done)
            w["n_tokens"] = sum(len(r.tokens) for r in done)
            errs = [r.error for r in reqs if r.error]
            if errs:
                w["request_errors"] = len(errs)
                w.setdefault("error", errs[0][:200])
            dt = t_end - t0
            if dt > 0 and w["n_tokens"]:
                w["agg_tok_per_s"] = round(w["n_tokens"] / dt, 2)
            ttfts = sorted(1e3 * (t_toks[i][0] - t_sub[i]) for i in t_toks)
            w["ttft_ms_p50"] = (round(_pctl(ttfts, 0.5), 1)
                                if ttfts else None)
            w["ttft_ms_p95"] = (round(_pctl(ttfts, 0.95), 1)
                                if ttfts else None)
            # real inter-token latencies from the callback stamps — the
            # A/B's headline latency side (speculation exists to shrink
            # exactly this number)
            itls = sorted(1e3 * (b - a) for ts in t_toks.values()
                          for a, b in zip(ts, ts[1:]))
            w["itl_p50_ms"] = round(_pctl(itls, 0.5), 2) if itls else None
            # latency attribution (runtime/flightrec): the scheduler-side
            # TTFT decomposition per completed request — the
            # continuous-batching throughput number, explained — plus the
            # decode-phase step/preempt/verify split
            attrib: dict = {"queue": [], "pagein": [], "admission": [],
                            "prefill": [], "first_decode": []}
            itl_attrib: dict = {"step": [], "preempt": [], "verify": []}
            rel_errs = []
            for i, r in enumerate(reqs):
                if not (r.done.is_set() and r.error is None):
                    continue
                bd = r.ttft_breakdown()  # the one phase formula (flightrec)
                if bd is None:
                    continue
                attrib["queue"].append(bd["queue_ms"])
                attrib["pagein"].append(bd["pagein_ms"])
                attrib["admission"].append(bd["admission_ms"])
                attrib["prefill"].append(bd["prefill_ms"])
                attrib["first_decode"].append(bd["first_decode_ms"])
                itl_attrib["step"].append(r.ms_decode_steps)
                itl_attrib["preempt"].append(r.ms_preempt)
                itl_attrib["verify"].append(r.ms_verify)
                # reassembly error vs the INDEPENDENTLY measured wall
                # TTFT — this wave's own perf_counter stamps (submit call
                # → first on_token callback), a different clock read at
                # different sites than the scheduler's attribution
                # stamps, so a broken accounting (a dropped phase, a
                # double-charge) shows up here
                if i in t_toks:
                    wall = 1e3 * (t_toks[i][0] - t_sub[i])
                    total = (bd["queue_ms"] + bd["pagein_ms"]
                             + bd["admission_ms"] + bd["prefill_ms"]
                             + bd["first_decode_ms"])
                    if wall > 0:
                        rel_errs.append(abs(total - wall) / wall)
            if attrib["queue"]:
                w["ttft_attrib_ms"] = {
                    k: round(sum(v) / len(v), 2) for k, v in attrib.items()}
                w["itl_attrib_ms"] = {
                    k: round(sum(v) / len(v), 2)
                    for k, v in itl_attrib.items()}
                # phases must reassemble the measured wall TTFT (the
                # ISSUE-7 acceptance bound is 5%; report the worst one)
                w["ttft_attrib_max_rel_err"] = (round(max(rel_errs), 4)
                                                if rel_errs else None)
            if occ:
                w["block_occupancy_peak"] = round(max(occ), 4)
                w["block_occupancy_mean"] = round(sum(occ) / len(occ), 4)
            w["kv_blocks_total"] = int(g_total.value())
            w["kv_blocks_shared_peak"] = int(peaks["shared"])
            w["prefix_reuse_tokens"] = int(reuse.total() - r0)
            drafted = reg.counter(tm.SPEC_DRAFT_TOKENS).total() - d0
            accepted = reg.counter(tm.SPEC_ACCEPTED_TOKENS).total() - a0
            if drafted:
                w["spec_drafted"] = int(drafted)
                w["spec_accepted"] = int(accepted)
                w["spec_accept_rate"] = round(accepted / drafted, 4)
            return w

        out["phase"] = "scenario_run"
        w_off = wave(0)
        out.update(w_off)
        # -- spec on/off A/B over the identical wave -----------------------
        spec_k = _scn_int("DLLAMA_BENCH_SCN_SPEC", 4)
        out["phase"] = "scenario_spec_on"
        w_on = wave(spec_k)
        out["spec_lookup"] = spec_k
        out["spec_ab"] = {
            "off": {k: w_off.get(k)
                    for k in ("agg_tok_per_s", "itl_p50_ms", "ttft_ms_p50",
                              "n_completed")},
            "on": {k: w_on.get(k)
                   for k in ("agg_tok_per_s", "itl_p50_ms", "ttft_ms_p50",
                             "n_completed", "spec_drafted",
                             "spec_accepted", "spec_accept_rate")},
        }
        if w_on.get("error"):
            out.setdefault("error", f"spec-on wave: {w_on['error']}"[:200])
        if w_on.get("agg_tok_per_s"):
            # the A/B's ranked throughput number: tok/s the spec-on wave
            # actually delivered (accepted drafts + verify emissions)
            out["accepted_tok_per_s"] = w_on["agg_tok_per_s"]
        if w_on.get("spec_accept_rate") is not None:
            out["spec_accept_rate"] = w_on["spec_accept_rate"]
        if (w_on.get("itl_p50_ms") is not None
                and w_off.get("itl_p50_ms") is not None):
            out["itl_p50_ms_delta"] = round(
                w_on["itl_p50_ms"] - w_off["itl_p50_ms"], 2)

        # -- tiered KV memory: idle/resume wave (--kv-host-blocks) ---------
        # The capacity shape the tier exists for: S sessions complete a
        # turn and go idle (their KV parks in the cached LRU), the
        # device pool is DELIBERATELY smaller than their combined KV so
        # cold blocks spill to the host mirror, then every session
        # resumes with its history + new text. Reported:
        # `sessions_per_chip` (idle sessions whose KV survived to
        # resume — a block-reuse hit on the resume prompt instead of a
        # full re-prefill) and `resume_ttft_p95_ms` (what a page-in
        # resume costs), both ranked by tools/bench_compare.py and
        # guarded by tools/perf_baseline.py.
        n_sessions = _scn_int("DLLAMA_BENCH_SCN_SESSIONS", 10)
        out["phase"] = "scenario_tiered"

        def tiered_wave() -> dict:
            w: dict = {}
            eng = InferenceEngine(mpath, tpath, tp=1, kv_block_size=block,
                                  kv_host_blocks=8 * n_sessions)
            # 2 slots -> a 2*table_width+1 device pool, well under the
            # sessions' combined KV (the point of the wave)
            sched = BatchScheduler(eng, n_slots=2)
            reg = tm.registry()
            reuse = reg.counter(tm.PREFIX_REUSE_TOKENS)
            spill = reg.counter(tm.KV_SPILL_BLOCKS)
            pagein = reg.counter(tm.KV_PAGEIN_BLOCKS)
            s0, p0 = spill.total(), pagein.total()
            srng = np.random.default_rng(0xC1)
            prompts = [[int(x) for x in srng.integers(1, 200, 4 * block + 4)]
                       for _ in range(n_sessions)]
            try:
                # turn 1: sessions run and retire (go idle)
                reqs = [sched.submit(p, 4, stop_on_eos=False)
                        for p in prompts]
                for r in reqs:
                    if not r.done.wait(
                            timeout=max(5.0, deadline - time.monotonic())):
                        w["error"] = "deadline inside tiered wave"
                        return w
                w["spill_blocks"] = int(spill.total() - s0)
                w["host_used_idle"] = int(
                    reg.gauge(tm.KV_BLOCKS_HOST_USED).value())
                # resumes: sequential so per-session reuse attributes
                hits = 0
                ttfts: list = []
                for i, p in enumerate(prompts):
                    r0 = reuse.total()
                    stamp: list = []
                    t_sub = time.perf_counter()
                    req = sched.submit(
                        p + [int(x) for x in srng.integers(1, 200, 8)],
                        4, stop_on_eos=False,
                        on_token=lambda _t, _p, s=stamp:
                            s.append(time.perf_counter()))
                    if not req.done.wait(
                            timeout=max(5.0, deadline - time.monotonic())):
                        w["error"] = "deadline inside resume wave"
                        return w
                    if req.error is None and reuse.total() - r0 >= block:
                        hits += 1  # KV survived idle: a retained session
                    if stamp:
                        ttfts.append(1e3 * (stamp[0] - t_sub))
                w["sessions_per_chip"] = hits
                w["pagein_blocks"] = int(pagein.total() - p0)
                if ttfts:
                    ttfts.sort()
                    w["resume_ttft_p50_ms"] = round(_pctl(ttfts, 0.5), 1)
                    w["resume_ttft_p95_ms"] = round(_pctl(ttfts, 0.95), 1)
                return w
            finally:
                sched.close()
                eng.close()

        tw = tiered_wave()
        out["tiered"] = tw
        if tw.get("sessions_per_chip") is not None:
            out["sessions_per_chip"] = tw["sessions_per_chip"]
        if tw.get("resume_ttft_p95_ms") is not None:
            out["resume_ttft_p95_ms"] = tw["resume_ttft_p95_ms"]
        if tw.get("error"):
            out.setdefault("error", f"tiered wave: {tw['error']}"[:200])
        out["phase"] = "done"
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_multichip(deadline: float, *, out: dict | None = None) -> dict:
    """``--scenario multichip``: the overlap/wire A/B on a ≥2-device mesh.

    Four engine configs over the same tiny fixture model — the cross of
    ``--comm-overlap {off,auto}`` × ``--wire {f32,q80}`` — each measured
    for greedy decode step time and then profiled for the Eval/Sync split
    and the EXPOSED collective wall (``dllama_comm_exposed_ms``: sync lane
    time not covered by concurrent compute — the quantity the overlapped
    ring merges exist to shrink; runtime/profiling.EvalSyncSplit). The
    per-config analytic wire bytes (qcollectives.wire_traffic_model) show
    the q80 wire's byte shrink next to the time numbers.

    Skip contract: fewer than 2 visible devices emits ``skipped: true`` +
    ``skip_reason`` (tools/bench_compare.py reads that as "no hardware",
    never a regression), the same first-class skip as a dead backend.

    Workload knobs (env): DLLAMA_BENCH_MC_STEPS (24 decode steps per
    config), DLLAMA_BENCH_MC_TP (tp width; default: largest power of two
    ≤ min(n_devices, 4) — the fixture has 4 heads)."""
    import shutil
    import tempfile

    out = {} if out is None else out
    out["phase"] = "scenario_setup"
    import jax

    n_dev = len(jax.devices())
    out["n_devices"] = n_dev
    if n_dev < 2:
        out["skipped"] = True
        out["skip_reason"] = (f"multichip scenario needs >= 2 devices, "
                              f"found {n_dev} (CPU mesh: XLA_FLAGS="
                              f"--xla_force_host_platform_device_count=8)")
        out["phase"] = "done"
        return out
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tests"))
    import numpy as np

    from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime.engine import InferenceEngine

    tp = _scn_int("DLLAMA_BENCH_MC_TP", 0)
    if tp <= 0:
        tp = 1
        while tp * 2 <= min(n_dev, 4):
            tp *= 2
    steps = _scn_int("DLLAMA_BENCH_MC_STEPS", 24)
    out.update(tp=tp, decode_steps=steps)

    d = tempfile.mkdtemp(prefix="dllama-bench-mc-")
    prev_wire = os.environ.get("DLLAMA_TPU_WIRE")
    try:
        mpath, tpath = os.path.join(d, "m.m"), os.path.join(d, "t.t")
        rng = np.random.default_rng(0xAB)
        write_tiny_model(mpath, tiny_header_params(
            dim=256, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=64, vocab_size=268, seq_len=256), rng)
        tfile.write_tfile(tpath, byte_vocab_tokenizer())

        ab: dict = {}
        tokens_by_cfg: dict = {}
        for overlap, wire in (("off", "f32"), ("auto", "f32"),
                              ("off", "q80"), ("auto", "q80")):
            key = f"overlap_{overlap}_{wire}"
            if time.monotonic() > deadline:
                ab[key] = {"error": "deadline before config ran"}
                continue
            out["phase"] = f"config_{key}"
            os.environ["DLLAMA_TPU_WIRE"] = wire
            eng = InferenceEngine(mpath, tpath, tp=tp,
                                  comm_overlap=overlap, temperature=0.0)
            try:
                res = eng.generate([1, 5, 9, 13], steps, stop_on_eos=False)
                n_pred = sum(s.n_tokens for s in res.steps
                             if s.kind == "pred")
                rec: dict = {
                    "n_chunks": eng.cfg.comm_overlap,
                    "decode_tok_per_s": round(res.pred_tok_per_s, 2),
                    "decode_ms_per_step": (round(res.pred_ms / n_pred, 3)
                                           if n_pred else None),
                    "wire_kb_per_token": round(sum(
                        b for _, _, b in eng._wire_traffic) / 1024.0, 3),
                    "wire_ops": sorted({f"{op}/{w}" for op, w, _
                                        in eng._wire_traffic}),
                }
                tokens_by_cfg[key] = res.tokens
                try:
                    split = eng.measure_split()
                    rec["sync_ms"] = round(split.sync_ms, 4)
                    rec["eval_ms"] = round(split.eval_ms, 4)
                    rec["comm_exposed_ms"] = round(split.exposed_ms, 4)
                except Exception as e:  # noqa: BLE001 — keep the rates
                    rec["split_error"] = f"{type(e).__name__}: {e}"[:200]
                ab[key] = rec
            finally:
                eng.close()
        out["ab"] = ab

        # the acceptance invariant, checked where the data is: the f32
        # wire's tokens must be identical overlap-on vs overlap-off
        if ("overlap_off_f32" in tokens_by_cfg
                and "overlap_auto_f32" in tokens_by_cfg):
            out["f32_tokens_identical"] = (
                tokens_by_cfg["overlap_off_f32"]
                == tokens_by_cfg["overlap_auto_f32"])

        # flat fields tools/bench_compare.py ranks
        auto_f32 = ab.get("overlap_auto_f32", {})
        off_f32 = ab.get("overlap_off_f32", {})
        auto_q80 = ab.get("overlap_auto_q80", {})
        if auto_f32.get("decode_tok_per_s"):
            out["decode_tok_per_s"] = auto_f32["decode_tok_per_s"]
        if auto_q80.get("decode_tok_per_s"):
            out["decode_tok_per_s_q80"] = auto_q80["decode_tok_per_s"]
        rates = [c.get("decode_tok_per_s") for c in ab.values()
                 if isinstance(c, dict) and c.get("decode_tok_per_s")]
        if rates:
            out["agg_tok_per_s"] = max(rates)
        if auto_f32.get("comm_exposed_ms") is not None:
            out["comm_exposed_ms"] = auto_f32["comm_exposed_ms"]
        if off_f32.get("comm_exposed_ms") is not None:
            out["comm_exposed_ms_off"] = off_f32["comm_exposed_ms"]
        if ("comm_exposed_ms" in out and "comm_exposed_ms_off" in out):
            out["exposed_overlap_lower"] = (
                out["comm_exposed_ms"] < out["comm_exposed_ms_off"])
        if (auto_q80.get("wire_kb_per_token") is not None
                and auto_f32.get("wire_kb_per_token")):
            out["wire_q80_shrink"] = round(
                auto_f32["wire_kb_per_token"]
                / max(1e-9, auto_q80["wire_kb_per_token"]), 2)
        out["phase"] = "done"
        return out
    finally:
        if prev_wire is None:
            os.environ.pop("DLLAMA_TPU_WIRE", None)
        else:
            os.environ["DLLAMA_TPU_WIRE"] = prev_wire
        shutil.rmtree(d, ignore_errors=True)


def bench_fleet(deadline: float, *, out: dict | None = None) -> dict:
    """``--scenario fleet``: staggered mixed traffic through the fleet
    router (serve/router.py) over N in-process api replicas — each a
    real engine + continuous-batching scheduler + HTTP server on a
    loopback port — with a mid-run replica kill and restart. This is
    the serving topology ROADMAP item 3 describes, measured the way the
    Gemma-on-Cloud-TPU comparison argues for: aggregate tok/s and tail
    TTFT *under churn*, not single-engine throughput. Reported fields
    (tools/bench_compare.py ranks the first three, the counters ride as
    context): ``agg_tok_per_s``, ``ttft_ms_p50``/``ttft_ms_p95``
    (measured at the client through the router, queue + dispatch
    included), and the router's retry/eject/shed counters proving the
    kill/restart schedule actually ran — plus the durable-streams
    verdict on the churn wave: ``streams_resumed`` (mid-stream deaths
    the failover spliced; the happy path is ``streams_resumed > 0,
    streams_dropped = 0``), ``streams_dropped`` (client-visible
    mid-stream errors that survived nothing), and ``resume_p95_ms``
    (detection → first continued token). The kill is aimed: the
    scenario waits (bounded) for a stream that has delivered its first
    chunk and kills the replica its session is bound to, so the death
    lands mid-stream — a pre-first-byte death is an ordinary retry hop
    and would leave the resume path unmeasured.

    After the churn wave, a TWO-TENANT CONTENTION wave runs against the
    restored fleet: a ``flooder`` tenant bursts every request at once
    while a lighter ``interactive`` tenant trickles in behind it, both
    named via ``X-Dllama-Tenant`` and fair-share-scheduled
    (runtime/tenancy weighted per-tenant FIFOs). Reported: per-tenant
    tok/s, queue-wait p95, and sheds under ``tenants``, plus
    ``jain_index`` — Jain's fairness over the wave's per-tenant token
    deltas (higher is better; a flooder that starves the interactive
    tenant drags it toward 0.5).

    Workload knobs (env): DLLAMA_BENCH_FLEET_REPLICAS (3),
    DLLAMA_BENCH_SCN_REQUESTS (18), DLLAMA_BENCH_SCN_MAXTOK (12),
    DLLAMA_BENCH_SCN_STAGGER (0.05 s), DLLAMA_BENCH_TENANT_HEAVY (10),
    DLLAMA_BENCH_TENANT_LIGHT (5).

    DLLAMA_BENCH_FLEET_DISAGG=1 switches the fleet to prefill/decode
    disaggregation: every replica runs the paged pool, replica 0 is
    tagged ``--role prefill``, and the router warms cold prefixes there
    before dispatching decode with an ``X-Dllama-KV-Peer`` pointer — so
    decode replicas pull KV over the checksummed Q80 wire instead of
    recomputing. The churn kill then lands on a DECODE replica (the
    scenario keeps its mid-run death, but the lone prefill stays up so
    the disaggregated path is measured, not just its absence). Extra
    reported fields: ``kv_migrations``/``kv_fallbacks`` (wire outcomes),
    ``kvwire_tx_bytes``/``kvwire_rx_bytes`` (wire volume),
    ``kvmigrate_ms_p50``/``p95`` (per-request TTFT attribution of the
    parked fetch, from the opt-in timing block)."""
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request
    from http.server import ThreadingHTTPServer

    out = {} if out is None else out
    out["phase"] = "scenario_setup"
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tests"))
    import numpy as np

    from helpers import (byte_vocab_tokenizer, tiny_header_params,
                         write_tiny_model)

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime import slo as slo_mod
    from dllama_tpu.runtime import telemetry as tm
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.serve.api import BatchedApiState, make_handler
    from dllama_tpu.serve.router import FleetRouter, make_router_handler

    n_replicas = _scn_int("DLLAMA_BENCH_FLEET_REPLICAS", 3)
    n_reqs = _scn_int("DLLAMA_BENCH_SCN_REQUESTS", 18)
    max_tok = _scn_int("DLLAMA_BENCH_SCN_MAXTOK", 12)
    stagger_s = float(os.environ.get("DLLAMA_BENCH_SCN_STAGGER", "0.05"))
    disagg = os.environ.get("DLLAMA_BENCH_FLEET_DISAGG", "") not in ("", "0")
    out.update(n_replicas=n_replicas, n_requests=n_reqs)
    if disagg:
        out["disagg"] = True

    d = tempfile.mkdtemp(prefix="dllama-bench-fleet-")
    engines: list = []
    servers: list = []
    states: list = []
    fleet = router_httpd = None
    try:
        mpath, tpath = os.path.join(d, "m.m"), os.path.join(d, "t.t")
        rng = np.random.default_rng(0xF1)
        write_tiny_model(mpath, tiny_header_params(
            dim=256, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=64, vocab_size=268, seq_len=256), rng)
        td = byte_vocab_tokenizer()
        td.chat_template = "<|start_header_id|>"  # detected as llama3
        tfile.write_tfile(tpath, td)

        def start_replica(i, port=0):
            # one real engine + batched scheduler + HTTP front per
            # replica — the same stack `python -m dllama_tpu api
            # --batch-slots 2` serves, minus the process boundary.
            # Disagg needs the paged pool on every replica (KV export
            # and import are both block-granular), and replica 0 is
            # the fleet's prefill tier.
            if i >= len(engines):
                engines.append(InferenceEngine(
                    mpath, tpath, tp=1,
                    kv_block_size=16 if disagg else 0))
            state = BatchedApiState(
                engines[i], n_slots=2,
                role="prefill" if disagg and i == 0 else None)
            httpd = ThreadingHTTPServer(("127.0.0.1", port),
                                        make_handler(state))
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            return state, httpd

        out["phase"] = "scenario_engines"
        for i in range(n_replicas):
            state, httpd = start_replica(i)
            states.append(state)
            servers.append(httpd)
        urls = [f"http://127.0.0.1:{s.server_address[1]}" for s in servers]

        out["phase"] = "scenario_router"
        # SLO objectives under which the scenario runs: deliberately
        # loose defaults (CPU-backend-safe — the bench asserts the
        # observatory machinery, the baseline tracks the numbers)
        slo_spec = os.environ.get(
            "DLLAMA_BENCH_SLO",
            "ttft_p95_ms=30000,itl_p50_ms=1000,shed_rate=0.5")
        fleet = FleetRouter(urls, probe_interval_s=0.2, eject_after=2,
                            backoff_min_s=0.2, backoff_max_s=1.0,
                            slo_objectives=slo_mod.parse_slo(slo_spec))
        router_httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                           make_router_handler(fleet))
        threading.Thread(target=router_httpd.serve_forever,
                         daemon=True).start()
        router_url = f"http://127.0.0.1:{router_httpd.server_address[1]}"
        reg = tm.registry()
        up = reg.gauge(tm.ROUTER_REPLICA_UP)
        t_wait = time.monotonic() + 30
        while time.monotonic() < t_wait and not all(
                up.value(replica=r.name) for r in fleet.replicas):
            time.sleep(0.05)
        retries0 = reg.counter(tm.ROUTER_RETRIES).total()
        ejects0 = reg.counter(tm.ROUTER_EJECTS).total()
        shed0 = reg.counter(tm.ROUTER_SHED).total()
        resumed0 = reg.counter(tm.ROUTER_STREAM_RESUMES).total(
            outcome="resumed")
        h_resume = reg.histogram(tm.ROUTER_STREAM_RESUME_MS)
        resume_n0 = h_resume.count()
        mig0 = reg.counter(tm.KVWIRE_MIGRATIONS).total(outcome="migrated")
        fb0 = reg.counter(tm.KVWIRE_MIGRATIONS).total(outcome="fallback")
        txb0 = reg.counter(tm.KVWIRE_TX_BYTES).total()
        rxb0 = reg.counter(tm.KVWIRE_RX_BYTES).total()
        if disagg:
            # the router must have probed the prefill tag before traffic
            # (otherwise the first wave silently measures non-disagg)
            t_wait = time.monotonic() + 15
            while time.monotonic() < t_wait and not any(
                    r.is_prefill() for r in fleet.replicas):
                time.sleep(0.05)
            out["prefill_probed"] = any(r.is_prefill()
                                        for r in fleet.replicas)

        out["phase"] = "scenario_traffic"
        results: dict = {}

        def do_request(i):
            t0 = time.perf_counter()
            stream = i % 2 == 0
            body = {"messages": [{"role": "user",
                                  "content": f"fleet bench {i % 6} "
                                             + "ab" * (i % 4)}],
                    "max_tokens": max_tok, "temperature": 0,
                    "stream": stream, "session_id": f"s{i}"}
            if disagg and not stream:
                body["timing"] = True  # carries kvmigrate_ms attribution
            # registered up front (and mutated in place) so the churn
            # choreography can see which requests are mid-flight
            rec: dict = {"t_sub": t0, "stream": stream}
            results[i] = rec
            try:
                req = urllib.request.Request(
                    router_url + "/v1/chat/completions",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    if stream:
                        raw = b""
                        while True:
                            chunk = r.read1(65536)
                            if not chunk:
                                break
                            if "t_first" not in rec \
                                    and b'"delta"' in raw + chunk:
                                rec["t_first"] = time.perf_counter()
                            raw += chunk
                        died = (b"upstream_error" in raw
                                or b'"finish_reason": "error"' in raw)
                        rec["midstream"] = died
                        rec["ok"] = b"[DONE]" in raw and not died
                        rec["tokens"] = (raw.count(b'"delta"')
                                         if rec["ok"] else 0)
                    else:
                        data = json.loads(r.read())
                        rec["t_first"] = time.perf_counter()
                        rec["ok"] = True
                        rec["tokens"] = data["usage"]["completion_tokens"]
                        kvms = data.get("timing", {}).get("kvmigrate_ms")
                        if kvms:
                            rec["kvmigrate_ms"] = kvms
            except urllib.error.HTTPError as e:
                rec.update(ok=False, status=e.code)
            except Exception as e:  # noqa: BLE001 — per-request forensics
                rec.update(ok=False, error=repr(e)[:120])
            rec["t_end"] = time.perf_counter()
            results[i] = rec

        kill_at = max(2, n_reqs // 3)
        restart_at = max(kill_at + 2, (2 * n_reqs) // 3)
        # disagg keeps the churn but aims it at a DECODE replica — the
        # lone prefill tier dying would just measure the (covered
        # elsewhere) no-prefill fallback instead of disaggregation
        ki = (n_replicas - 1) if disagg else 0
        idx_of = {u.split("//", 1)[1]: j for j, u in enumerate(urls)}
        threads: list = []
        t0 = time.perf_counter()
        for i in range(n_reqs):
            if time.monotonic() > deadline:
                out["error"] = "deadline inside traffic wave"
                break
            if i == kill_at:
                # the churn event: a replica dies mid-traffic — new
                # connections refused, its scheduler fails in-flight
                # work. Aim it MID-STREAM: wait (bounded) for a stream
                # that has delivered its first chunk and kill the
                # replica its session is bound to — a pre-first-byte
                # death is a plain retry hop, not a durable-stream
                # resume, and would leave the failover path unmeasured
                out["phase"] = "scenario_kill"
                t_aim = time.monotonic() + 30
                while time.monotonic() < min(t_aim, deadline):
                    with fleet._lock:
                        aff = {k: v.name
                               for k, v in fleet._affinity.items()}
                    live = [j for j, r in list(results.items())
                            if r.get("stream") and "t_first" in r
                            and "t_end" not in r
                            and f"sid:s{j}" in aff
                            and (not disagg
                                 or idx_of[aff[f"sid:s{j}"]] != 0)]
                    if live:
                        ki = idx_of[aff[f"sid:s{live[0]}"]]
                        break
                    time.sleep(0.02)
                servers[ki].shutdown()
                servers[ki].server_close()
                states[ki].close(drain_s=0.0)
            if i == restart_at:
                out["phase"] = "scenario_restart"
                state, httpd = start_replica(
                    ki, port=int(urls[ki].rsplit(":", 1)[1]))
                states[ki], servers[ki] = state, httpd
            th = threading.Thread(target=do_request, args=(i,))
            th.start()
            threads.append(th)
            time.sleep(stagger_s)
        for th in threads:
            th.join(timeout=max(5.0, deadline - time.monotonic()))
        t_end = time.perf_counter()

        out["phase"] = "scenario_report"
        done = [r for r in results.values() if r.get("ok")]
        out["n_completed"] = len(done)
        out["n_failed"] = sum(1 for r in results.values()
                              if not r.get("ok") and not r.get("midstream"))
        out["n_midstream_error"] = sum(1 for r in results.values()
                                       if r.get("midstream"))
        out["n_tokens"] = sum(r["tokens"] for r in done)
        dt = t_end - t0
        if dt > 0 and out["n_tokens"]:
            out["agg_tok_per_s"] = round(out["n_tokens"] / dt, 2)
        ttfts = sorted(1e3 * (r["t_first"] - r["t_sub"])
                       for r in done if "t_first" in r)
        out["ttft_ms_p50"] = round(_pctl(ttfts, 0.5), 1) if ttfts else None
        out["ttft_ms_p95"] = round(_pctl(ttfts, 0.95), 1) if ttfts else None
        out["router_retries"] = int(reg.counter(tm.ROUTER_RETRIES).total()
                                    - retries0)
        out["router_ejects"] = int(reg.counter(tm.ROUTER_EJECTS).total()
                                   - ejects0)
        out["router_shed"] = int(reg.counter(tm.ROUTER_SHED).total()
                                 - shed0)
        # durable streams under churn: the kill lands mid-stream, so
        # the router's failover must splice continuations — resumed
        # streams finish token-exactly (they count toward n_completed),
        # dropped ones surface as the client-visible mid-stream error
        out["streams_resumed"] = int(reg.counter(
            tm.ROUTER_STREAM_RESUMES).total(outcome="resumed") - resumed0)
        out["streams_dropped"] = out["n_midstream_error"]
        out["resume_p95_ms"] = (round(h_resume.quantile(0.95), 1)
                                if h_resume.count() > resume_n0 else None)
        if disagg:
            # wire outcomes + volume: what the disaggregation actually
            # moved instead of recomputing, and what fell back
            out["kv_migrations"] = int(reg.counter(
                tm.KVWIRE_MIGRATIONS).total(outcome="migrated") - mig0)
            out["kv_fallbacks"] = int(reg.counter(
                tm.KVWIRE_MIGRATIONS).total(outcome="fallback") - fb0)
            out["kvwire_tx_bytes"] = int(reg.counter(
                tm.KVWIRE_TX_BYTES).total() - txb0)
            out["kvwire_rx_bytes"] = int(reg.counter(
                tm.KVWIRE_RX_BYTES).total() - rxb0)
            kvms = sorted(r["kvmigrate_ms"] for r in done
                          if r.get("kvmigrate_ms"))
            out["kvmigrate_ms_p50"] = (round(_pctl(kvms, 0.5), 1)
                                       if kvms else None)
            out["kvmigrate_ms_p95"] = (round(_pctl(kvms, 0.95), 1)
                                       if kvms else None)
        # the restart's re-admission, telemetry-asserted: the breaker
        # must bring the killed replica back before the scenario ends
        t_wait = time.monotonic() + 15
        killed = fleet.replicas[ki].name
        while time.monotonic() < t_wait \
                and not up.value(replica=killed):
            time.sleep(0.1)
        out["readmitted"] = bool(up.value(replica=killed))
        # two-tenant contention wave: a flooding tenant bursts the
        # restored fleet while a light interactive tenant trickles in
        # behind it — fair-share admission (weighted per-tenant FIFOs,
        # runtime/tenancy) must keep the light tenant served. In-process
        # fleet means ONE shared tenant registry across the router and
        # every replica, so per-tenant totals are read directly.
        # Reported: per-tenant tok/s + queue-wait p95 + sheds, and
        # ``jain_index`` — Jain's fairness over the wave's per-tenant
        # decode-token deltas (1.0 = served proportionally to demand;
        # a starved light tenant drags it toward 1/n). Knobs:
        # DLLAMA_BENCH_TENANT_HEAVY (10) / DLLAMA_BENCH_TENANT_LIGHT (5).
        out["phase"] = "scenario_tenants"
        from dllama_tpu.runtime import tenancy as tn
        treg = tn.registry()
        treg.set_limits(tn.parse_limits(
            {"flooder": {"weight": 1.0},
             "interactive": {"weight": 4.0}}))
        n_heavy = _scn_int("DLLAMA_BENCH_TENANT_HEAVY", 10)
        n_light = _scn_int("DLLAMA_BENCH_TENANT_LIGHT", 5)
        snap0 = treg.snapshot()["tenants"]
        tok0 = {t: st.get("decode_tokens", 0)
                for t, st in snap0.items()}
        t_results: dict = {}

        def tenant_request(tag, tenant, i):
            rec: dict = {"t_sub": time.perf_counter()}
            t_results[tag] = rec
            body = {"messages": [{"role": "user",
                                  "content": f"tenant {tenant} wave {i}"}],
                    "max_tokens": max_tok, "temperature": 0,
                    "stream": False}
            try:
                req = urllib.request.Request(
                    router_url + "/v1/chat/completions",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json",
                             "X-Dllama-Tenant": tenant})
                with urllib.request.urlopen(req, timeout=120) as r:
                    json.loads(r.read())
                    rec["ok"] = True
            except Exception as e:  # noqa: BLE001 — per-request forensics
                rec.update(ok=False, error=repr(e)[:120])
            rec["t_end"] = time.perf_counter()

        t_threads: list = []
        tw0 = time.perf_counter()
        for i in range(n_heavy):  # the flood: all at once
            th = threading.Thread(target=tenant_request,
                                  args=(f"h{i}", "flooder", i))
            th.start()
            t_threads.append(th)
        for i in range(n_light):  # the interactive trickle
            th = threading.Thread(target=tenant_request,
                                  args=(f"l{i}", "interactive", i))
            th.start()
            t_threads.append(th)
            time.sleep(stagger_s)
        for th in t_threads:
            th.join(timeout=max(5.0, deadline - time.monotonic()))
        tw = time.perf_counter() - tw0
        snap1 = treg.snapshot()["tenants"]
        tenant_toks: dict = {}
        out["tenants"] = {}
        for tenant in ("flooder", "interactive"):
            st = snap1.get(tenant, {})
            toks = st.get("decode_tokens", 0) - tok0.get(tenant, 0)
            tenant_toks[tenant] = toks
            qw = st.get("queue_wait_ms", {})
            out["tenants"][tenant] = {
                "tok_per_s": round(toks / tw, 2) if tw > 0 else None,
                "queue_wait_ms_p95": (round(qw["p95"], 1)
                                      if qw.get("n") else None),
                "sheds": sum(st.get("sheds", {}).values())}
        out["jain_index"] = round(
            tn.jain_index(tenant_toks.values()), 4)
        # the SLO observatory's verdict on the run: per-objective
        # compliance + worst burn, plus the two flat fields the
        # compare/baseline tools rank (slo_compliance_min: 1.0 = every
        # objective met, 0.0 = at least one violated; slo_worst_burn:
        # the hottest error-budget burn across objectives × windows)
        ev = fleet.slo.evaluate()
        out["slo"] = {
            name: {"threshold": rec["threshold"],
                   "estimate": round(rec["estimate"], 4),
                   "compliant": rec["compliant"],
                   "burn": {w: round(b, 3)
                            for w, b in rec["burn"].items()}}
            for name, rec in ev["objectives"].items()}
        out["slo_compliance_min"] = min(
            (1.0 if rec["compliant"] else 0.0)
            for rec in ev["objectives"].values())
        out["slo_worst_burn"] = round(max(
            max(rec["burn"].values())
            for rec in ev["objectives"].values()), 3)
        out["phase"] = "done"
        return out
    finally:
        if router_httpd is not None:
            router_httpd.shutdown()
            router_httpd.server_close()
        if fleet is not None:
            fleet.close()
        for httpd in servers:
            try:
                httpd.shutdown()
                httpd.server_close()
            except OSError:
                pass  # the killed replica's server is already closed
        for state in states:
            state.close(drain_s=0.0)
        for eng in engines:
            eng.close()
        shutil.rmtree(d, ignore_errors=True)


def bench_eval(deadline: float, *, out: dict | None = None) -> dict:
    """``--scenario eval``: the quality observatory's throughput-and-
    parity scenario. Scores the committed fixture
    (tests/goldens/eval_tiny.jsonl) teacher-forced through the REAL
    serving stack (runtime/evalharness) under every config in
    telemetry.EVAL_CONFIGS — the engine oracle plus dense/paged/
    paged_spec continuous batching — and reports, per config,
    ``eval_tok_per_s`` (scored positions per wall second; ranked
    higher-better by tools/bench_compare.py) beside ``perplexity``
    (ranked lower-better) and the bit-exact ``total_nll_hex``. The
    headline carries the batched ``eval_tok_per_s`` and a
    ``parity_drift`` flag: any exact-parity pair (telemetry.EVAL_PARITY)
    whose totals differ bit-from-bit is a numerics bug, not a quality
    tradeoff, and tools/bench_compare.py calls it out as such.

    Workload knobs (env): DLLAMA_BENCH_SCN_SLOTS (4),
    DLLAMA_BENCH_KV_BLOCK (16)."""
    import shutil
    import tempfile

    out = {} if out is None else out
    out["phase"] = "scenario_setup"
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tests"))
    import numpy as np

    from helpers import (byte_vocab_tokenizer, tiny_header_params,
                         write_tiny_model)

    from dllama_tpu.formats import tfile
    from dllama_tpu.runtime import evalharness
    from dllama_tpu.runtime import telemetry as tm
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    n_slots = _scn_int("DLLAMA_BENCH_SCN_SLOTS", 4)
    block = _scn_int("DLLAMA_BENCH_KV_BLOCK", 16)
    out.update(n_slots=n_slots, kv_block_size=block, dataset="eval_tiny")
    seqs = evalharness.load_dataset(
        os.path.join(here, "tests", "goldens", "eval_tiny.jsonl"))
    out["n_seqs"] = len(seqs)

    d = tempfile.mkdtemp(prefix="dllama-bench-eval-")
    try:
        mpath, tpath = os.path.join(d, "m.m"), os.path.join(d, "t.t")
        rng = np.random.default_rng(0xC0)
        write_tiny_model(mpath, tiny_header_params(
            dim=256, hidden_dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=64, vocab_size=268, seq_len=256), rng)
        tfile.write_tfile(tpath, byte_vocab_tokenizer())

        out["phase"] = "scenario_eval"
        configs: dict = {}
        for config in tm.EVAL_CONFIGS:
            kw = {}
            if config in ("paged", "paged_spec"):
                kw["kv_block_size"] = block
            if config == "paged_spec":
                kw["spec_lookup"] = 4
            eng = InferenceEngine(mpath, tpath, tp=1, **kw)
            sched = None
            try:
                if config == "single":
                    run = evalharness.run_eval(seqs, dataset="eval_tiny",
                                               config=config, engine=eng)
                else:
                    sched = BatchScheduler(eng, n_slots=n_slots)
                    run = evalharness.run_eval(seqs, dataset="eval_tiny",
                                               config=config, sched=sched)
            finally:
                if sched is not None:
                    sched.close()
                eng.close()
            configs[config] = {k: run[k] for k in (
                "n_tokens", "perplexity", "total_nll_hex",
                "eval_tok_per_s", "wall_s")}
        out["configs"] = configs
        # the ranked numbers: batched eval throughput (paged — the config
        # production promotion would run) and the dataset perplexity
        out["eval_tok_per_s"] = configs["paged"]["eval_tok_per_s"]
        out["perplexity"] = round(configs["paged"]["perplexity"], 6)
        out["total_nll_hex"] = configs["paged"]["total_nll_hex"]
        out["parity_drift"] = any(
            configs[a]["total_nll_hex"] != configs[b]["total_nll_hex"]
            for a, b in tm.EVAL_PARITY
            if a in configs and b in configs)
        out["phase"] = "done"
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


SCENARIOS = ("continuous", "multichip", "fleet", "eval")
SCENARIO_FNS = {"continuous": bench_continuous, "multichip": bench_multichip,
                "fleet": bench_fleet, "eval": bench_eval}


def _result_skeleton(metric: str) -> dict:
    """The one-line emit contract's required fields + the git stamp —
    shared by main() and scenario_main so the shape cannot drift."""
    result: dict = {
        "metric": metric,
        "value": 0.0,
        "unit": "tok/s",
        "vs_baseline": 0.0,
        "error": None,
    }
    try:
        result["git"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:  # noqa: BLE001 — traceability only
        result["git"] = None
    return result


def _mark_skipped(result: dict, detail: str, attempts: list,
                  t_start: float) -> None:
    """Stamp the first-class skip contract (no live measurement ran —
    tools/bench_compare.py must read this as 'no hardware', never as a
    regression) — shared by every no-backend emit path."""
    result["skipped"] = True
    result["skip_reason"] = f"backend unavailable: {detail}"
    result["error"] = f"backend unavailable: {detail}"
    result["probe_attempts"] = attempts
    result["elapsed_s"] = round(time.monotonic() - t_start, 1)


def _stage_cache_env() -> None:
    """Persistent XLA compile cache for the measurement children —
    amortizes compiles across stages and across bench runs."""
    from dllama_tpu.compile_cache import enable  # stdlib-only: no jax here

    enable()


def scenario_main(name: str) -> None:
    """``bench.py --scenario <name>`` entry: probe the backend, run the
    serving scenario in an isolated stage child (same wedge containment as
    the preset stages), and print exactly ONE JSON line whose per-stage
    fields tools/bench_compare.py knows how to diff."""
    t_start = time.monotonic()
    result = _result_skeleton("eval_tok_per_s" if name == "eval"
                              else f"{name}_agg_tok_per_s")
    if name not in SCENARIOS:
        result["error"] = f"unknown scenario {name!r} (have {SCENARIOS})"
        emit(result)
        return

    force_platform = os.environ.get("DLLAMA_BENCH_PLATFORM")
    if force_platform:
        os.environ["JAX_PLATFORMS"] = force_platform
    attempts: list = []
    ok, detail = probe_backend(force_platform, attempts)
    if not ok:
        _mark_skipped(result, detail, attempts, t_start)
        emit(result)
        return
    try:
        info = json.loads(detail)
    except (ValueError, IndexError):
        info = {"platform": "unknown", "kind": "unknown", "n": 0}
    result["platform"] = info.get("platform")
    result["device_kind"] = info.get("kind")
    _stage_cache_env()
    if (name == "multichip" and info.get("platform") == "cpu"
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # the CPU backend exposes ONE device by default; the multichip A/B
        # needs a mesh — give the stage child the 8-device virtual mesh
        # the test tier uses (a real TPU slice is unaffected)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_"
                                     "count=8").strip()

    res = run_stage(name, STAGE_DEADLINE_S)
    result["stages"] = {name: res}
    if res.get("skipped"):
        # the scenario itself declared a first-class skip (e.g. a single
        # device): propagate it so comparisons read "no hardware"
        result["skipped"] = True
        result["skip_reason"] = res.get("skip_reason")
        result["error"] = res.get("skip_reason")
    elif res.get("agg_tok_per_s"):
        result["value"] = res["agg_tok_per_s"]
    elif res.get("eval_tok_per_s"):
        # the eval scenario's headline is scored positions per second
        result["value"] = res["eval_tok_per_s"]
    else:
        result["error"] = res.get("error", "scenario did not measure")
    result["elapsed_s"] = round(time.monotonic() - t_start, 1)
    emit(result)


def _find_fallback_capture():
    """Newest VALID banked capture, for emitting when the live chip is down.

    The round-4 failure this guards against: the chip wedged hours before the
    driver's end-of-round bench run, so that round's record held only dead
    probes even though a clean fetch-forced capture existed on disk.  Search
    order: captures under bench_results/capture_*/, newest first, then
    committed BENCH_r*_manual.json snapshots.  A capture is valid iff

    * its directory has no ``INVALID`` marker (rounds 1-3 enqueue-rate
      captures are marked),
    * it is not itself a fallback emission (no recursive staleness), and
    * at least one stage carries BOTH ``fetch_rtt_ms`` (proof the
      fetch-forced methodology produced it) and a measured decode number, and
    * its top-level headline ``value`` is nonzero (a capture whose headline
      stage failed is passed over for an older one that measured).

    Returns ``(data, path)`` or ``None``."""
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    cands = []
    for p in glob.glob(os.path.join(
            here, "bench_results/capture_*/BENCH_live.json")):
        if not os.path.exists(os.path.join(os.path.dirname(p), "INVALID")):
            cands.append(p)
    # capture dirs are named capture_<utc-ts>: the name sorts by time
    cands.sort(key=lambda p: os.path.basename(os.path.dirname(p)),
               reverse=True)
    def _round_no(p: str) -> int:
        # BENCH_r<NN>_manual.json — numeric sort (lexicographic would rank
        # r9 above r10)
        import re

        m = re.search(r"BENCH_r(\d+)", os.path.basename(p))
        return int(m.group(1)) if m else -1

    cands += sorted(glob.glob(os.path.join(here, "BENCH_r*_manual.json")),
                    key=_round_no, reverse=True)
    for p in cands:
        try:
            with open(p) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(data, dict) or "fallback" in data:
            continue
        stages = data.get("stages") or {}
        if not any(isinstance(s, dict) and s.get("fetch_rtt_ms")
                   and s.get("decode_tok_per_s") for s in stages.values()):
            continue
        if data.get("value"):
            return data, p
    return None


def main() -> None:
    t_start = time.monotonic()
    result = _result_skeleton("decode_tok_per_s_llama8b_q40_1chip")

    force_platform = os.environ.get("DLLAMA_BENCH_PLATFORM")  # e.g. "cpu" self-test
    if force_platform:
        os.environ["JAX_PLATFORMS"] = force_platform

    attempts: list = []
    ok, detail = probe_backend(force_platform, attempts)
    if not ok:
        # late-window retry: the round-2 hang looked like a transient
        # backend-side lock; give the chip one more chance after a long wait
        wait = min(300.0, max(0.0, STAGE_DEADLINE_S / 2))
        time.sleep(wait)
        info = probe_once(force_platform, attempts)
        if info is not None:
            ok, detail = True, info
    if not ok:
        fb = _find_fallback_capture()
        if fb is not None:
            data, path = fb
            here = os.path.dirname(os.path.abspath(__file__))
            # first-class skip marker: the LIVE measurement did not run —
            # the numbers below are a re-emitted banked capture, so a
            # comparison tool must read this as "no hardware", never as a
            # regression or an improvement (tools/bench_compare.py)
            data["skipped"] = True
            data["skip_reason"] = (f"backend unavailable: {detail}; "
                                   f"re-emitting banked capture "
                                   f"{os.path.relpath(path, here)}")
            data["fallback"] = {
                "source": os.path.relpath(path, here),
                "live_probe_error": detail,
                "probe_attempts": attempts,
                "note": ("backend unavailable at bench time; emitting the "
                         "newest valid fetch-forced capture banked "
                         "earlier (VERDICT r4 next #4)"),
            }
            data["elapsed_s"] = round(time.monotonic() - t_start, 1)
            emit(data)
            return
        _mark_skipped(result, detail, attempts, t_start)
        result["env"] = {
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
            "accel_devices": sorted(
                f for f in os.listdir("/dev") if f.startswith(("accel", "vfio"))
            ) if os.path.isdir("/dev") else [],
        }
        emit(result)
        return

    try:
        info = json.loads(detail)
    except (ValueError, IndexError):
        info = {"platform": "unknown", "kind": "unknown", "n": 0}
    result["platform"] = info.get("platform")
    result["device_kind"] = info.get("kind")
    if len(attempts) > 1:  # flaky init is itself a finding worth recording
        result["probe_attempts"] = attempts

    # the parent stays jax-free: every measurement runs in a --stage child
    # (stage_child re-pins jax_platforms there)
    _stage_cache_env()

    # promoted serving config (tools/promote_config.py, written when an
    # on-chip A/B showed a combo beating `auto` by >=10%): apply its env
    # knobs to the measurement children, with full provenance in the line.
    # Explicitly-set env vars win — a sweep/debug run isn't overridden.
    promo_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "bench_promoted.json")
    if os.environ.get("DLLAMA_BENCH_NO_PROMO"):
        promo_path = ""  # isolation runs (e.g. the f8-KV twin) opt out
    if promo_path and os.path.exists(promo_path):
        try:
            with open(promo_path) as f:
                promo = json.load(f)
            applied = {}
            for var, val in (promo.get("env") or {}).items():
                if var not in os.environ:
                    os.environ[var] = str(val)
                    applied[var] = str(val)
            result["promoted_config"] = {
                "combo": promo.get("combo"), "applied_env": applied,
                "evidence": promo.get("evidence")}
        except (OSError, ValueError) as e:
            result["promoted_config"] = {"error": f"{type(e).__name__}: {e}"}

    on_tpu = info.get("platform") == "tpu"
    tflops, gbps = detect_specs(str(info.get("kind", "")))

    # 1b FIRST: the cheap preset banks a real number before the 8B shape —
    # which once OOM-wedged the chip for the rest of the window — ever runs.
    specs = ["1b", "8b", "8b@b16", "1b@s8k"] if on_tpu else ["tiny"]
    if os.environ.get("DLLAMA_BENCH_PRESET"):
        specs = os.environ["DLLAMA_BENCH_PRESET"].split(",")
    bad = [s for s in specs
           if s.partition("@")[0] not in PRESETS
           or s.partition("@")[2] not in ("", "b16", "s8k")]
    if bad:
        result["error"] = f"unknown preset(s) {bad}"
        emit(result)
        return

    # the window scales with the stage list: one STAGE_DEADLINE_S covers the
    # first stage (probe + compiles dominate it) and each further stage adds
    # headroom, so a slow early stage can't silently starve the later ones
    deadline = (t_start + PROBE_TIMEOUT_S + STAGE_DEADLINE_S
                + 300.0 * max(0, len(specs) - 1))

    # Watchdog: the per-stage deadline checks can't fire while blocked INSIDE
    # a jax call (backend init / compile hang — the exact round-1 failure).
    # A daemon timer force-emits the JSON line and exits 0 at the deadline.
    import threading

    _wd_done = threading.Event()

    def _watchdog():
        # poll instead of a fixed Timer: time spent WAITING on the chip
        # lock (legitimate contention with a concurrent capture, not a
        # wedge) extends the effective deadline
        while not _wd_done.wait(10.0):
            if time.monotonic() > deadline + _LOCK_WAIT_TOTAL[0] + 60:
                break
        if _wd_done.is_set():
            return
        # kill in-flight stage children FIRST: os._exit releases the chip
        # lock while an orphan would keep its model staged — the exact
        # double-residency wedge the lock exists to prevent
        for ch in list(_LIVE_CHILDREN):
            try:
                ch.kill()
            except Exception:  # noqa: BLE001
                pass
        try:
            result.setdefault("stages", {})
            result["error"] = (result.get("error")
                               or f"watchdog: exceeded {STAGE_DEADLINE_S}s inside a stage")
            result["elapsed_s"] = round(time.monotonic() - t_start, 1)
            # deep-copy first: the main thread mutates the shared stage dicts
            # and a mid-encode mutation must not kill the line we exist to emit
            try:
                snapshot = json.loads(json.dumps(result, default=str))
            except Exception:  # noqa: BLE001
                snapshot = {"metric": result.get("metric"), "value": 0.0,
                            "unit": "tok/s", "vs_baseline": 0.0,
                            "error": result.get("error")}
            emit(snapshot)
        finally:
            os._exit(0)

    wd = threading.Thread(target=_watchdog, daemon=True)
    wd.start()

    stages: dict = {}
    result["stages"] = stages  # shared upfront: the watchdog emits partials
    for spec in specs:
        remaining = deadline - time.monotonic()
        if remaining < 60:
            stages[spec] = {"error": "window exhausted before stage ran"}
            continue
        base = spec.partition("@")[0]
        if ("@" in spec and base in stages
                and "decode_tok_per_s" not in stages[base]):
            # the base preset ran THIS invocation and failed — don't repeat
            # the failure at batch 16 (an explicit @b16-only run still runs)
            stages[spec] = {"error": "skipped: base preset did not measure"}
            continue
        stages[spec] = run_stage(spec, min(STAGE_DEADLINE_S, remaining))

    # headline preference: the 8B BASELINE shape when it measured, else the
    # largest preset that did (a banked 1b number beats a zero)
    head = next((s for s in ("8b", "1b", "tiny")
                 if stages.get(s, {}).get("decode_tok_per_s")),
                specs[0].partition("@")[0])
    head_res = stages.get(head, {})
    n_params = matmul_param_count(head)
    # bytes/weight by the measured representation (the stage records it):
    # Q40 planes = 1B codes + f32/32 scales; bf16 dense = 2B
    wrepr = head_res.get("weights", "q40")
    weight_gb = n_params * (2.0 if wrepr == "bf16" else 1 + 4 / 32) / 1e9
    if head_res.get("decode_tok_per_s"):
        v = head_res["decode_tok_per_s"]
        result["value"] = v
        result["metric"] = f"decode_tok_per_s_llama{head}_{wrepr}_1chip"
        result["vs_baseline"] = round(v / NORTH_STAR_TOK_S, 4)
        # roofline + efficiency context: the ceilings come from the hw_probe
        # file when one exists (honest measured silicon) and the nameplate
        # table otherwise — the section names its source either way
        # (runtime/roofline, loaded jax-free by file path)
        roofmod = _roofline_mod()
        ceil = roofmod.load_ceilings(device_kind=str(info.get("kind", "")))
        result["roofline"] = roofmod.rate_roofline(v, weight_gb, ceil)
        # per program-FAMILY fractions (decode vs prefill vs paged): the
        # paged family prices the same weight stream, so its lower
        # fraction IS the visible cost of the block-table gather/kernel
        result["roofline"]["families"] = roofmod.rate_roofline_families(
            head_res, weight_gb, n_params, ceil)
        # legacy flat fields (older captures carry these; same numbers as
        # the section, nameplate-based)
        result["roofline_decode_tok_per_s"] = round(gbps / weight_gb, 1)
        result["hbm_util_decode"] = round(v * weight_gb / gbps, 4)
        if head_res.get("prefill_tok_per_s"):
            result["prefill_mfu"] = round(
                head_res["prefill_tok_per_s"] * 2 * n_params / (tflops * 1e12), 4)
    else:
        result["error"] = head_res.get("error", "no result")

    # chip is alive: spend any remaining window on the @pytest.mark.tpu tier
    # (the error-bound claims that have never run on hardware) and embed the
    # outcome — VERDICT round-2 next #1.
    if on_tpu and time.monotonic() < deadline and not result.get("error"):
        budget = min(420.0, deadline + 120 - time.monotonic())
        try:
            env = dict(os.environ, DLLAMA_TESTS_TPU="1")
            env.pop("JAX_PLATFORMS", None)
            env.pop("XLA_FLAGS", None)
            t_lk = time.monotonic()
            with _chip_lock():  # the tier stages real models on the chip
                _LOCK_WAIT_TOTAL[0] += time.monotonic() - t_lk
                tp = subprocess.run(
                    [sys.executable, "-m", "pytest", "tests", "-m", "tpu", "-q",
                     "--no-header", "-p", "no:cacheprovider"],
                    capture_output=True, timeout=budget,
                    cwd=os.path.dirname(os.path.abspath(__file__)), env=env)
            result["tpu_test_tier"] = {
                "rc": tp.returncode,
                "tail": _tail(tp.stdout)[-400:],
            }
        except subprocess.TimeoutExpired as e:
            result["tpu_test_tier"] = {"rc": None, "timeout_s": budget,
                                       "tail": _tail(e.stdout)[-400:]}
        except Exception as e:  # noqa: BLE001
            result["tpu_test_tier"] = {"rc": None, "tail": f"{type(e).__name__}: {e}"}

    result["elapsed_s"] = round(time.monotonic() - t_start, 1)
    _wd_done.set()
    emit(result)


def baseline_main(argv: list) -> int:
    """``bench.py --baseline {check,update}``: the perf-regression
    sentinel (tools/perf_baseline.py) wrapped around a bench run.

    Without ``--result FILE`` the bench runs live in a SUBPROCESS (main's
    watchdog force-exits its process on a wedge — the comparison must
    survive that) and its one emitted JSON line is the comparison side.
    ``check`` exits 1 naming every regressed metric; a skipped run or a
    run with no overlapping metrics is first-class NO EVIDENCE and exits
    0 (so ``make perf-check`` stays green on hardware-less runners
    without pretending it verified anything). ``update`` records the
    result as the new ``PERF_BASELINE.json``."""
    import argparse

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    import perf_baseline

    ap = argparse.ArgumentParser(prog="bench.py --baseline")
    ap.add_argument("mode", choices=("check", "update"))
    ap.add_argument("--result", default=None,
                    help="compare/record this bench JSON instead of "
                         "running a live bench")
    ap.add_argument("--baseline-file",
                    default=os.path.join(here, "PERF_BASELINE.json"))
    ap.add_argument("--name", default="local",
                    help="baseline name (update mode)")
    args = ap.parse_args(argv)

    if args.result:
        try:
            bench = perf_baseline.load_bench_json(args.result)
        except (OSError, ValueError) as e:
            # filesystem error, not a perf verdict: named rc 2 (the
            # regression exit code stays reserved for real regressions)
            print(f"❌ result file unusable: {e}", file=sys.stderr)
            return 2
    else:
        proc = subprocess.run([sys.executable,
                               os.path.join(here, "bench.py")],
                              capture_output=True, text=True, cwd=here)
        bench = perf_baseline.last_json_line(proc.stdout)
        if bench is None:
            print(f"❌ live bench emitted no JSON line (rc={proc.returncode})"
                  f"\n{_tail(proc.stderr)}", file=sys.stderr)
            return 2

    if args.mode == "update":
        try:
            doc = perf_baseline.make_baseline(bench, args.name,
                                              source=args.result or "live")
        except ValueError as e:
            # a skipped/empty run must never OVERWRITE a real baseline
            print(f"❌ not updating baseline: {e}", file=sys.stderr)
            return 2
        perf_baseline.write_baseline(doc, args.baseline_file)
        return 0

    try:
        with open(args.baseline_file, encoding="utf-8") as f:
            baseline = json.load(f)
    except (OSError, ValueError) as e:
        # unreadable OR corrupt (truncated write, merge-conflict markers):
        # a named rc-2, never a traceback that CI reads as a regression
        print(f"❌ baseline file unusable: {e}", file=sys.stderr)
        return 2
    cmp = perf_baseline.compare(bench, baseline)
    print(perf_baseline.format_report(cmp), file=sys.stderr)
    emit({"metric": "baseline_check", "baseline": baseline.get("name"),
          "verdict": cmp["verdict"],
          "regressed": [r["metric"] for r in cmp["regressions"]],
          "result": cmp})
    return 1 if cmp["regressions"] else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--stage":
        stage_child(sys.argv[2])
    elif len(sys.argv) >= 3 and sys.argv[1] == "--scenario":
        scenario_main(sys.argv[2])
    elif len(sys.argv) >= 2 and sys.argv[1] == "--baseline":
        sys.exit(baseline_main(sys.argv[2:]))
    else:
        main()
