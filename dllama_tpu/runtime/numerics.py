"""Numerics observatory — the layer that watches the *values*.

The stack is quantized end to end (Q40 weights, Q80 activation-sync
collectives) and the whole design bets that
those lossy representations stay quality-neutral. Until this module,
nothing checked: a NaN burst, a mis-scaled Q40 block, or replica drift in
the quantized collectives surfaced only as garbage tokens — no metric, no
named layer, no alarm. Four instruments close that gap:

* **Activation-stat taps** — ``models/llama.py``'s forward optionally
  returns a per-layer stats pytree (rms / abs-max / non-finite count /
  Q80 roundtrip error per block site: ``attn_out``, ``mlp_out``,
  ``final_norm``, ``logits``). Behind an engine flag
  (``--numerics-taps``): with the flag off the default trace is
  byte-identical and compile-ledger-quiet — the tapped program is never
  even jitted. The flag is a TRACE-TIME thread-local
  (:func:`taps_active`), read inside ``forward`` exactly like the mesh
  plan, so the tapped and plain programs coexist in one process.
* **Non-finite tripwire** — every guarded decode-step program
  (``models.llama.*_guarded``) returns a per-row count of non-finite
  decode-step logits, fused into the dispatch (one ``isfinite``
  reduction against a full forward). Always on; feeds
  ``dllama_nonfinite_total{site}``. Opt-in fail-fast
  (``--numerics-failfast``) turns a poisoned request into an explicit
  :class:`NumericsError` (HTTP 5xx with the site named) instead of
  emitting garbage tokens.
* **Quant-error audit** — ``python -m dllama_tpu audit --model m.m``
  (:func:`audit_model`): offline, host-only per-tensor table of Q40/Q80
  reconstruction health (non-finite values, scale range, roundtrip
  SNR/MSE via the ``formats/quants.py`` reference codecs). The Q80
  roundtrip error of live activations is sampled at the
  activation-sync boundary by the taps
  (``parallel.qcollectives.q80_roundtrip_error`` — the same
  quantization math the quantized-wire collective ships), published as
  ``dllama_q80_roundtrip_error{site}``.
* **Golden canary drift sentinel** — :class:`CanarySentinel` replays a
  fixed-seed canary prompt through the engine's existing prefill-width
  program (cache-hit: zero extra compiles after the golden is recorded)
  and compares greedy token ids + a logit fingerprint against the
  recorded golden. Drift increments ``dllama_canary_drift_total`` and
  the WARN names the first divergent layer using the taps when they are
  on. Driven by the batch scheduler's tick (and after single-sequence
  completions); surfaced via ``GET /debug/numerics`` and the ``--stats``
  ``drift=N!`` marker.

Import-light on purpose: jax only inside the functions that trace, so the
audit CLI and the lint tooling run without a backend.
"""

from __future__ import annotations

import math
import threading
import zlib
from contextlib import contextmanager

import numpy as np

from . import failpoints, telemetry

#: tap sites in model order — layer-stacked sites first, then the head
TAP_SITES = ("attn_out", "mlp_out", "final_norm", "logits")

#: tripwire sites (the dispatch families that carry the fused check)
TRIPWIRE_SITES = ("decode", "batch", "verify", "prefill", "canary")


class NumericsError(RuntimeError):
    """Non-finite values on a decode path with fail-fast armed: the
    request dies with a named site instead of emitting garbage tokens
    (HTTP 5xx in the serving layers)."""


def nonfinite_error(site: str, count: int) -> NumericsError:
    """The ONE spelling of the fail-fast error, so every layer (engine,
    batched serving, HTTP) names the site the same way."""
    return NumericsError(
        f"non-finite values in decode-step logits (site={site}, "
        f"{count} lanes) — numerics fail-fast is armed "
        f"(--numerics-failfast); see /debug/numerics")


# -- trace-time tap flag ------------------------------------------------------

_tls = threading.local()


def taps_active() -> bool:
    """Whether the current TRACE collects activation taps (read inside
    ``models.llama.forward`` at trace time, like the mesh plan)."""
    return getattr(_tls, "taps", False)


@contextmanager
def collecting_taps():
    """Arm the tap flag for the enclosed trace
    (``models.llama.forward_with_taps`` wraps its forward call in this)."""
    prev = getattr(_tls, "taps", False)
    _tls.taps = True
    try:
        yield
    finally:
        _tls.taps = prev


# -- non-finite tripwire ------------------------------------------------------

# in-graph poison selector values (models.llama._poison_logits): the
# `logits` failpoint's `nonfinite` action returns the mode string and the
# dispatch ships the matching code as a traced scalar — 0.0 means clean.
POISON_CODES = {"nan": 1.0, "inf": 2.0}

# the `wire` failpoint's codes ride the SAME traced scalar but a disjoint
# range: >= 3 corrupts THIS device's shipped ring-collective partial
# (batch row 0 only — parallel/qcollectives._maybe_poison_partial) instead
# of the logits, proving a poisoned quantized hop trips the tripwire for
# exactly one request. Only reachable when the trace contains the
# overlapped/ring wire collectives (--comm-overlap on a tp mesh).
WIRE_POISON_CODES = {"nan": 3.0, "inf": 4.0}

# module state for GET /debug/numerics: last counts per site + last taps
_state_lock = threading.Lock()
_last_nonfinite: dict[str, int] = {}
_last_taps: dict | None = None


def poison_code() -> float:
    """Fire the ``logits`` then ``wire`` failpoints for this dispatch;
    returns the in-graph poison code (0.0 = clean; 1-2 poison the logits,
    3-4 poison the wire collective's shipped partial). Raise-type actions
    armed on either site propagate as usual."""
    mode = failpoints.fire("logits")
    if mode:
        return POISON_CODES.get(str(mode), POISON_CODES["nan"])
    mode = failpoints.fire("wire")
    if mode:
        return WIRE_POISON_CODES.get(str(mode), WIRE_POISON_CODES["nan"])
    return 0.0


def record_nonfinite(count: int, site: str) -> None:
    """Count one non-finite tripwire event (``count`` > 0 affected lanes
    at ``site``) into ``dllama_nonfinite_total{site}`` and the debug
    state. One increment per event, not per lane — the counter is an
    alarm rate, the lane count lives in the error/debug detail."""
    telemetry.registry().counter(telemetry.NONFINITE).inc(site=site)
    with _state_lock:
        _last_nonfinite[site] = int(count)


def check_nonfinite(count, site: str, *, failfast: bool = False) -> int:
    """Host-side tripwire tail shared by the engine paths: ``count`` is
    the guarded dispatch's per-row non-finite count (array or scalar).
    Returns the total; records + optionally fail-fasts when nonzero."""
    n = int(np.sum(np.asarray(count)))
    if n > 0:
        record_nonfinite(n, site)
        if failfast:
            raise nonfinite_error(site, n)
    return n


# -- activation-stat taps (host side) ----------------------------------------


def record_taps(taps: dict, *, site_prefix: str = "") -> dict:
    """Publish one tapped dispatch's stats pytree (numpy leaves, from
    ``forward_with_taps``): per-site gauges (rms of the last layer,
    abs-max and Q80 roundtrip error maxed over layers), the non-finite
    counter per site, and the per-layer detail kept for
    ``GET /debug/numerics``. Returns the summarized dict."""
    reg = telemetry.registry()
    summary: dict = {}
    for site, st in taps.items():
        rms = np.atleast_1d(np.asarray(st["rms"], np.float64))
        absmax = np.atleast_1d(np.asarray(st["absmax"], np.float64))
        nf = int(np.sum(np.asarray(st["nonfinite"])))
        q80 = np.atleast_1d(np.asarray(st["q80_err"], np.float64))
        label = site_prefix + site
        reg.gauge(telemetry.ACTIVATION_RMS).set(float(rms[-1]), site=label)
        reg.gauge(telemetry.ACTIVATION_ABSMAX).set(float(absmax.max()),
                                                   site=label)
        reg.gauge(telemetry.Q80_ROUNDTRIP_ERROR).set(float(q80.max()),
                                                     site=label)
        if nf > 0:
            record_nonfinite(nf, "taps")
        summary[site] = {
            "rms": [float(v) for v in rms],
            "absmax": [float(v) for v in absmax],
            "nonfinite": nf,
            "q80_err": [float(v) for v in q80],
        }
    with _state_lock:
        global _last_taps
        _last_taps = summary
    return summary


def first_divergent_layer(taps: dict, golden: dict,
                          rtol: float = 1e-3) -> str | None:
    """Name the first (layer, site) whose tapped rms deviates from the
    golden's beyond ``rtol`` — model order: per layer, attn_out before
    mlp_out, then the head sites. None when every site agrees."""
    layered = [s for s in ("attn_out", "mlp_out") if s in taps and s in golden]
    if layered:
        n_layers = len(taps[layered[0]]["rms"])
        for layer in range(n_layers):
            for site in layered:
                a = taps[site]["rms"][layer]
                b = golden[site]["rms"][layer]
                if not math.isclose(a, b, rel_tol=rtol, abs_tol=1e-9):
                    return f"layer {layer} ({site})"
    for site in ("final_norm", "logits"):
        if site in taps and site in golden:
            a, b = taps[site]["rms"][0], golden[site]["rms"][0]
            if not math.isclose(a, b, rel_tol=rtol, abs_tol=1e-9):
                return site
    return None


# -- golden canary drift sentinel --------------------------------------------


class CanarySentinel:
    """Fixed-seed canary replay + golden comparison for one engine.

    The canary prompt is ``width`` random token ids (fixed seed) at the
    engine's SMALLEST prefill bucket width, dispatched through the
    engine's existing ``forward`` program (the tapped one when taps are
    on) on a scratch KV column — engine position, sampler RNG, and
    serving state are untouched, and after the golden run every replay
    is a compile-cache hit (the acceptance bar: ledger-quiet). Each
    replay allocates a FRESH scratch KV rather than reusing the donated
    output of the previous one: a donated-output buffer carries a
    different input signature (committed-ness/layout) than a fresh
    array, and feeding it back was measured to key a new executable —
    the exact post-steady retrace the sentinel must never cause.

    Drift = greedy token ids OR the crc32 logit fingerprint of the last
    position differing from the recorded golden. Each drift increments
    ``dllama_canary_drift_total`` and WARNs; with taps on the WARN names
    the first divergent layer from the per-layer rms comparison.
    """

    def __init__(self, engine, interval_s: float = 60.0,
                 seed: int = 0xCA7A):
        if getattr(engine, "multihost", False):
            raise ValueError(
                "the canary sentinel is single-host only (its scratch "
                "dispatches are not broadcast to worker mirrors)")
        self.eng = engine
        self.interval_s = interval_s
        width = engine.prefill_buckets[-1]
        rng = np.random.default_rng(seed)
        self.tokens = rng.integers(
            0, engine.cfg.vocab_size, size=(1, width)).astype(np.int32)
        self.golden: dict | None = None
        self._last_run = 0.0
        # _lock guards only the bookkeeping (status() must answer while a
        # replay is in flight — it is the endpoint an operator hits when
        # numerics look wrong); _replay_lock serializes the device
        # dispatches themselves
        self._lock = threading.Lock()
        self._replay_lock = threading.Lock()
        self.runs = 0
        self.drifts = 0
        self.last: dict | None = None

    # -- the replay dispatch -------------------------------------------------

    def _replay(self):
        """One canary forward on the scratch KV; returns
        ``(logits [T, vocab] np, taps summary | None)``."""
        import jax
        import jax.numpy as jnp

        from ..parallel.api import use_plan
        from contextlib import nullcontext

        eng = self.eng
        # fresh scratch KV per replay (class docstring: a donated-output
        # buffer fed back keys a new executable — the one thing a
        # post-steady canary must never do); dropped right after, so the
        # allocation is transient
        kv = eng._fresh_kv()
        tapped = getattr(eng, "_step_tapped", None)
        fn = tapped if tapped is not None else eng._step
        with eng.watchdog.guard("canary"):
            with (use_plan(eng.plan) if eng.plan is not None
                    else nullcontext()):
                out, _kv_out = fn(eng.params, eng.cfg,
                                  jnp.asarray(self.tokens, jnp.int32),
                                  jnp.int32(0), kv)
        if tapped is not None:
            logits, taps = out
            taps = record_taps(jax.tree_util.tree_map(np.asarray, taps))
        else:
            logits, taps = out, None
        row = np.asarray(logits[0], dtype=np.float32)
        # direct non-finite signal on the replayed logits (site=canary):
        # a NaN burst during a replay must not surface only as opaque
        # fingerprint drift. Count-only — the canary is diagnostics, a
        # fail-fast here would kill the sentinel itself.
        bad = int(row.size - np.count_nonzero(np.isfinite(row)))
        if bad:
            record_nonfinite(bad, "canary")
        return row, taps

    @staticmethod
    def _fingerprint(logits: np.ndarray) -> tuple[list[int], int]:
        ids = [int(t) for t in np.argmax(logits, axis=-1)]
        crc = zlib.crc32(np.ascontiguousarray(logits[-1],
                                              np.float32).tobytes())
        return ids, crc


    def ensure_golden(self) -> dict:
        """Record the golden on the first call (run at engine/scheduler
        startup, BEFORE serving steady state, so any compile this width
        needs happens while compiles are still expected). Same recording
        + accounting path as :meth:`run` — a golden recording IS a run."""
        with self._lock:
            golden = self.golden
        if golden is None:
            self.run()
            with self._lock:
                golden = self.golden
        return golden

    def maybe_run(self) -> dict | None:
        """Time-gated replay (the scheduler-tick / post-completion hook):
        no-op until ``interval_s`` has elapsed since the last run."""
        now = telemetry.now_ns() / 1e9
        with self._lock:
            if self.golden is not None \
                    and now - self._last_run < self.interval_s:
                return None
        return self.run()

    def run(self) -> dict:
        """One canary replay + golden comparison; the very first call
        records the golden instead of comparing. The dispatch runs under
        ``_replay_lock`` only, so :meth:`status` never blocks behind a
        multi-second forward."""
        reg = telemetry.registry()
        with self._replay_lock:
            logits, taps = self._replay()
            ids, crc = self._fingerprint(logits)
            with self._lock:
                # the interval starts at the replay, golden or not
                self._last_run = telemetry.now_ns() / 1e9
                self.runs += 1
                reg.counter(telemetry.CANARY_RUNS).inc()
                if self.golden is None:
                    self.golden = {"token_ids": ids, "logits_crc": crc,
                                   "taps": taps}
                    self.last = {"drift": False, "golden_recorded": True}
                    return self.last
                golden = self.golden
            token_drift = ids != golden["token_ids"]
            crc_drift = crc != golden["logits_crc"]
            result: dict = {"drift": bool(token_drift or crc_drift),
                            "token_drift": bool(token_drift),
                            "fingerprint_drift": bool(crc_drift),
                            "divergent_layer": None}
            if result["drift"]:
                reg.counter(telemetry.CANARY_DRIFT).inc()
                if taps is not None and golden.get("taps") is not None:
                    result["divergent_layer"] = first_divergent_layer(
                        taps, golden["taps"])
                where = (result["divergent_layer"]
                         or "unknown (enable --numerics-taps for layer "
                            "attribution)")
                print(f"⚠️ canary drift: fixed-seed replay diverged from "
                      f"the recorded golden (tokens "
                      f"{'differ' if token_drift else 'match'}, logit "
                      f"fingerprint "
                      f"{'differs' if crc_drift else 'matches'}) — first "
                      f"divergent: {where}", flush=True)
            with self._lock:
                if result["drift"]:
                    self.drifts += 1
                self.last = result
            return result

    def status(self) -> dict:
        """JSON-able state for ``GET /debug/numerics``."""
        with self._lock:
            return {
                "golden_recorded": self.golden is not None,
                "interval_s": self.interval_s,
                "canary_width": int(self.tokens.shape[1]),
                "runs": self.runs,
                "drifts": self.drifts,
                "last": self.last,
            }


# -- offline quant-error audit ------------------------------------------------


def _snr_db(x: np.ndarray, y: np.ndarray) -> float:
    """10·log10(signal/error) power ratio; inf when the roundtrip is
    exact, 0.0 for an all-zero signal."""
    sig = float(np.sum(np.square(x, dtype=np.float64)))
    err = float(np.sum(np.square((x - y).astype(np.float64))))
    if err == 0.0:
        return float("inf")
    if sig == 0.0:
        return 0.0
    return 10.0 * math.log10(sig / err)


def audit_tensor(key: str, rec, buf, *, dense: np.ndarray) -> dict:
    """One audit row: reconstruction health + roundtrip error of one
    tensor. ``dense`` is the reference-dequantized f32 flat array."""
    from ..formats import quants as q

    n = dense.size
    finite_mask = np.isfinite(dense)
    nf = int(n - np.count_nonzero(finite_mask))
    finite = dense[finite_mask] if nf else dense
    row: dict = {
        "tensor": key,
        "type": q.FLOAT_TYPE_NAMES.get(rec.float_type, str(rec.float_type)),
        "n": int(n),
        "nonfinite": nf,
        "absmax": float(np.max(np.abs(finite))) if finite.size else 0.0,
        "rms": (float(np.sqrt(np.mean(np.square(finite, dtype=np.float64))))
                if finite.size else 0.0),
    }
    if rec.float_type in (q.Q40, q.Q80):
        unpack = q.unpack_q40 if rec.float_type == q.Q40 else q.unpack_q80
        scales, _codes = unpack(buf, n)
        s = scales.astype(np.float32)
        row["scale_nonfinite"] = int(np.sum(~np.isfinite(s)))
        sf = s[np.isfinite(s)]
        row["scale_absmax"] = float(np.max(np.abs(sf))) if sf.size else 0.0
    if nf == 0 and n and n % q.QUANT_BLOCK_SIZE == 0:
        # Q40 roundtrip of the reference-dequantized values: for dense
        # (f32/f16) tensors this is what Q40-quantizing them would cost;
        # for already-quantized tensors it documents self-consistency
        # (healthy blocks re-encode near-exactly). An exact roundtrip
        # stores SNR as None + q40_exact (inf is not strict JSON).
        y40 = q.dequantize_q40(q.quantize_q40(dense), n)
        row["q40_mse"] = float(np.mean(np.square((dense - y40)
                                                 .astype(np.float64))))
        snr = _snr_db(dense, y40)
        row["q40_exact"] = math.isinf(snr)
        row["q40_snr_db"] = None if math.isinf(snr) else snr
        if rec.float_type == q.Q80:
            y80 = q.dequantize_q80(q.quantize_q80(dense), n)
            snr80 = _snr_db(dense, y80)
            row["q80_snr_db"] = None if math.isinf(snr80) else snr80
    return row


def audit_model(path: str, emit=None) -> dict:
    """Offline per-tensor quant-error audit (``python -m dllama_tpu audit
    --model m.m``). Host-only — no jax, no device: every tensor is
    reference-dequantized (``formats/quants.py``) one at a time and
    scored. Publishes ``dllama_quant_audit_min_snr_db`` /
    ``dllama_quant_audit_nonfinite_total`` and returns
    ``{"rows": [...], "nonfinite_tensors": [...], "min_snr_db": ...}``."""
    from ..formats.mfile import ModelFile

    rows: list[dict] = []
    with ModelFile.open(path) as mf:
        for key, rec in mf.tensors.items():
            dense = np.asarray(mf.tensor_f32(key), np.float32).reshape(-1)
            rows.append(audit_tensor(key, rec, mf.raw(key), dense=dense))
    bad = [r["tensor"] for r in rows
           if r["nonfinite"] or r.get("scale_nonfinite")]
    snrs = [r["q40_snr_db"] for r in rows
            if r.get("q40_snr_db") is not None]
    min_snr = min(snrs) if snrs else float("inf")
    total_nf = sum(r["nonfinite"] for r in rows)
    reg = telemetry.registry()
    reg.gauge(telemetry.QUANT_AUDIT_MIN_SNR).set(
        0.0 if math.isinf(min_snr) else min_snr)
    if total_nf:
        reg.counter(telemetry.QUANT_AUDIT_NONFINITE).inc(total_nf)
    out = {"model": str(path), "tensors": len(rows), "rows": rows,
           "nonfinite_tensors": bad,
           "min_snr_db": None if math.isinf(min_snr) else min_snr}
    if emit is not None:
        emit(f"🔬 quant audit: {path} ({len(rows)} tensors)")
        emit(f"{'tensor':34s} {'type':5s} {'nonfin':>6s} {'absmax':>10s} "
             f"{'rms':>10s} {'q40 snr dB':>10s}")
        for r in rows:
            snr = r.get("q40_snr_db")
            snr_s = ("exact" if r.get("q40_exact")
                     else f"{snr:.1f}" if snr is not None else "-")
            emit(f"{r['tensor']:34s} {r['type']:5s} {r['nonfinite']:6d} "
                 f"{r['absmax']:10.4g} {r['rms']:10.4g} {snr_s:>10s}")
        if bad:
            emit(f"❌ non-finite values in {len(bad)} tensor(s): "
                 + ", ".join(bad))
        else:
            emit(f"✅ no non-finite values; worst Q40 roundtrip SNR "
                 + ("exact" if out["min_snr_db"] is None
                    else f"{out['min_snr_db']:.1f} dB"))
    return out


# -- GET /debug/numerics -------------------------------------------------------


def debug_snapshot(engine=None) -> dict:
    """JSON-able observatory state: tripwire totals per site, the last
    tapped dispatch's per-layer stats, and the canary status."""
    reg = telemetry.registry()
    nf = reg.counter(telemetry.NONFINITE)
    with _state_lock:
        taps = _last_taps
        last_counts = dict(_last_nonfinite)
    canary = getattr(engine, "canary", None) if engine is not None else None
    return {
        "nonfinite_total": nf.total(),
        "nonfinite_by_site": {s: nf.total(site=s)
                              for s in TRIPWIRE_SITES + ("taps",)
                              if nf.total(site=s)},
        "last_nonfinite_lanes": last_counts,
        "failfast": bool(getattr(engine, "nf_failfast", False)),
        "taps_enabled": bool(getattr(engine, "numerics_taps", False)),
        "taps": taps,
        "canary": canary.status() if canary is not None else None,
        "canary_drift_total": reg.counter(telemetry.CANARY_DRIFT).total(),
    }
