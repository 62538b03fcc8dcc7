"""XLA compile-and-device introspection — the layer PR 2's bugs hid under.

The telemetry registry (runtime/telemetry.py) sees wall time, queues, and
tokens, but every one of PR 2's worst bugs lived BELOW it, in what XLA
compiled: cross-engine trace-cache poisoning, duplicate full-model compiles,
a shard_map path that never traced. Nothing recorded what was compiled, when,
or why — each was diagnosed by hand. This module is that record:

* **Compile ledger** — every ``plan_scoped_jit`` callable is wrapped in an
  :class:`ObservedJit` proxy whose per-call cost is two thread-local writes
  (~100 ns against multi-ms dispatches). Real compiles are detected through
  ``jax.monitoring`` duration events (``jaxpr_trace_duration`` /
  ``backend_compile_duration``), which fire only on genuine retraces and
  XLA compiles — NOT on pjit fastpath-cache entry churn, which a
  cache-size probe would misreport as compiles. The ledger records program
  name, engine scope, active mesh plan, per-leaf argument signature, and
  wall/backend time into ``dllama_compile_total`` /
  ``dllama_compile_seconds``; with ``ledger().analyze`` set it also
  AOT-relowers the same arguments to pull ``memory_analysis()`` bytes
  (``dllama_program_hbm_bytes{program,kind}``) and ``cost_analysis()``
  FLOPs (``dllama_program_flops``) — a second backend compile of identical
  HLO, absorbed by the persistent compile cache, so it is on by default
  only in api serving mode. With the persistent cache enabled the proxy is
  also the program store's client (runtime/program_store): a warm start
  LOADS each specialization's executable instead of tracing it, and the
  ledger's events say which (``source``: ``store`` | ``trace``).
* **Retrace sentinel** — once an engine scope is marked steady (the batch
  scheduler does this after two compile-quiet ticks; single-sequence mode
  after one compile-quiet completion), any further compile in that scope is
  counted in ``dllama_retrace_unexpected_total`` and WARN-logged with the
  per-leaf shape/plan diff that caused it. Creating a new wrapper in a scope
  re-opens it (the program set is no longer closed).
* **HBM startup report** — :func:`hbm_startup_report` AOT-compiles the
  engine's decode and prefill programs at load, emits a budget table
  (weights vs KV from runtime/hbm.py vs per-program temp/output bytes from
  ``memory_analysis()``) and publishes the same gauges.

``GET /debug/compiles`` (serve/api.py) dumps :meth:`CompileLedger.snapshot`.
Dependency-free at import (jax/parallel imports are call-time) so the
telemetry lint tooling can import it without a backend.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from collections import deque

from .. import compile_cache
from . import program_store, telemetry

# a broken analysis pass must never break the dispatch it rode in on; cap
# the WARN spam one misbehaving program can emit
_MAX_WARNS_PER_PROGRAM = 8
_MAX_DIFF_LINES = 12


def _describe_leaf(x) -> str:
    """Short shape/dtype tag for one argument leaf: ``f32[1,8]``-style for
    arrays, ``repr`` (bounded) for static scalars/objects."""
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        dt = getattr(aval, "dtype", None)
        name = getattr(dt, "name", str(dt))
        return f"{name}[{','.join(str(d) for d in aval.shape)}]"
    shape = getattr(x, "shape", None)
    if shape is not None and getattr(x, "dtype", None) is not None:
        return f"{x.dtype}[{','.join(str(d) for d in shape)}]"
    r = repr(x)
    return r if len(r) <= 80 else r[:77] + "..."


def _signature(args: tuple, kwargs: dict) -> dict[str, str]:
    """Flat per-leaf description of a call's arguments — the diffable
    identity of one compiled specialization (static values included: a
    changed ``n_steps`` static is a legitimate retrace cause and must show
    in the diff)."""
    import jax

    sig: dict[str, str] = {}
    leaves = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        sig[key] = _describe_leaf(leaf)
    return sig


def _plan_desc() -> str:
    """The active mesh plan at call (= trace) time, e.g. ``tp=2,sp=2``."""
    try:
        from ..parallel.api import current_plan

        plan = current_plan()
    except Exception:  # noqa: BLE001 — introspection never breaks a dispatch
        return "unknown"
    if plan is None:
        return "none"
    return ",".join(f"{a}={n}" for a, n in plan.mesh.shape.items()) or "none"


def _sig_diff(old: dict[str, str] | None, new: dict[str, str]) -> list[str]:
    if not old:
        return ["(first compile in scope — no prior signature)"]
    lines = []
    for k, v in new.items():
        if k not in old:
            lines.append(f"+ {k} = {v}")
        elif old[k] != v:
            lines.append(f"~ {k}: {old[k]} -> {v}")
    for k in old:
        if k not in new:
            lines.append(f"- {k} = {old[k]}")
    if not lines:
        lines = ["(identical leaf shapes — an input-sharding, weak-type, or "
                 "mesh-plan change keyed a new executable; e.g. a program's "
                 "first dispatch on its own donated output)"]
    return lines[:_MAX_DIFF_LINES]


_HBM_KINDS = (("temp", "temp_size_in_bytes"),
              ("output", "output_size_in_bytes"),
              ("argument", "argument_size_in_bytes"),
              ("alias", "alias_size_in_bytes"),
              ("code", "generated_code_size_in_bytes"))


def cost_analysis_dict(compiled) -> dict:
    """Version-compat accessor for ``compiled.cost_analysis()``: newer
    jax returns one properties dict, 0.4.x returns a one-element list of
    dicts — indexing the raw return by key TypeErrors on exactly one of
    the two. Every consumer (``analyze_compiled`` below, roofline
    attribution, tests measuring FLOPs) goes through here so the compat
    decision lives in one place."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # jax<=0.4.x returns [dict]
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


_MOSAIC_CALL = re.compile(
    r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"')
_JIT_NAME = re.compile(r"jit\(([^()]*)\)")


def mosaic_kernels(hlo_text: str) -> dict[str, int]:
    """The COMPILED Pallas (Mosaic) kernels of an optimized HLO text, counted
    by the jitted function that made the ``pallas_call`` (``quant_matmul``,
    ``_call`` of flash attention, ``paged_ragged_attention``, ...). A kernel
    run in interpret mode lowers to plain HLO and never appears here, so a
    nonempty answer is the proof that a program runs the kernels themselves.
    A call inside a layer scan's body counts once, as the text holds it."""
    out: dict[str, int] = {}
    for op_name in _MOSAIC_CALL.findall(hlo_text):
        names = _JIT_NAME.findall(op_name)
        key = names[-1] if names else "pallas_call"
        out[key] = out.get(key, 0) + 1
    return out


def analyze_compiled(program: str, compiled, *,
                     scope: str = "default") -> dict:
    """Pull ``memory_analysis()`` bytes, ``cost_analysis()`` FLOPs and the
    compiled Pallas kernels (:func:`mosaic_kernels`) off a compiled stage
    and publish the first two as per-(scope, program) gauges — two
    engines share program NAMES (``forward``, ``sampled_step``) but not
    shapes or shardings, so a scope-less gauge would let whichever engine
    compiled last silently overwrite the other's bytes. Best-effort: a
    backend without either analysis yields a partial dict, never a raise."""
    out: dict = {}
    reg = telemetry.registry()
    try:
        ma = compiled.memory_analysis()
        hbm = {kind: int(getattr(ma, attr, 0) or 0)
               for kind, attr in _HBM_KINDS}
        out["hbm_bytes"] = hbm
        out["hbm_total_bytes"] = (hbm["temp"] + hbm["output"]
                                  + hbm["argument"])
        g = reg.gauge(telemetry.PROGRAM_HBM_BYTES)
        for kind, v in hbm.items():
            g.set(v, scope=scope, program=program, kind=kind)
    except Exception as e:  # noqa: BLE001 — analysis is advisory, record why
        out["memory_analysis_error"] = f"{type(e).__name__}: {e}"
    try:
        ca = cost_analysis_dict(compiled)
        flops = float(ca.get("flops", 0.0) or 0.0)
        out["flops"] = flops
        reg.gauge(telemetry.PROGRAM_FLOPS).set(flops, scope=scope,
                                               program=program)
    except Exception as e:  # noqa: BLE001 — analysis is advisory, record why
        out["cost_analysis_error"] = f"{type(e).__name__}: {e}"
    try:
        out["kernels"] = mosaic_kernels(compiled.as_text())
    except Exception as e:  # noqa: BLE001 — analysis is advisory, record why
        out["kernels_error"] = f"{type(e).__name__}: {e}"
    return out


class CompileLedger:
    """Process-wide record of what XLA compiled, keyed (scope, program).

    A *scope* is one engine's program namespace (``engine-N``); steadiness
    is per scope so a second engine warming up never trips the first
    engine's retrace sentinel."""

    def __init__(self, max_events: int = 256):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max_events)
        self._programs: dict[tuple[str, str], dict] = {}
        self._steady: dict[str, bool] = {}
        self._compiles_by_scope: dict[str, int] = {}
        self._loads_by_scope: dict[str, int] = {}
        self._seq = 0
        # per-miss AOT memory/cost analysis (a second compile of identical
        # HLO): on for api serving, opt-in elsewhere. Env overrides both
        # ways for operators (DLLAMA_INTROSPECT_ANALYZE=0/1).
        self.analyze = os.environ.get("DLLAMA_INTROSPECT_ANALYZE") == "1"

    # -- wrap-time ----------------------------------------------------------

    def register(self, scope: str, program: str) -> dict:
        """Create/fetch the (scope, program) aggregate. Registering re-opens
        the scope: a new wrapper means the compiled-program set is no longer
        closed, so steady-state flips off until re-marked."""
        with self._lock:
            self._steady[scope] = False
            entry = self._programs.get((scope, program))
            if entry is None:
                entry = {"scope": scope, "program": program, "compiles": 0,
                         "hits": 0, "warns": 0, "last_sig": None,
                         "last_plan": None, "last_compile_s": 0.0,
                         "total_compile_s": 0.0, "analysis": None,
                         "unexpected": 0, "q40_paths": None}
                self._programs[(scope, program)] = entry
            return entry

    # -- steady-state -------------------------------------------------------

    def compile_count(self, scope: str) -> int:
        return self.build_counts(scope)[0]

    def build_counts(self, scope: str) -> tuple[int, int]:
        """``(programs built, of those loaded from the program store)`` in
        ``scope``; their difference was traced and compiled here."""
        with self._lock:
            return (self._compiles_by_scope.get(scope, 0),
                    self._loads_by_scope.get(scope, 0))

    def steady(self, scope: str) -> bool:
        with self._lock:
            return self._steady.get(scope, False)

    def mark_steady(self, scope: str) -> None:
        """Arm the retrace sentinel for ``scope``: from here on, any compile
        in the scope is unexpected (counted + WARN-logged with its diff)."""
        with self._lock:
            self._steady[scope] = True

    def measured_hbm_bytes(self, scope: str) -> dict[str, int]:
        """Measured per-program device bytes (argument + temp + output,
        from ``memory_analysis()``) for every analyzed program in
        ``scope`` — the HBM admission guard's cross-check against the
        shape-algebra estimate. Empty when nothing was analyzed (analyze
        off, or the backend has no memory_analysis)."""
        out: dict[str, int] = {}
        with self._lock:
            for (sc, program), entry in self._programs.items():
                if sc != scope:
                    continue
                total = (entry["analysis"] or {}).get("hbm_total_bytes", 0)
                if total:
                    out[program] = int(total)
        return out

    # -- miss/hit recording (ObservedJit) ------------------------------------

    def note_q40_paths(self, entry: dict, paths: dict | None) -> None:
        """File the per-path Q40 matmul counts of a trace of ``entry``'s
        program (:func:`note_q40_path`) and publish them. A call that did
        not trace (a cached jaxpr) brings None and changes nothing."""
        if not paths:
            return
        with self._lock:
            entry["q40_paths"] = dict(paths)
        g = telemetry.registry().gauge(telemetry.Q40_MATMUL_PATHS)
        for path, n in paths.items():
            g.set(n, scope=entry["scope"], program=entry["program"],
                  path=path)

    def note_mixer_paths(self, entry: dict, win: dict) -> None:
        """The recurrent mixers' twin of :meth:`note_q40_paths`
        (:func:`note_gdn_path`, :func:`note_ssd_path`): gauges
        ``dllama_gated_delta_paths`` and ``dllama_ssd_paths``."""
        for kind, gauge in _MIXER_GAUGES.items():
            paths = win.get(kind)
            if not paths:
                continue
            with self._lock:
                entry[kind + "_paths"] = dict(paths)
            g = telemetry.registry().gauge(gauge)
            for key, n in paths.items():
                form, path = key.split(":")
                g.set(n, scope=entry["scope"], program=entry["program"],
                      form=form, path=path)

    def mixer_paths(self, scope: str, kind: str) -> dict[str, dict[str, int]]:
        with self._lock:
            return {program: dict(entry[kind + "_paths"])
                    for (sc, program), entry in sorted(self._programs.items())
                    if sc == scope and entry.get(kind + "_paths")}

    def q40_paths(self, scope: str) -> dict[str, dict[str, int]]:
        """``{program: {path: count}}`` for the programs of ``scope`` whose
        newest trace held a Q40 matmul."""
        with self._lock:
            return {program: dict(entry["q40_paths"])
                    for (sc, program), entry in sorted(self._programs.items())
                    if sc == scope and entry.get("q40_paths")}

    def record(self, entry: dict, compile_s: float, signature: dict,
               plan: str, analysis: dict | None, *,
               backend_s: float = 0.0, source: str = "trace") -> None:
        """File one event of a program coming into being. ``source`` says
        how: ``trace`` (traced, lowered and compiled; ``compile_s`` is the
        observed wall time, the first execution included when the jitted
        call itself did it; ``backend_s`` the XLA backend portion, the
        retrieval alone when the persistent compile cache served the
        executable: the retrace still cost the trace) or ``store`` (deserialized from the program
        store, runtime/program_store: ``compile_s`` is the load alone)."""
        scope, program = entry["scope"], entry["program"]
        reg = telemetry.registry()
        with self._lock:
            unexpected = self._steady.get(scope, False)
            diff = _sig_diff(entry["last_sig"], signature) if unexpected \
                else None
            if unexpected and entry["last_plan"] not in (None, plan):
                diff = [f"~ mesh plan: {entry['last_plan']} -> {plan}"] + diff
            entry["compiles"] += 1
            entry["last_sig"] = signature
            entry["last_plan"] = plan
            entry["last_compile_s"] = compile_s
            entry["total_compile_s"] += compile_s
            if analysis:
                entry["analysis"] = analysis
            if unexpected:
                entry["unexpected"] += 1
            self._compiles_by_scope[scope] = \
                self._compiles_by_scope.get(scope, 0) + 1
            if source == "store":
                self._loads_by_scope[scope] = \
                    self._loads_by_scope.get(scope, 0) + 1
            self._seq += 1
            self._events.append({
                "seq": self._seq, "time": time.time(), "scope": scope,
                "program": program, "compile_s": round(compile_s, 6),
                "backend_s": round(backend_s, 6), "source": source,
                "plan": plan, "n_leaves": len(signature),
                "unexpected": unexpected, "diff": diff,
                "analysis": analysis,
            })
            warn = unexpected and entry["warns"] < _MAX_WARNS_PER_PROGRAM
            if warn:
                entry["warns"] += 1
        reg.counter(telemetry.COMPILE_TOTAL).inc(scope=scope,
                                                 program=program)
        reg.histogram(telemetry.COMPILE_SECONDS).record(compile_s)
        loaded = source == "store"
        reg.counter(telemetry.PROGRAMS_LOADED if loaded
                    else telemetry.PROGRAMS_TRACED).inc()
        reg.counter(telemetry.PROGRAM_LOAD_SECONDS if loaded
                    else telemetry.PROGRAM_TRACE_SECONDS).inc(compile_s)
        if unexpected:
            reg.counter(telemetry.RETRACE_UNEXPECTED).inc(program=program)
        if warn:
            lines = "\n".join(f"      {d}" for d in (diff or []))
            print(f"⚠️ unexpected "
                  f"{'program load' if loaded else 'recompile'} after "
                  f"steady state: "
                  f"{scope}/{program} took {compile_s * 1e3:.0f} ms "
                  f"(plan {plan})\n{lines}", flush=True)

    # -- export --------------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able ledger dump (``GET /debug/compiles``)."""
        with self._lock:
            programs = []
            for entry in self._programs.values():
                e = {k: v for k, v in entry.items() if k != "last_sig"}
                e["hbm_total_bytes"] = (entry["analysis"] or {}).get(
                    "hbm_total_bytes", 0)
                programs.append(e)
            return {
                "steady": dict(self._steady),
                "analyze": self.analyze,
                "programs": sorted(
                    programs, key=lambda e: (e["scope"], e["program"])),
                "events": list(self._events),
            }

    def reset(self) -> None:
        """Forget everything (tests). Registry metrics are NOT zeroed —
        use ``telemetry.registry().reset()`` for that."""
        with self._lock:
            self._events.clear()
            self._programs.clear()
            self._steady.clear()
            self._compiles_by_scope.clear()
            self._loads_by_scope.clear()


_ledger = CompileLedger()


def ledger() -> CompileLedger:
    """The process-wide compile ledger."""
    return _ledger


# -- compile detection via jax.monitoring --------------------------------
#
# The pjit wrapper's C++ cache size is NOT a compile signal: its fastpath
# cache keys more finely than the executable cache (input sharding objects,
# committed-ness), so entries appear without any retrace — e.g. the first
# dispatch after engine.reset(). jax.monitoring's duration events fire only
# for the real thing: ``jaxpr_trace_duration`` on a genuine retrace,
# ``backend_compile_duration`` on an XLA compile (absent when the
# persistent compile cache serves the executable — the trace event still
# fires, and a steady-state retrace is a latency cliff either way).
# Attribution is a thread-local window: the listener runs on the thread
# doing the compile, which is the thread inside ObservedJit.__call__.

_tls = threading.local()
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
# the XLA persistent cache served a compile request (a plain event, no
# duration): what the program store asks before it serializes on the CPU
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_monitoring_state: list = []  # [] = untried, [True] = on, [False] = absent


def _event_listener(name: str, duration_s: float, **_kw) -> None:
    win = getattr(_tls, "window", None)
    if win is None:
        return
    if name == _BACKEND_EVENT:
        win["backend_s"] += duration_s
        win["n_backend"] += 1
    elif name == _TRACE_EVENT:
        win["n_trace"] += 1


def _plain_event_listener(name: str, **_kw) -> None:
    win = getattr(_tls, "window", None)
    if win is not None and name == _CACHE_HIT_EVENT:
        win["n_cache_hit"] += 1


# "chunk" first and "grouped" (the routed chunk kernel, ops.expert_chunk:
# one count a projection) last: the step's "N fused / 0 tiled / 0 xla" and
# a dense chunk's "N chunk / 0 fused / 0 tiled / 0 xla" read as they always did
Q40_PATHS = ("chunk", "fused", "tiled", "xla", "grouped")


def note_q40_path(path: str) -> None:
    """``ops.linear`` (and ``models.share`` for the routed chunk kernel)
    calls this while a Q40 matmul is traced, with the path
    it gave it (one of :data:`Q40_PATHS`). The count goes to the program
    whose :class:`ObservedJit` is tracing on this thread; outside one it is
    dropped. A layer scan's body is traced once, so its matmuls count once
    (as :func:`mosaic_kernels` counts a kernel in a scan's body once)."""
    win = getattr(_tls, "window", None)
    if win is not None:
        paths = win.setdefault("q40", dict.fromkeys(Q40_PATHS, 0))
        paths[path] += 1


# the recurrent mixers by the key their counts are filed under: the gauge
# each publishes to and the name the start-up report gives it
_MIXER_GAUGES = {"gdn": telemetry.GATED_DELTA_PATHS, "ssd": telemetry.SSD_PATHS,
                 "mla": telemetry.MLA_PATHS,
                 "conv": telemetry.SHORT_CONV_PATHS}
_MIXER_TITLES = {"gdn": "gated delta rule", "ssd": "ssd mixer",
                 "mla": "latent attention",
                 "conv": "gated short convolution"}


def _note_mixer_path(kind: str, form: str, path: str) -> None:
    win = getattr(_tls, "window", None)
    if win is not None:
        paths = win.setdefault(kind, {})
        paths[f"{form}:{path}"] = paths.get(f"{form}:{path}", 0) + 1


def note_gdn_path(form: str, path: str) -> None:
    """``models.hybrid`` calls this while a gated delta-rule mixer is traced:
    which ``form`` (``chunk`` or ``step``) took which ``path`` (``pallas`` or
    ``xla``). Filed like :func:`note_q40_path`, under ``form:path``."""
    _note_mixer_path("gdn", form, path)


def note_ssd_path(form: str, path: str) -> None:
    """:func:`note_gdn_path` for the SSD mixer (``models.falcon_h1``)."""
    _note_mixer_path("ssd", form, path)


def note_mla_path(form: str, path: str) -> None:
    """:func:`note_gdn_path` for latent attention (``models.axk1``): ``step``
    and ``chunk`` each took ``pallas`` (the ``mla_paged_step`` / ``mla_chunk``
    kernel) or ``xla``."""
    _note_mixer_path("mla", form, path)


def note_short_conv_path(form: str, path: str) -> None:
    """:func:`note_gdn_path` for the gated short convolution
    (``models.lfm2``): ``chunk`` and ``step`` both run ``xla`` (three taps a
    channel over a tail of two rows: elementwise work XLA fuses into the
    projections' neighbours; there is no kernel to choose)."""
    _note_mixer_path("conv", form, path)


def _monitoring_on() -> bool:
    if not _monitoring_state:
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_event_listener)
            monitoring.register_event_listener(_plain_event_listener)
            _monitoring_state.append(True)
        except Exception:  # noqa: BLE001 — degrade to pass-through, no ledger
            _monitoring_state.append(False)
    return _monitoring_state[0]


def _new_window() -> dict:
    """What one traced call gathers on its thread: the monitoring events'
    counts, and (``q40``, once a matmul is noted) the Q40 path counts."""
    return {"backend_s": 0.0, "n_backend": 0, "n_trace": 0, "n_cache_hit": 0}


@contextlib.contextmanager
def _thread_window():
    """A fresh window for what this thread traces or compiles inside the
    block, the enclosing one (a dispatch's own) put back after it. The
    dispatch path itself does this inline: it is the hot one."""
    prev = getattr(_tls, "window", None)
    win = _tls.window = _new_window()
    try:
        yield win
    finally:
        _tls.window = prev


# a specialization the program store does not serve: the jit path takes it
_ASIDE = object()


class ObservedJit:
    """Identity-preserving proxy over a ``jax.jit`` callable that feeds the
    compile ledger. Hit path: two thread-local writes. Compile path (a
    retrace/compile just happened — already 100 ms+): build the leaf
    signature, optionally AOT-relower for memory/cost analysis, record.
    AOT attributes (``lower``, ``eval_shape``, ...) delegate.

    With the persistent cache enabled (``compile_cache.programs_dir()``) and
    the jit's function and options known, it is also the program store's
    client (runtime/program_store): the first call with a signature loads
    that specialization's executable from the store, or lowers and compiles
    it once and files it there, and every later call with the signature goes
    to that executable. Hit path then: one dictionary lookup on the static
    arguments and the top-level arrays' shapes (never a walk of a tree) and
    the call into the ``jax.stages.Compiled``. The store stands aside under
    a mesh plan or several processes, for keyword arguments and for anything
    its key cannot hold; an executable that refuses its arguments drops out
    and the call falls back to the jit, as a signature never stored does."""

    def __init__(self, jitted, scope: str, program: str, *, fun=None,
                 options: dict | None = None):
        self._jitted = jitted
        self.scope = scope
        self.program = program
        self._observed = _monitoring_on()
        self._entry = _ledger.register(scope, program)
        self._fun = fun
        self._options = dict(options or {})
        # the store's key holds these two options and no other
        self._storable = (fun is not None and options is not None and
                          set(options) <= {"static_argnums", "donate_argnums"})
        static = self._options.get("static_argnums", ())
        self._static = frozenset((static,) if isinstance(static, int)
                                 else static)
        self._programs: dict[tuple, object] = {}
        self._programs_lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if self._storable and not kwargs:
            directory = compile_cache.programs_dir()
            if directory is not None:
                out = self._call_stored(directory, args)
                if out is not _ASIDE:
                    return out
        if not self._observed:
            return self._jitted(*args, **kwargs)
        prev = getattr(_tls, "window", None)
        win = _tls.window = _new_window()
        t0 = time.perf_counter()
        try:
            out = self._jitted(*args, **kwargs)
        finally:
            _tls.window = prev  # restore BEFORE any analysis compiles below
        if not (win["n_trace"] or win["n_backend"]):
            self._entry["hits"] += 1  # GIL-atomic enough for a debug count
            return out
        compile_s = time.perf_counter() - t0
        analysis = None
        try:
            sig = _signature(args, kwargs)
            if _ledger.analyze:
                # donated inputs stay abstractly valid (avals survive
                # deletion), so re-lowering with the same args is safe; the
                # second backend compile of identical HLO is absorbed by
                # the persistent compile cache when it is enabled
                analysis = analyze_compiled(
                    self.program,
                    self._jitted.lower(*args, **kwargs).compile(),
                    scope=self.scope)
        except Exception as e:  # noqa: BLE001 — never break the dispatch
            analysis = {"error": f"{type(e).__name__}: {e}"}
            sig = {}
        self._note_paths(win)
        _ledger.record(self._entry, compile_s, sig, _plan_desc(), analysis,
                       backend_s=win["backend_s"])
        return out

    def _call_stored(self, directory: str, args: tuple):
        """The call through the signature's stored executable, or
        :data:`_ASIDE` where the jit has to take it."""
        static = self._static
        sig = tuple(a if i in static else getattr(a, "shape", None)
                    for i, a in enumerate(args))
        exe = self._programs.get(sig)
        if exe is None:
            exe = self._admit(directory, sig, args)
        if exe is _ASIDE:
            return _ASIDE
        try:
            out = exe(*[a for i, a in enumerate(args) if i not in static])
        except (TypeError, ValueError) as e:
            # the compiled call's own check, made before anything runs or
            # is donated: not this executable's arguments (another tree
            # under the same top-level shapes)
            self._programs[sig] = _ASIDE
            program_store.say(
                f"{self.program} refused its arguments "
                f"({type(e).__name__}: {str(e)[:200]}); this signature "
                f"goes through the jit from here on")
            return _ASIDE
        self._entry["hits"] += 1
        return out

    def _note_paths(self, notes: dict) -> None:
        _ledger.note_q40_paths(self._entry, notes.get("q40"))
        _ledger.note_mixer_paths(self._entry, notes)

    def _admit(self, directory: str, sig: tuple, args: tuple):
        """The executable for a signature met for the first time: loaded
        from the store, or lowered and compiled here and filed there; or
        :data:`_ASIDE`. One thread builds, the others wait for it."""
        with self._programs_lock:
            exe = self._programs.get(sig)
            if exe is None:
                exe = self._programs[sig] = self._build(directory, args)
            return exe

    def _build(self, directory: str, args: tuple):
        import jax

        plan = _plan_desc()
        if plan != "none" or jax.process_count() > 1:
            # no cell runs there: unmeasured code has no claim. An engine
            # keeps its plan, so the wrapper stops asking
            self._storable = False
            return _ASIDE
        try:
            key, devices = program_store.program_key(
                program=self.program, fun=self._fun, options=self._options,
                args=args, static=self._static, plan=plan)
        except program_store.Unkeyable as e:
            program_store.say(f"{self.program} has no key ({e}); traced at "
                              f"every start")
            return _ASIDE
        t0 = time.perf_counter()
        got = program_store.load(directory, self.program, key, devices)
        backend_s = 0.0
        if got is not None:
            compiled, notes = got
            source = "store"
        else:
            # lower and compile ONCE (it reads and feeds the XLA cache
            # exactly as the jitted call would), under a window of this
            # thread's so that the trace's path notes are gathered
            with _thread_window() as notes:
                compiled = self._jitted.lower(*args).compile()
            backend_s = notes["backend_s"]
            fresh = self._observed and not notes["n_cache_hit"]
            notes = {k: v for k, v in notes.items()
                     if k == "q40" or k in _MIXER_GAUGES}
            if fresh or program_store.serializes_again():
                program_store.save(directory, self.program, key, compiled,
                                   notes)
            else:
                program_store.say(
                    "XLA:CPU cannot serialize again what its persistent "
                    "cache served; such programs are served from their "
                    "compiles and traced at the next start", once="cpu")
            source = "trace"
        seconds = time.perf_counter() - t0
        analysis = None
        try:
            signature = _signature(args, {})
            if _ledger.analyze:
                analysis = analyze_compiled(self.program, compiled,
                                            scope=self.scope)
        except Exception as e:  # noqa: BLE001 — never break the dispatch
            analysis = {"error": f"{type(e).__name__}: {e}"}
            signature = {}
        self._note_paths(notes)
        _ledger.record(self._entry, seconds, signature, plan, analysis,
                       backend_s=backend_s, source=source)
        return compiled

    def lower(self, *args, **kwargs):
        # an AOT lowering traces too (the start-up report's programs): give
        # note_q40_path a window, and keep it out of a dispatch's own
        with _thread_window() as win:
            try:
                return self._jitted.lower(*args, **kwargs)
            finally:
                self._note_paths(win)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def observe(jitted, *, scope: str, program: str, fun=None,
            options: dict | None = None) -> ObservedJit:
    """Wrap a jitted callable for the compile ledger (plan_scoped_jit's
    hook point). ``fun`` (what was jitted) and ``options`` (the jit's) are
    what the program store's key needs; without them the wrapper only
    observes."""
    return ObservedJit(jitted, scope, program, fun=fun, options=options)


# -- HBM startup report --------------------------------------------------------


def _gb(n: float) -> str:
    return f"{n / 1024 ** 3:.2f} GB" if n >= 1024 ** 2 else f"{n / 1024:.0f} kB"


def hbm_budget_line(engine) -> str:
    """The one-line per-device HBM budget: the shape-algebra estimate
    (runtime/hbm.py) against the limit the device reports."""
    from .hbm import device_memory_bytes

    est = engine.hbm_estimate
    limit = device_memory_bytes()
    return (f"🧮 HBM budget/device: weights {_gb(est['weights_bytes'])} + "
            f"KV {_gb(est['kv_bytes'])} over {engine.tp * engine.pp} "
            f"shard(s) + margin → need {_gb(est['need_per_device'])}"
            + (f" of {_gb(limit)}" if limit else " (device limit unknown)"))


def startup_line(engine) -> str:
    """The engine's start-up stamps (``engine.startup_s``: seconds per
    build phase, the serving generator's included once it is built), one
    line beside the HBM budget, and the decoder family's words on its
    layers (models/family.py)."""
    from ..models.family import family_of

    parts = getattr(engine, "startup_s", None) or {}
    return (f"🧮 start-up: {sum(parts.values()):.2f} s ("
            + ", ".join(f"{k} {v:.2f}" for k, v in parts.items()) + ")"
            + family_of(engine.cfg).describe(engine.cfg, engine))


def q40_paths_line(scope: str) -> str:
    """Which path ``ops.linear`` gave the Q40 matmuls of each program traced
    so far in ``scope`` (:func:`note_q40_path`): the line that says whether
    the fused dequant-GEMV engaged. Empty before any such trace."""
    by_program = _ledger.q40_paths(scope)
    if not by_program:
        return ""
    return "🧮 q40 matmuls: " + "; ".join(
        f"{program} " + " / ".join(f"{n[p]} {p}" for p in Q40_PATHS)
        for program, n in by_program.items())


def mixer_paths_line(scope: str, kind: str) -> str:
    """Which path each program's recurrent mixers of ``kind`` (``gdn``,
    ``ssd``) took, by form (:func:`note_gdn_path`, :func:`note_ssd_path`);
    empty for a model without them."""
    by_program = _ledger.mixer_paths(scope, kind)
    if not by_program:
        return ""
    return f"🧮 {_MIXER_TITLES[kind]}: " + "; ".join(
        f"{program} " + ", ".join(f"{n} {key}" for key, n in sorted(paths.items()))
        for program, paths in by_program.items())


def _emit_q40_paths(scope: str, emit) -> None:
    for kind in _MIXER_GAUGES:
        line = mixer_paths_line(scope, kind)
        if line:
            emit(line)
    line = q40_paths_line(scope)
    if line:
        emit(line)


def compile_report(scope: str, emit=print) -> None:
    """One line per program the ledger saw compile in ``scope``: wall and
    XLA-backend seconds (backend 0 = the persistent cache served it) and,
    where a miss was analyzed (``ledger().analyze``), its measured HBM bytes
    and compiled Pallas kernels."""
    events = [e for e in _ledger.snapshot()["events"] if e["scope"] == scope]
    loaded = [e for e in events if e["source"] == "store"]
    emit(f"🧮 compiles: {len(events)} in {scope}, "
         f"{sum(e['compile_s'] for e in events):.2f} s wall, "
         f"{sum(e['backend_s'] for e in events):.2f} s in the XLA backend"
         + (f"; {len(loaded)} loaded from the program store in "
            f"{sum(e['compile_s'] for e in loaded):.2f} s, "
            f"{len(events) - len(loaded)} traced" if loaded else ""))
    for e in events:
        a = e["analysis"] or {}
        kern = a.get("kernels")
        if e["source"] == "store":
            head = (f"🧮   loaded {e['program']}: {e['compile_s']:.2f} s "
                    f"from the program store")
        else:
            head = (f"🧮   compiled {e['program']}: {e['compile_s']:.2f} s "
                    f"wall, {e['backend_s']:.2f} s backend")
        emit(head
             + (f", HBM {_gb(a['hbm_total_bytes'])}"
                if a.get("hbm_total_bytes") else "")
             + ("" if kern is None else ", Pallas kernels: "
                + (" ".join(f"{k}x{n}" for k, n in sorted(kern.items()))
                   or "none")))
    _emit_q40_paths(scope, emit)


def hbm_startup_report(engine, emit=print) -> dict:
    """Per-device HBM budget table at engine load: the shape-algebra
    estimate (runtime/hbm.py — weights + KV + margin) cross-checked against
    what XLA actually allocated per program (``memory_analysis()`` of the
    AOT-compiled decode and prefill programs). Emits one table to the log,
    publishes ``dllama_program_hbm_bytes`` / ``dllama_program_flops``
    gauges, and returns the raw dict. Cost: one AOT compile per program,
    shared with the first dispatch via the persistent compile cache."""
    from .hbm import device_memory_bytes

    est = dict(engine.hbm_estimate)
    limit = device_memory_bytes()
    report: dict = {
        "weights_bytes": est["weights_bytes"],
        "kv_bytes": est["kv_bytes"],
        "need_per_device": est["need_per_device"],
        "limit_bytes": limit,
        "n_shards": engine.tp * engine.pp,
        "programs": {},
    }
    emit(hbm_budget_line(engine))
    emit(startup_line(engine))
    max_temp = 0
    scope = getattr(engine, "introspection_scope", "default")
    for name in ("decode", "prefill"):
        try:
            info = analyze_compiled(*engine.aot_compiled(name), scope=scope)
        except Exception as e:  # noqa: BLE001 — report is advisory, say why
            emit(f"🧮   program {name}: analysis unavailable "
                 f"({type(e).__name__}: {e})")
            report["programs"][name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        report["programs"][name] = info
        hbm = info.get("hbm_bytes") or {}
        max_temp = max(max_temp, hbm.get("temp", 0))
        flops = info.get("flops")
        emit(f"🧮   program {name}: temp {_gb(hbm.get('temp', 0))}, "
             f"output {_gb(hbm.get('output', 0))}, "
             f"args {_gb(hbm.get('argument', 0))}"
             + (f", {flops:.3g} flops/dispatch" if flops else ""))
    _emit_q40_paths(scope, emit)
    actual = est["weights_bytes"] + est["kv_bytes"]
    actual = actual // max(1, report["n_shards"]) + max_temp
    report["actual_floor_bytes"] = actual
    if limit and actual > limit:
        emit(f"⚠️ 🧮 measured floor {_gb(actual)} exceeds the device limit "
             f"{_gb(limit)} — the shape-algebra margin was optimistic")
    elif actual > est["need_per_device"]:
        emit(f"⚠️ 🧮 measured floor {_gb(actual)} exceeds the hbm.py "
             f"estimate {_gb(est['need_per_device'])} — estimate drift, "
             f"check runtime/hbm.py against this model")
    return report
