"""Serving flight recorder — per-tick scheduler history, black-box dumps,
and Perfetto (Chrome trace-event) timeline export.

PR 1's metrics are aggregates and the span ring (telemetry.SpanTracer)
only sees per-request phases; neither records *why* a scheduler tick
admitted, requeued, preempted, or stalled — exactly the information a
prefill/decode token-budget tuning pass (or a postmortem of a wedged
batch) needs. This module is that record:

* **Tick ring** — one structured record per work-carrying scheduler tick
  (batch composition, admit/retire/requeue/preempt/spec_degraded
  decisions with machine-readable reasons, the prefill-vs-decode token
  split, speculative draft/accept counts, dispatch wall time, block-pool
  occupancy, queue depth). Bounded
  (:data:`RING_TICKS`), host-only, always on: recording is one lock +
  dict append per event against multi-ms ticks, touches no jitted
  program, and is therefore trace-invisible (zero post-steady compiles —
  ledger-asserted in tests/test_flightrec.py).
* **Event ring** — per-request lifecycle events (submit / admit /
  decode_armed / first_token / requeue / preempt / retire / timeout)
  from any thread, stamped with the tick they happened in.
* **Postmortem dumps** — :meth:`FlightRecorder.dump` writes the last N
  ticks + events + the span ring to a JSON crash file (rate-limited per
  reason). The watchdog stall path, scheduler crash supervision, and
  KV-block exhaustion all call it, so a dead batch always leaves a
  readable black box naming the victim requests and the decisions
  leading in. ``GET /debug/flight`` serves the live rings.
* **Chrome-trace export** — :func:`to_chrome_trace` renders the rings +
  span ring as Perfetto-loadable trace-event JSON (per-slot request
  tracks, a scheduler tick track, queue-depth/occupancy/block counter
  tracks, one flow per request). ``GET /debug/timeline`` serves it live;
  ``python -m dllama_tpu timeline --dump f.json`` converts offline.

* **Tick phases** — :meth:`FlightRecorder.tick_phase` divides the open
  tick into the closed ``telemetry.TICK_PHASES`` vocabulary: each phase
  is a ``jax.profiler.TraceAnnotation`` (``dllama.tick.<name>`` under
  the root ``dllama.tick`` span, which carries the tick number — so a
  profiler capture shows the loop thread on the device lanes' clock and
  names the same tick as this ring), an entry of the tick record's
  ``phases``, and a series of ``dllama_tick_phase_ms_total{phase}``.
  Inside ``step_wait`` each blocking fetch is a nested
  ``dllama.step.fetch`` annotation (:func:`fetch_span`), the profiler's
  alone.
* **The loop's whole life** — from one tick's end to the next one's
  start is an interval of its own (``telemetry.BETWEEN_TICKS``: the
  annotation ``dllama.loop.between_ticks``, the next tick record's
  ``gap_before_ms``, a series of the phase counter). Under a profiler
  both edges of every tick read the loop thread's CPU clock (``cpu_ms``
  on the record, ``cpu_us`` on the annotations); always, one edge every
  :data:`CPU_SAMPLE_NS` reads it and the process's, so that a stall can
  be attributed.
* **Stall ring** — any ONE interval (a phase span, a gap between two
  phases, a gap between two ticks) of :data:`STALL_MIN_MS` or more
  leaves a record that outlives the tick ring (:data:`RING_STALLS`),
  attributed by :func:`stall_cause` from the CPU clocks, the collector's
  time (one ``gc.callbacks`` hook) and the compile ledger's counts, and
  one ``loop stall`` line on stderr.

Dependency-free (stdlib + runtime.telemetry only — importable without
jax: the serving layer injects the annotation factory through
:func:`set_annotation_factory`). Like the span ring, the recorder is
process-global: two schedulers in one process interleave their ticks
(request ids are per-scheduler counters), so this is a debug view, not
an audit log.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import tempfile
import threading
import time
from collections import deque

from . import telemetry

RING_TICKS = 256
RING_EVENTS = 4096
RING_STALLS = 64
# one interval of the loop's life this long is a stall: the longest
# honest one in any benchmark cell is a 130 ms prefill chunk (idle_wait
# sleeps at most 50 ms), the shortest stall met 0.43 s
STALL_MIN_MS = 250.0
# the loop thread reads its CPU clocks (its own and the process's) at a
# tick's end when the last reading is this old, and wherever a stall is
# found; while a profiler listens its own clock alone at both edges of
# every tick. Each read is a system call (no vDSO serves a CPU clock):
# 0.3 us on a bare host, 6 us on the sealed machine that holds the chip,
# where the clocks also tick at 10 ms, so that a reading a tick would say
# little and cost four calls; a reading a second costs nothing
CPU_SAMPLE_NS = 1_000_000_000
# at most one "loop stall" line a second on stderr (every stall is still
# recorded and counted)
STALL_LOG_MIN_INTERVAL_S = 1.0
# phases that call into the runtime: a stall there with neither CPU clock
# moving is blocked on the device side, not a process that stood still
_RUNTIME_PHASES = frozenset(
    ("prefill_dispatch", "step_upload", "step_dispatch", "step_wait"))
# one postmortem per reason per window: exhaustion under sustained
# pressure must not spray a file per tick
DUMP_MIN_INTERVAL_S = 30.0

# spans with slot == -1 (the single-sequence engine path) render on one
# synthetic "engine" thread in the trace
_NO_SLOT_TID = 999

# ``jax.profiler.TraceAnnotation`` once runtime/serving.py is imported;
# None keeps this module jax-free (phases then only feed the tick record
# and the counter)
_annotate = None
_NO_SPAN = contextlib.nullcontext()


def set_annotation_factory(factory) -> None:
    """Install the profiler-annotation factory tick phases open
    (``factory(name, **metadata)`` → a context manager with
    ``set_metadata``); ``None`` turns annotations off."""
    global _annotate
    _annotate = factory


def fetch_span(what: str):
    """``with flightrec.fetch_span("tokens"):`` — one blocking fetch of a
    step output, nested in the step's ``step_wait`` phase as the
    profiler annotation ``telemetry.STEP_FETCH_SPAN`` (``what=<output>``).
    The trace is its one record: nothing is written to the tick record
    or the registry and no lock is taken, so with no profiler running it
    is the annotation's ~0.4 µs no-op."""
    if _annotate is None:
        return _NO_SPAN
    return _annotate(telemetry.STEP_FETCH_SPAN, what=what)


def _cpu_ns() -> tuple[int, int]:
    """``(loop thread's, process's)`` CPU time in ns: what tells a loop
    that worked from one that was kept off its CPU. Two system calls (no
    vDSO serves a CPU clock), the second one a walk over every thread."""
    return time.thread_time_ns(), time.process_time_ns()


class _CollectorWatch:
    """The garbage collector's running time by generation, from ONE
    ``gc.callbacks`` hook for the process (installed by the first tick any
    recorder opens). A collection stops every Python thread wherever it was
    triggered, so the total is the process's; a recorder reads ``ns_by_gen``
    at a tick's edges and takes differences."""

    def __init__(self):
        self.clock = telemetry.now_ns
        self.ns_by_gen = [0, 0, 0]
        self.installed = False
        self._t0 = 0

    def install(self) -> None:
        if not self.installed:
            self.installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = self.clock()
        else:
            self.ns_by_gen[info["generation"]] += self.clock() - self._t0


_collector = _CollectorWatch()


def stall_cause(where: str, ms: float, cpu_ms: float | None,
                proc_cpu_ms: float | None, gc_ms: float = 0.0,
                compiles: int = 0, loads: int = 0) -> str:
    """Why an interval of ``ms`` lasted that long, one name of
    ``telemetry.STALL_CAUSES``: the first row that applies (TELEMETRY.md
    prints the table). "Small" is under a tenth of the interval; CPU
    times of ``None`` (never read on this thread) decide nothing."""
    small = 0.1 * ms
    if compiles or loads:
        return "compile"
    if gc_ms > 0.5 * ms:
        return "collector"
    if cpu_ms is None or proc_cpu_ms is None:
        return "unknown"
    if cpu_ms > 0.5 * ms:
        return "own_code"
    if cpu_ms < small and proc_cpu_ms > 0.5 * ms:
        return "other_thread"
    if cpu_ms < small and proc_cpu_ms < small:
        return ("device_wait" if where in _RUNTIME_PHASES
                else "process_stood_still")
    return "unknown"


def _phase_sums(phase_spans) -> dict:
    """``{phase: ms}`` over a tick's ``[name, offset_ms, ms]`` spans, in
    first-seen order."""
    sums: dict[str, float] = {}
    for name, _off, ms in phase_spans:
        sums[name] = sums.get(name, 0.0) + ms
    return sums


class _TickPhase:
    """One ``with recorder.tick_phase(name)`` span (see
    :meth:`FlightRecorder.tick_phase`)."""

    __slots__ = ("_rec", "_name", "_ann", "t0_ns", "t1_ns")

    def __init__(self, rec: "FlightRecorder", name: str):
        self._rec = rec
        self._name = name
        self._ann = None

    def __enter__(self):
        if _annotate is not None:
            self._ann = _annotate(f"{telemetry.TICK_SPAN}.{self._name}")
            self._ann.__enter__()
        self.t0_ns = self._rec._clock()
        return self

    def next_phase(self, name: str) -> None:
        """End this phase and start ``name`` at the same instant, inside
        one ``with`` (a guard held across both phases then costs neither
        a gap). ``t0_ns`` moves to the new phase's start."""
        self.__exit__(None, None, None)
        self._name, self._ann = name, None
        self.__enter__()

    @property
    def traced(self) -> bool:
        """A profiler is listening: metadata that costs something to make
        is worth making."""
        return self._ann is not None and self._ann.is_enabled()

    def set(self, **metadata) -> None:
        """Attach ``key=value`` metadata to the phase's profiler
        annotation (shown as the event's stats in the trace)."""
        if self._ann is not None:
            self._ann.set_metadata(**metadata)

    def __exit__(self, *exc):
        self.t1_ns = self._rec._clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._note_phase(self._name, self.t0_ns, self.t1_ns)
        return False


class FlightRecorder:
    """Bounded tick + event rings for one process's serving loop(s).

    Thread model: ticks are opened/closed by the scheduler loop thread;
    events may arrive from any thread (submit runs on HTTP handlers).
    All state is under one lock; every operation is O(1) appends.

    ``clock`` is injectable (monotonic ns) so the golden-fixture
    generator can record deterministic timelines, and ``cpu_clock``
    (``() -> (thread's, process's) CPU ns``) and ``thread_clock`` (the
    first of the two alone) beside it."""

    def __init__(self, clock=None, cpu_clock=None, thread_clock=None):
        self._clock = clock or telemetry.now_ns
        self._cpu_clock = cpu_clock or _cpu_ns
        self._thread_clock = thread_clock or time.thread_time_ns
        self._lock = threading.Lock()
        self._ticks: deque = deque(maxlen=RING_TICKS)
        self._events: deque = deque(maxlen=RING_EVENTS)
        self._stalls: deque = deque(maxlen=RING_STALLS)
        self._cur: dict | None = None
        self._root = None  # the open tick's dllama.tick annotation
        # the open tick's opening edge: (the collector's ns_by_gen,
        # n_active going in)
        self._opened = ([0, 0, 0], 0)
        # the last tick's closing edge: (thread ident, t_end_ns,
        # ns_by_gen); None until a loop has closed a tick
        self._edge: tuple | None = None
        # the loop thread's last reading of its CPU clocks: (thread ident,
        # the edge's monotonic ns, thread's CPU ns, process's CPU ns), and
        # the two clocks' usual rates (CPU ns a wall ns) over the last
        # sampling period that held no stall
        self._cpu_mark: tuple | None = None
        self._cpu_usual = (0.0, 0.0)
        # under a profiler: the loop thread's CPU clock at the open tick's
        # start and at the last tick's end
        self._thread_open: int | None = None
        self._thread_edge: int | None = None
        self._gap_ann = None  # the open between-ticks annotation
        self._stall_logged_ns: int | None = None
        self._tick_seq = 0
        self._dump_seq = 0
        self._last_dump: dict[str, float] = {}
        self._dumps: deque = deque(maxlen=16)
        reg = telemetry.registry()
        self._m_ticks = reg.counter(telemetry.FLIGHT_TICKS)
        self._m_phase_ms = reg.counter(telemetry.TICK_PHASE_MS)
        self._m_stalls = reg.counter(telemetry.LOOP_STALLS)
        self._m_stall_ms = reg.counter(telemetry.LOOP_STALL_MS)
        self._m_dumps = reg.counter(telemetry.FLIGHT_DUMPS)

    def reset(self) -> None:
        """Forget everything, including the dump rate limiter (tests)."""
        self.loop_edge()
        with self._lock:
            self._ticks.clear()
            self._events.clear()
            self._stalls.clear()
            self._cur = None
            self._cpu_mark = None
            self._stall_logged_ns = None
            self._tick_seq = 0
            self._dump_seq = 0
            self._last_dump.clear()
            self._dumps.clear()

    # -- tick lifecycle (scheduler loop thread) -----------------------------

    def loop_edge(self) -> None:
        """A scheduler's loop starts or has ended: what precedes its next
        tick is no gap between two ticks of one loop."""
        gap, self._gap_ann, self._edge = self._gap_ann, None, None
        if gap is not None:
            gap.__exit__(None, None, None)

    def _cpu_sample(self, ident: int, t_ns: int, inside_ms: float | None):
        """Read the loop thread's CPU clocks at the edge ``t_ns`` and make
        the reading the mark. ``inside_ms`` None: a sampling period ended
        without a stall, and what it spent a wall ns becomes the usual
        rate. Else the last ``inside_ms`` before ``t_ns`` are a tick or a
        gap that holds a stall: returns what THAT spent, as what the whole
        window since the mark spent less the usual rate over the rest of
        it: ``{"cpu_ms", "proc_cpu_ms", "cpu_window_ms"}`` (``{}`` where
        this thread had read nothing to subtract from)."""
        thread_ns, proc_ns = self._cpu_clock()
        mark, self._cpu_mark = self._cpu_mark, (ident, t_ns, thread_ns,
                                                proc_ns)
        if mark is None or mark[0] != ident or t_ns <= mark[1]:
            self._cpu_usual = (0.0, 0.0)
            return {}
        window_ns = t_ns - mark[1]
        spent = (thread_ns - mark[2], proc_ns - mark[3])
        if inside_ms is None:
            self._cpu_usual = (spent[0] / window_ns, spent[1] / window_ns)
            return {}
        rest_ns = max(0.0, window_ns - inside_ms * 1e6)
        cpu, proc = (max(0.0, ns - usual * rest_ns) / 1e6
                     for ns, usual in zip(spent, self._cpu_usual))
        return {"cpu_ms": cpu, "proc_cpu_ms": proc,
                "cpu_window_ms": window_ns / 1e6}

    def begin_tick(self, queue_depth: int = 0, n_admissions: int = 0,
                   n_active: int = 0) -> None:
        """Open a tick record and, under a profiler, the root
        ``dllama.tick`` annotation (``tick`` = this record's number,
        ``n_active`` = live slots going in) the tick's phases nest in.
        What lay between the last tick's end and here (same thread) closes
        as the between-ticks interval: its annotation, this record's
        ``gap_before_ms``, the phase counter's ``between_ticks`` series,
        and a stall record where it lasted :data:`STALL_MIN_MS`."""
        _collector.install()
        ident = threading.get_ident()
        gc_ns = _collector.ns_by_gen[:]
        gap, self._gap_ann = self._gap_ann, None
        edge, self._edge = self._edge, None
        if edge is not None and edge[0] != ident:
            edge = None   # another loop's tick: CPU clocks of two threads
        thread_ns = None
        if gap is not None:
            if gap.is_enabled() and edge is not None \
                    and self._thread_edge is not None:
                thread_ns = self._thread_clock()
                gap.set_metadata(
                    cpu_us=(thread_ns - self._thread_edge) // 1000)
            gap.__exit__(None, None, None)
        with self._lock:
            self._tick_seq += 1
            seq = self._tick_seq
            t_ns = self._clock()
            gap_ms = (t_ns - edge[1]) / 1e6 if edge is not None else 0.0
            self._cur = {"tick": seq,
                         "t_start_ns": t_ns,
                         "gap_before_ms": gap_ms,
                         "queue_depth": queue_depth,
                         "n_admissions": n_admissions,
                         "decisions": [], "dispatch_ms": 0.0,
                         "prefill_ms": 0.0, "prefill_tokens": 0,
                         "decode_tokens": 0, "n_active": 0,
                         "phase_spans": []}
        self._opened = (gc_ns, n_active)
        self._thread_open = None
        if _annotate is not None:
            self._root = _annotate(telemetry.TICK_SPAN, tick=seq,
                                   n_active=n_active)
            self._root.__enter__()
            if self._root.is_enabled():
                self._thread_open = (thread_ns if thread_ns is not None
                                     else self._thread_clock())
        mark = self._cpu_mark
        if mark is None or mark[0] != ident:
            self._cpu_sample(ident, t_ns, None)    # this thread's first
        if edge is not None:
            self._m_phase_ms.inc(gap_ms, phase=telemetry.BETWEEN_TICKS)
            if gap_ms >= STALL_MIN_MS:
                self.note_stall(
                    telemetry.BETWEEN_TICKS, edge[1], gap_ms, tick=seq,
                    n_active=n_active, queue_depth=queue_depth,
                    **self._cpu_sample(ident, t_ns, gap_ms),
                    gc_ns=[b - a for a, b in zip(edge[2], gc_ns)])

    def tick_phase(self, name: str) -> _TickPhase:
        """``with recorder.tick_phase("emit"):`` — one phase of the
        scheduler's tick (``name`` from ``telemetry.TICK_PHASES``, a
        literal at every call site: dlint span-phases holds the
        vocabulary closed). Three records, one clock read each side:
        the profiler annotation ``dllama.tick.<name>``, the open tick
        record's ``phase_spans`` entry (``[name, offset_ms, ms]``;
        ``phases[name]``, the sum over repeats, is filled in when the
        tick closes), and ``dllama_tick_phase_ms_total``. With no
        profiler running the annotation is a no-op (~0.4 µs)."""
        return _TickPhase(self, name)

    def _note_phase(self, name: str, t0_ns: int, t1_ns: int) -> None:
        ms = (t1_ns - t0_ns) / 1e6
        with self._lock:
            cur = self._cur
            if cur is not None:
                cur["phase_spans"].append(
                    [name, (t0_ns - cur["t_start_ns"]) / 1e6, ms])
        self._m_phase_ms.inc(ms, phase=name)

    def note(self, event: str, rid: int = -1, reason: str = "",
             **extra) -> None:
        """One lifecycle/decision event: always appended to the event ring
        (stamped with the current tick number), and — when a tick is open
        — to that tick's decision list, so the tick record reads as "what
        the scheduler decided and why"."""
        rec = {"t_ns": self._clock(), "event": event, "rid": rid}
        if reason:
            rec["reason"] = reason
        rec.update(extra)
        with self._lock:
            rec["tick"] = self._tick_seq
            self._events.append(rec)
            if self._cur is not None:
                d = {"event": event, "rid": rid}
                if reason:
                    d["reason"] = reason
                d.update(extra)
                self._cur["decisions"].append(d)

    def note_dispatch(self, ms: float, n_active: int, emitted: int) -> None:
        """One decode dispatch inside the current tick."""
        with self._lock:
            if self._cur is None:
                return
            self._cur["dispatch_ms"] += ms
            self._cur["n_active"] = max(self._cur["n_active"], n_active)
            self._cur["decode_tokens"] += emitted

    def note_prefill(self, rid: int, ms: float, n_tokens: int) -> None:
        """One prefill chunk dispatch inside the current tick (the prefill
        side of the tick's token-budget split)."""
        with self._lock:
            if self._cur is None:
                return
            self._cur["prefill_ms"] += ms
            self._cur["prefill_tokens"] += n_tokens

    def note_queued(self, ms: float) -> None:
        """The open tick's ``step_wait`` waited behind device work that was
        queued before the step (a prompt's prefill chunks, enqueued in a
        burst), ``ms`` of it by the generator's own reckoning from recent
        such waits: the wait is held against :data:`STALL_MIN_MS` less
        that, so a long prompt is no stall and a wait that outlasts its
        queue by a quarter second still is."""
        with self._lock:
            if self._cur is not None:
                self._cur["queued_ms"] = self._cur.get("queued_ms", 0.0) + ms

    def note_spec(self, drafted: int, accepted: int) -> None:
        """One speculative verify dispatch's draft/accept counts inside
        the current tick — the tick record's view of what the verify
        width bought (accept rate per tick, next to the dispatch wall it
        cost). Zero-draft ticks are recorded too: a run of
        ``spec_draft_tokens: 0`` ticks under spec serving is the
        degraded-proposer signature a postmortem should show."""
        with self._lock:
            if self._cur is None:
                return
            self._cur["spec_draft_tokens"] = (
                self._cur.get("spec_draft_tokens", 0) + drafted)
            self._cur["spec_accept_tokens"] = (
                self._cur.get("spec_accept_tokens", 0) + accepted)

    def end_tick(self, blocks: dict | None = None, **extra) -> None:
        """Close the tick (and its root annotation). Idle ticks (no
        decisions, no dispatch, no prefill) are dropped — the ring stays
        signal-dense and tick numbering gaps mark idle stretches — but
        not before their intervals were held against
        :data:`STALL_MIN_MS`: a tick that only overslept in ``idle_wait``
        still leaves its stall record. ``extra`` lands in the record
        (``compiles`` / ``loads``: what the compile ledger counted over
        the tick, which a stall record of this tick is attributed by).
        While a profiler listens the record carries ``cpu_ms``, the loop
        thread's CPU time over the tick, and the root annotation
        ``cpu_us``."""
        ident = threading.get_ident()
        gc0, n_active = self._opened
        cpu_us = None
        root, self._root = self._root, None
        self._thread_edge = None
        if root is not None:
            if root.is_enabled():
                self._thread_edge = self._thread_clock()
                if self._thread_open is not None:
                    cpu_us = (self._thread_edge - self._thread_open) // 1000
                    root.set_metadata(cpu_us=cpu_us)
            root.__exit__(None, None, None)
        with self._lock:
            cur, self._cur = self._cur, None
            if cur is None:
                return
            t_end = cur["t_end_ns"] = self._clock()
            wall_ms = (t_end - cur["t_start_ns"]) / 1e6
            cur["phases"] = _phase_sums(cur["phase_spans"])
            unphased_ms = cur["unphased_ms"] = max(
                0.0, wall_ms - sum(cur["phases"].values()))
            if cpu_us is not None:
                cur["cpu_ms"] = cpu_us / 1e3
            if blocks is not None:
                cur["blocks"] = dict(blocks)
            cur.update(extra)
            work = bool(cur["decisions"] or cur["dispatch_ms"]
                        or cur["prefill_ms"] or cur["prefill_tokens"])
            if work:
                self._ticks.append(cur)
        gc_ns = _collector.ns_by_gen[:]
        if wall_ms >= STALL_MIN_MS:
            self._tick_stalls(cur, wall_ms, max(n_active, cur["n_active"]),
                              self._cpu_sample(ident, t_end, wall_ms),
                              [b - a for a, b in zip(gc0, gc_ns)])
        elif t_end - self._cpu_mark[1] >= CPU_SAMPLE_NS:
            self._cpu_sample(ident, t_end, None)
        self._edge = (ident, t_end, gc_ns)
        if _annotate is not None:
            self._gap_ann = _annotate(telemetry.LOOP_GAP_SPAN)
            self._gap_ann.__enter__()
        self._m_phase_ms.inc(unphased_ms, phase=telemetry.BETWEEN_PHASES)
        if work:
            self._m_ticks.inc()

    # -- stalls ---------------------------------------------------------------

    def _tick_stalls(self, cur: dict, wall_ms: float, n_active: int,
                     spent: dict, gc_ns: list[int]) -> None:
        """One stall record for every interval of the closed tick ``cur``
        (a phase span, or a gap between two phases or a phase and the
        tick's edge) of :data:`STALL_MIN_MS` or more."""
        about = dict(tick=cur["tick"], n_active=n_active,
                     queue_depth=cur["queue_depth"], **spent, gc_ns=gc_ns,
                     compiles=cur.get("compiles", 0),
                     loads=cur.get("loads", 0))
        t0, at, prev = cur["t_start_ns"], 0.0, "tick_start"
        queued = {"step_wait": cur.get("queued_ms", 0.0)}
        for name, off, ms in [*cur["phase_spans"], ["tick_end", wall_ms, 0.0]]:
            if off - at >= STALL_MIN_MS:
                self.note_stall(telemetry.BETWEEN_PHASES, t0 + int(at * 1e6),
                                off - at, between=[prev, name], **about)
            if ms - queued.get(name, 0.0) >= STALL_MIN_MS:
                self.note_stall(name, t0 + int(off * 1e6), ms, **about)
            at, prev = off + ms, name

    def note_stall(self, where: str, t_start_ns: int, ms: float, *, tick: int,
                   n_active: int = 0, queue_depth: int = 0,
                   cpu_ms: float | None = None,
                   proc_cpu_ms: float | None = None,
                   cpu_window_ms: float | None = None,
                   gc_ns=(0, 0, 0), compiles: int = 0, loads: int = 0,
                   between: list[str] | None = None) -> dict:
        """Record ONE interval of the loop's life that lasted
        :data:`STALL_MIN_MS` or more: into the stall ring, the two
        counters, and (at most once a second) a line on stderr, so that an
        untraced run says what its stall was. ``where`` is a name of
        ``telemetry.TICK_PHASES``, ``between_ticks`` or ``between_phases``
        (then ``between`` names the phases on either side). ``cpu_ms`` /
        ``proc_cpu_ms`` are the loop thread's and the process's CPU time
        over the enclosing tick or gap, reckoned (:meth:`_cpu_sample`)
        from the ``cpu_window_ms`` of wall since the last reading, at most
        :data:`CPU_SAMPLE_NS` before it (``None``: this thread had read
        nothing to subtract from);
        ``compiles`` / ``loads`` are the enclosing tick's, ``gc_ns`` the
        collector's time in the tick or gap by generation. Times are the recorder's monotonic ns (the clock of
        ``Request.t_submit``). Returns the record."""
        gens = [g for g, ns in enumerate(gc_ns) if ns > 0]
        gc_ms = sum(gc_ns) / 1e6
        rec = {"t_start_ns": int(t_start_ns), "ms": ms, "where": where,
               "tick": tick, "n_active": n_active,
               "queue_depth": queue_depth, "cpu_ms": cpu_ms,
               "proc_cpu_ms": proc_cpu_ms,
               "cpu_window_ms": cpu_window_ms, "gc_ms": gc_ms,
               "gc_gen": gens[-1] if gens else None,
               "compiles": compiles, "loads": loads,
               "cause": stall_cause(where, ms, cpu_ms, proc_cpu_ms, gc_ms,
                                    compiles, loads)}
        if between is not None:
            rec["between"] = list(between)
        t_end_ns = rec["t_start_ns"] + int(ms * 1e6)
        with self._lock:
            self._stalls.append(rec)
            last = self._stall_logged_ns
            log = last is None or \
                t_end_ns - last >= STALL_LOG_MIN_INTERVAL_S * 1e9
            if log:
                self._stall_logged_ns = t_end_ns
        self._m_stalls.inc(where=where, cause=rec["cause"])
        self._m_stall_ms.inc(ms, where=where)
        if log:
            at = where if between is None else \
                f"{where} ({between[0]} | {between[1]})"
            cpu = "cpu not read" if cpu_ms is None else (
                f"cpu {cpu_ms:.1f} ms, process cpu {proc_cpu_ms:.1f} ms")
            print(f"⚠ loop stall {ms:.0f} ms in {at}, tick {tick}, "
                  f"{n_active} rows: {cpu}, gc {gc_ms:.1f} ms, compiles "
                  f"{compiles}, loads {loads} -> {rec['cause']}",
                  file=sys.stderr, flush=True)
        return rec

    # -- views ---------------------------------------------------------------

    def snapshot(self, n_ticks: int = RING_TICKS,
                 n_events: int = RING_EVENTS) -> dict:
        """The live rings (``GET /debug/flight``), newest last; ``stalls``
        is the whole stall ring, which outlives the ticks it names. An OPEN
        tick is included as a partial record marked ``"open": true`` — a
        mid-tick postmortem (exhaustion dump, watchdog stall while the
        loop thread is wedged inside a dispatch) must show the dying
        tick's decisions, not stop at the last completed one."""
        with self._lock:
            ticks = list(self._ticks)[-n_ticks:]
            if self._cur is not None:
                cur = dict(self._cur)
                cur["decisions"] = list(cur["decisions"])
                cur["phase_spans"] = list(cur["phase_spans"])
                cur["phases"] = _phase_sums(cur["phase_spans"])
                cur["open"] = True
                ticks.append(cur)
            return {"tick_seq": self._tick_seq,
                    "ticks": ticks,
                    "events": list(self._events)[-n_events:],
                    "stalls": list(self._stalls),
                    "dumps": list(self._dumps)}

    def payload(self, reason: str, victims=(), info: dict | None = None, *,
                spans=None, requests=None) -> dict:
        """The dump-file document: rings + span ring + request timelines.
        ``spans``/``requests`` are injectable for the deterministic
        golden-fixture generator; by default they come from the live
        tracer."""
        snap = self.snapshot()
        snap.pop("dumps", None)
        tr = telemetry.tracer()
        return {"reason": reason,
                "victims": [int(v) for v in victims],
                "info": dict(info or {}),
                "t_ns": self._clock(),
                "pid": os.getpid(),
                **snap,
                "spans": tr.raw_spans() if spans is None else spans,
                "requests": (tr.recent_requests() if requests is None
                             else requests)}

    def dump(self, reason: str, victims=(),
             info: dict | None = None) -> str | None:
        """Write the black-box postmortem file; returns its path, or None
        when rate-limited (same reason within
        :data:`DUMP_MIN_INTERVAL_S`) or unwritable. Directory:
        ``DLLAMA_FLIGHT_DIR`` env, else the system temp dir."""
        now = time.monotonic()
        with self._lock:
            last = self._last_dump.get(reason)
            if last is not None and now - last < DUMP_MIN_INTERVAL_S:
                return None
            self._last_dump[reason] = now
            self._dump_seq += 1
            seq = self._dump_seq
        doc = self.payload(reason, victims, info)
        d = os.environ.get("DLLAMA_FLIGHT_DIR") or tempfile.gettempdir()
        path = os.path.join(
            d, f"dllama-flight-{os.getpid()}-{seq:03d}-{reason}.json")
        try:
            with open(path, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=1)
        except OSError as e:
            print(f"🛑 flight recorder: postmortem write to {path} failed "
                  f"({e})", flush=True)
            with self._lock:
                # a failed write must not arm the rate limiter: the next
                # incident (disk freed, dir fixed) still gets its postmortem
                if self._last_dump.get(reason) == now:
                    del self._last_dump[reason]
            return None
        self._m_dumps.inc(reason=reason)
        with self._lock:
            self._dumps.append(path)
        print(f"🧾 flight recorder: {reason} postmortem → {path} (victims: "
              f"{', '.join(str(v) for v in victims) or 'none'})", flush=True)
        return path


def ttft_phases(t_submit: int, t_admit: int, t_decode: int,
                t_first_token: int, ms_prefill: float,
                ms_pagein: float = 0.0,
                ms_kvmigrate: float = 0.0) -> dict:
    """THE TTFT phase formula — every surface that decomposes a first
    token (the ``dllama_ttft_attrib_ms`` histograms, the API ``timing``
    block on both serving paths) derives from this one function, so they can never drift apart. Timestamps
    are monotonic ns; ``ms_prefill`` is the request's own prefill chunk
    dispatch wall, ``ms_pagein`` its KV-tier page-in wall (resumed
    sessions restoring spilled blocks; 0 everywhere else), and
    ``ms_kvmigrate`` its peer-KV migration wall (fetch + stage + commit,
    or the failed attempt before a recompute fallback; 0 everywhere
    else). Phases: queue (submit → admission start minus the migration
    wall — migration runs while the request is parked pre-admission, so
    it is carved out of the queue window), kvmigrate (peer-KV fetch +
    scatter, clamped to the queue window), pagein (host→device block
    restore for a resumed session), admission (admission start →
    decode-armed minus own prefill and pagein walls — bookkeeping plus
    interleave gaps while other requests' chunks ran), prefill (own
    chunk dispatch wall; pagein+prefill clamp to the admission window),
    first_decode (decode-armed → first token). The six sum to
    ``ttft_ms`` by construction. Single-sequence serving passes
    ``t_admit == t_submit`` (no scheduler queue → queue = 0)."""
    queue_window = (t_admit - t_submit) / 1e6
    kvmigrate = min(ms_kvmigrate, queue_window)
    window = (t_decode - t_admit) / 1e6
    pagein = min(ms_pagein, window)
    prefill = min(ms_prefill, window - pagein)
    return {"ttft_ms": (t_first_token - t_submit) / 1e6,
            "queue_ms": queue_window - kvmigrate,
            "kvmigrate_ms": kvmigrate,
            "pagein_ms": pagein,
            "admission_ms": window - prefill - pagein,
            "prefill_ms": prefill,
            "first_decode_ms": (t_first_token - t_decode) / 1e6}


def record_ttft(hist, bd: dict) -> None:
    """Publish a :func:`ttft_phases` breakdown into the
    ``dllama_ttft_attrib_ms`` histogram — the one publication site for
    both serving paths, so the phase label set can never diverge."""
    hist.record(bd["queue_ms"], phase="queue")
    hist.record(bd["kvmigrate_ms"], phase="kvmigrate")
    hist.record(bd["pagein_ms"], phase="pagein")
    hist.record(bd["admission_ms"], phase="admission")
    hist.record(bd["prefill_ms"], phase="prefill")
    hist.record(bd["first_decode_ms"], phase="first_decode")


_recorder = FlightRecorder()


def recorder() -> FlightRecorder:
    """The process-wide default recorder (what the scheduler writes and
    ``/debug/flight`` serves)."""
    return _recorder


# -- Chrome trace-event export ------------------------------------------------


def _span_tid(slot: int) -> int:
    return _NO_SLOT_TID if slot < 0 else slot


def to_chrome_trace(data: dict) -> dict:
    """Render a flight snapshot/dump (``ticks`` + ``events`` + raw
    ``spans``) as Chrome trace-event JSON, loadable in Perfetto or
    chrome://tracing.

    Track layout: pid 1 = the scheduler (tid 0: one ``X`` slice per tick
    with its decisions in ``args``, one instant per stall record, plus
    queue-depth / active-slot / kv-block counter tracks); pid 2 =
    requests (one thread per slot, ``X`` slices per request phase from
    the span ring, plus one flow — ``s``/``t``/``f`` events, id = request id — chaining each request's
    phases across slots). Timestamps are the recorder's monotonic ns
    rendered as µs; spans and ticks share one clock."""
    ticks = data.get("ticks") or []
    spans = data.get("spans") or []
    out: list[dict] = []
    # a stall is an instant on the scheduler track where it began (its
    # tick may have left the tick ring long since)
    for st in data.get("stalls") or ():
        out.append({"ph": "i", "pid": 1, "tid": 0, "s": "t",
                    "ts": st["t_start_ns"] / 1e3,
                    "name": f"stall {st['where']}", "cat": "stall",
                    "args": {k: v for k, v in st.items()
                             if k != "t_start_ns"}})

    def meta(pid, tid, what, name):
        e = {"ph": "M", "pid": pid, "name": what, "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        out.append(e)

    meta(1, None, "process_name", "scheduler")
    meta(1, 0, "thread_name", "ticks")
    meta(2, None, "process_name", "requests")
    for sl in sorted({s["slot"] for s in spans}):
        meta(2, _span_tid(sl), "thread_name",
             "engine" if sl < 0 else f"slot {sl}")

    for t in ticks:
        ts = t["t_start_ns"] / 1e3
        phase_spans = t.get("phase_spans") or ()
        if "t_end_ns" in t:
            dur = max(0.0, (t["t_end_ns"] - t["t_start_ns"]) / 1e3)
        else:
            # an open tick (mid-tick postmortem) reaches as far as its
            # last finished phase
            dur = max((off + ms for _n, off, ms in phase_spans),
                      default=0.0) * 1e3
        args = {k: t[k] for k in ("queue_depth", "n_admissions", "decisions",
                                  "gap_before_ms", "unphased_ms", "cpu_ms",
                                  "proc_cpu_ms", "dispatch_ms", "prefill_ms",
                                  "prefill_tokens", "decode_tokens",
                                  "spec_draft_tokens", "spec_accept_tokens",
                                  "n_active", "slots", "blocks",
                                  "prefill_budget", "phases") if k in t}
        out.append({"ph": "X", "pid": 1, "tid": 0, "ts": ts, "dur": dur,
                    "name": f"tick {t['tick']}", "cat": "tick",
                    "args": args})
        # the tick divided: one nested slice per phase span, same track
        # (Perfetto stacks a slice under the one that contains it)
        for name, off_ms, ms in phase_spans:
            out.append({"ph": "X", "pid": 1, "tid": 0,
                        "ts": ts + off_ms * 1e3,
                        "dur": max(0.0, min(ms * 1e3, dur - off_ms * 1e3)),
                        "name": name, "cat": "tick_phase",
                        "args": {"tick": t["tick"]}})
        out.append({"ph": "C", "pid": 1, "tid": 0, "ts": ts,
                    "name": "queue_depth",
                    "args": {"requests": t.get("queue_depth", 0)}})
        out.append({"ph": "C", "pid": 1, "tid": 0, "ts": ts,
                    "name": "active_slots",
                    "args": {"slots": t.get("n_active", 0)}})
        blocks = t.get("blocks")
        if blocks:
            args = {"used": blocks.get("used", 0),
                    "shared": blocks.get("shared", 0)}
            if "host_used" in blocks:
                # tiered KV memory: the host-resident block count rides
                # the same counter track, so a Perfetto view shows spill
                # pressure next to device occupancy
                args["host_used"] = blocks.get("host_used", 0)
            out.append({"ph": "C", "pid": 1, "tid": 0, "ts": ts,
                        "name": "kv_blocks", "args": args})

    by_rid: dict[int, list[dict]] = {}
    for s in spans:
        by_rid.setdefault(s["request_id"], []).append(s)
    for rid, ss in sorted(by_rid.items()):
        ss.sort(key=lambda s: (s["start_ns"], s["end_ns"]))
        for i, s in enumerate(ss):
            tid = _span_tid(s["slot"])
            ts = s["start_ns"] / 1e3
            dur = max(0.0, (s["end_ns"] - s["start_ns"]) / 1e3)
            args = {"request_id": rid, "phase": s["phase"],
                    "n_tokens": s["n_tokens"]}
            if s.get("tenant"):
                # tenant-bound spans (telemetry.SpanTracer.bind_tenant)
                # keep their attribution in the rendered trace, so a
                # Perfetto query can slice one tenant's requests out of
                # a mixed-tenant timeline
                args["tenant"] = s["tenant"]
            out.append({"ph": "X", "pid": 2, "tid": tid, "ts": ts,
                        "dur": dur, "name": f"r{rid} {s['phase']}",
                        "cat": "request", "args": args})
            if len(ss) == 1:
                # a single-span request still gets a complete flow: start
                # at the slice begin, finish at its end
                out.append({"ph": "s", "pid": 2, "tid": tid, "ts": ts,
                            "id": rid, "name": "request", "cat": "req"})
                out.append({"ph": "f", "pid": 2, "tid": tid,
                            "ts": ts + dur, "id": rid, "bp": "e",
                            "name": "request", "cat": "req"})
                continue
            ph = "s" if i == 0 else ("f" if i == len(ss) - 1 else "t")
            flow = {"ph": ph, "pid": 2, "tid": tid, "ts": ts, "id": rid,
                    "name": "request", "cat": "req"}
            if ph == "f":
                flow["bp"] = "e"
            out.append(flow)

    # global ts sort (metadata first) keeps every track's slices
    # monotonic — the validator and Perfetto's importer both assume it
    out.sort(key=lambda e: e.get("ts", -1.0))
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def validate_chrome_trace(trace: dict, expect_rids=None) -> list[str]:
    """Structural validation of a trace produced by
    :func:`to_chrome_trace` (the golden-fixture test and the offline
    converter's ``--check`` both use it). Returns a list of problems
    (empty = valid): per-track ``X`` timestamps must be monotonic with
    non-negative durations, every flow must run start→finish, and — when
    ``expect_rids`` is given — every one of those requests must be
    present as a complete flow with at least one phase slice."""
    problems: list[str] = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents is missing or not a list"]
    last_ts: dict[tuple, float] = {}
    flows: dict[int, list[str]] = {}
    slice_rids: set[int] = set()
    for i, e in enumerate(events):
        ph = e.get("ph")
        if ph == "M":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"event {i} ({ph}): non-numeric ts {ts!r}")
            continue
        if ph == "X":
            key = (e.get("pid"), e.get("tid"))
            if ts < last_ts.get(key, float("-inf")):
                problems.append(f"track {key}: ts regressed at event {i} "
                                f"({e.get('name')})")
            last_ts[key] = ts
            if e.get("dur", 0) < 0:
                problems.append(f"event {i} ({e.get('name')}): negative dur")
            rid = (e.get("args") or {}).get("request_id")
            if rid is not None:
                slice_rids.add(rid)
        elif ph in ("s", "t", "f"):
            flows.setdefault(e.get("id"), []).append(ph)
    for fid, phs in sorted(flows.items()):
        if phs[0] != "s" or phs[-1] != "f" \
                or any(p != "t" for p in phs[1:-1]):
            problems.append(f"flow {fid}: incomplete chain {phs} "
                            f"(want s, t*, f)")
    if expect_rids is not None:
        for rid in sorted(set(expect_rids)):
            if rid not in flows:
                problems.append(f"request {rid}: no flow in the trace")
            if rid not in slice_rids:
                problems.append(f"request {rid}: no phase slice in the "
                                f"trace")
    return problems


# -- fleet timeline join ------------------------------------------------------


def fleet_chrome_trace(router_dump: dict,
                       replica_dumps: dict[str, dict]) -> dict:
    """Join the router's span ring with each replica's flight dump into
    one Chrome trace keyed by the fleet request id.

    ``router_dump`` is a ``/debug/fleet`` body (its ``spans`` list holds
    the RouterSpanRing records: string ``request_id``, ``phase`` from
    telemetry.ROUTER_PHASES, ``replica``, ``hop``). ``replica_dumps``
    maps replica name → that replica's ``/debug/flight`` body, whose
    ``spans`` carry engine-local integer request ids plus the
    ``fleet``/``hop`` fields the API layer bound, and whose ``events``
    include the ``fleet_rid`` lifecycle binding (``rid`` = local id,
    ``reason`` = fleet id, ``hop``); either join path suffices.

    Track layout: pid 1 = the router (tid = hop index, so a retried
    request's two hops stack as two visible rows), pid 2+i = one process
    per replica with the usual per-slot threads. Every joined slice
    carries ``args.request_id`` = the fleet id (a string — flow ids and
    slice ids must be one type, the validator sorts them); one flow per
    fleet id chains router and replica slices in timestamp order, so a
    retried request reads as ONE flow crossing two replica tracks.
    Replica spans with no fleet binding (direct/local requests) render
    as slices under a ``local:`` id but contribute no flow. A top-level
    ``fleetJoin`` summary counts what joined — the offline
    ``fleettrace`` CLI exits 1 when nothing does. Timestamps are each
    process's monotonic ns: same-process fleets (tests) share one
    clock; cross-process dumps keep per-track order but tracks may be
    mutually offset."""
    out: list[dict] = []
    # (ts, dur, pid, tid) per fleet id, to chain the flow afterwards
    by_fleet: dict[str, list[tuple[float, float, int, int]]] = {}

    def meta(pid, tid, what, name):
        e = {"ph": "M", "pid": pid, "name": what, "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        out.append(e)

    meta(1, None, "process_name", "router")
    router_spans = router_dump.get("spans") or []
    for hop in sorted({max(0, int(s.get("hop", 0))) for s in router_spans}
                      or {0}):
        meta(1, hop, "thread_name", f"hop {hop}")
    n_router_ids = len({s["request_id"] for s in router_spans})
    for s in router_spans:
        rid = str(s["request_id"])
        tid = max(0, int(s.get("hop", 0)))
        ts = s["start_ns"] / 1e3
        dur = max(0.0, (s["end_ns"] - s["start_ns"]) / 1e3)
        args = {"request_id": rid, "phase": s["phase"]}
        for k in ("replica", "hop", "code", "state", "load"):
            if k in s:
                args[k] = s[k]
        out.append({"ph": "X", "pid": 1, "tid": tid, "ts": ts, "dur": dur,
                    "name": f"{s['phase']}", "cat": "router", "args": args})
        by_fleet.setdefault(rid, []).append((ts, dur, 1, tid))

    joined_ids: set[str] = set()
    n_unjoined_spans = 0
    for i, (name, dump) in enumerate(sorted(replica_dumps.items())):
        pid = 2 + i
        meta(pid, None, "process_name", f"replica {name}")
        # fleet_rid lifecycle events: local int rid -> (fleet id, hop) —
        # the binding for spans emitted before bind_fleet took effect
        bind: dict[int, tuple[str, int]] = {}
        for ev in dump.get("events") or []:
            if ev.get("event") == "fleet_rid" and ev.get("reason"):
                bind[ev.get("rid")] = (str(ev["reason"]),
                                       int(ev.get("hop", 0)))
        seen_tids: set[int] = set()
        for s in dump.get("spans") or []:
            local = s.get("request_id")
            fleet, hop = (s["fleet"], s.get("hop", 0)) \
                if "fleet" in s else bind.get(local, (None, 0))
            tid = _span_tid(s.get("slot", -1))
            if tid not in seen_tids:
                seen_tids.add(tid)
                meta(pid, tid, "thread_name",
                     "engine" if tid == _NO_SLOT_TID else f"slot {tid}")
            ts = s["start_ns"] / 1e3
            dur = max(0.0, (s["end_ns"] - s["start_ns"]) / 1e3)
            rid = fleet if fleet is not None else f"local:{name}:{local}"
            args = {"request_id": rid, "phase": s["phase"],
                    "local_rid": local, "hop": hop,
                    "n_tokens": s.get("n_tokens", 0)}
            out.append({"ph": "X", "pid": pid, "tid": tid, "ts": ts,
                        "dur": dur, "name": f"{s['phase']}",
                        "cat": "replica", "args": args})
            if fleet is not None:
                joined_ids.add(fleet)
                by_fleet.setdefault(fleet, []).append((ts, dur, pid, tid))
            else:
                n_unjoined_spans += 1

    for rid, slices in sorted(by_fleet.items()):
        slices.sort()
        if len(slices) == 1:
            ts, dur, pid, tid = slices[0]
            out.append({"ph": "s", "pid": pid, "tid": tid, "ts": ts,
                        "id": rid, "name": "request", "cat": "fleet"})
            out.append({"ph": "f", "pid": pid, "tid": tid, "ts": ts + dur,
                        "id": rid, "bp": "e", "name": "request",
                        "cat": "fleet"})
            continue
        for j, (ts, dur, pid, tid) in enumerate(slices):
            ph = "s" if j == 0 else ("f" if j == len(slices) - 1 else "t")
            flow = {"ph": ph, "pid": pid, "tid": tid, "ts": ts, "id": rid,
                    "name": "request", "cat": "fleet"}
            if ph == "f":
                flow["bp"] = "e"
            out.append(flow)

    out.sort(key=lambda e: e.get("ts", -1.0))
    return {"traceEvents": out, "displayTimeUnit": "ms",
            "fleetJoin": {"router_requests": n_router_ids,
                          "joined": len(joined_ids),
                          "replicas": len(replica_dumps),
                          "unjoined_replica_spans": n_unjoined_spans}}
