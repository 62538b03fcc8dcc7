"""Flight recorder, latency attribution, and Perfetto timeline export
(runtime/flightrec.py + the serving/engine wiring).

The ISSUE-7 acceptance criterion lives here: a continuous-batching run
on the CPU mesh (staggered arrivals through the paged scheduler) must
export a Perfetto-loadable Chrome trace in which every request's TTFT
attribution phases sum to within 5% of the measured wall TTFT — and the
compile ledger must show zero post-steady compiles with the recorder
enabled (recording is trace-invisible)."""

import gc
import json
import pathlib
import threading
import time
import urllib.request

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import failpoints, flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.serving import BatchScheduler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

GOLDEN = pathlib.Path(__file__).parent / "goldens" / "flight_dump.json"


@pytest.fixture(autouse=True)
def _fresh_recorder():
    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()


@pytest.fixture(scope="module")
def paged_engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("flightrec")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(31)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return InferenceEngine(str(mpath), str(tpath), tp=1, temperature=0.0,
                           seed=3, kv_block_size=16)


# -- recorder unit behavior --------------------------------------------------


def test_rings_bounded_and_idle_ticks_dropped():
    rec = flightrec.FlightRecorder()
    for i in range(flightrec.RING_TICKS + 40):
        rec.begin_tick(queue_depth=1)
        rec.note("admit", i)
        rec.end_tick()
    snap = rec.snapshot()
    assert len(snap["ticks"]) == flightrec.RING_TICKS
    assert snap["ticks"][-1]["tick"] == flightrec.RING_TICKS + 40
    # an idle tick (no decisions, no dispatch, no prefill) is dropped but
    # still numbers — the gap marks the idle stretch in a dump
    rec.begin_tick(queue_depth=0)
    rec.end_tick()
    snap = rec.snapshot()
    assert snap["tick_seq"] == flightrec.RING_TICKS + 41
    assert snap["ticks"][-1]["tick"] == flightrec.RING_TICKS + 40


def test_events_ring_stamps_current_tick():
    rec = flightrec.FlightRecorder()
    rec.note("submit", 7)           # outside any tick: tick 0
    rec.begin_tick(queue_depth=1)
    rec.note("admit", 7, slot=0)
    rec.note_dispatch(1.25, 1, 1)
    rec.note_prefill(7, 0.5, 8)
    rec.end_tick(blocks={"total": 4, "used": 1, "shared": 0})
    evs = rec.snapshot()["events"]
    assert [e["tick"] for e in evs] == [0, 1]
    t = rec.snapshot()["ticks"][-1]
    assert t["decisions"] == [{"event": "admit", "rid": 7, "slot": 0}]
    assert t["dispatch_ms"] == 1.25 and t["prefill_tokens"] == 8
    assert t["blocks"]["total"] == 4


def test_dump_writes_postmortem_and_rate_limits(tmp_path, monkeypatch):
    monkeypatch.setenv("DLLAMA_FLIGHT_DIR", str(tmp_path))
    dumps = tm.registry().counter(tm.FLIGHT_DUMPS)
    d0 = dumps.total(reason="test_reason")
    rec = flightrec.FlightRecorder()
    rec.begin_tick(queue_depth=1)
    rec.note("retire", 7, reason="kv_block_exhaustion", slot=0)
    rec.end_tick()
    path = rec.dump("test_reason", victims=[7], info={"error": "boom"})
    assert path is not None and str(tmp_path) in path
    doc = json.loads(pathlib.Path(path).read_text())
    assert doc["reason"] == "test_reason" and doc["victims"] == [7]
    assert doc["info"]["error"] == "boom"
    assert doc["ticks"][-1]["decisions"][0]["reason"] == "kv_block_exhaustion"
    assert "spans" in doc and "events" in doc
    assert dumps.total(reason="test_reason") == d0 + 1
    # same reason inside the rate window: skipped, no second file
    assert rec.dump("test_reason", victims=[8]) is None
    assert dumps.total(reason="test_reason") == d0 + 1
    # a different reason is a different incident: not rate-limited
    assert rec.dump("other_reason") is not None


# -- the loop's whole life: gaps, CPU clocks, the stall ring -------------------


class _Clock:
    """A monotonic clock that moves ``step_ns`` a read, and by hand."""

    def __init__(self, step_ns: int = 100_000):
        self.t, self.step_ns = 1_000_000_000, step_ns

    def __call__(self) -> int:
        self.t += self.step_ns
        return self.t


def test_tick_record_carries_gap_unphased_and_cpu_times():
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    rec.begin_tick(queue_depth=1)
    with rec.tick_phase("emit"):
        rec.note("admit", 1)
    rec.end_tick()
    clk.t += 7_000_000                   # 7 ms between the two ticks
    rec.begin_tick(queue_depth=1)
    with rec.tick_phase("emit"):
        rec.note("admit", 2)
    clk.t += 2_000_000                   # 2 ms after the phase, inside the tick
    rec.end_tick(compiles=0, loads=0)
    first, second = rec.snapshot()["ticks"]
    assert first["gap_before_ms"] == 0.0                 # no tick before it
    assert second["gap_before_ms"] == pytest.approx(7.1)
    wall = (second["t_end_ns"] - second["t_start_ns"]) / 1e6
    assert second["unphased_ms"] == pytest.approx(wall - second["phases"]["emit"])
    assert second["unphased_ms"] == pytest.approx(2.2)
    assert "cpu_ms" not in second        # no profiler: the CPU clocks are sampled, not read a tick
    assert rec.snapshot()["stalls"] == []


class _Span:
    """A profiler annotation that listens (``jax.profiler.TraceAnnotation``'s
    four methods), kept by name for the test to read."""

    made: list = []

    def __init__(self, name, **metadata):
        self.name, self.metadata, self.open = name, dict(metadata), None
        _Span.made.append(self)

    def __enter__(self):
        self.open = True
        return self

    def __exit__(self, *exc):
        self.open = False

    def is_enabled(self):
        return True

    def set_metadata(self, **metadata):
        assert self.open, "metadata set on a closed span"
        self.metadata.update(metadata)


def test_under_a_profiler_every_tick_and_gap_carries_its_cpu_time(monkeypatch):
    monkeypatch.setattr(_Span, "made", [])
    monkeypatch.setattr(flightrec, "_annotate", _Span)
    thread = iter(range(0, 10**9, 2_000_000))           # 2 ms of the loop thread's CPU between two readings
    both = []
    rec = flightrec.FlightRecorder(clock=_Clock(), thread_clock=lambda: next(thread),
                                   cpu_clock=lambda: both.append(1) or (0, 0))
    for i in range(3):
        rec.begin_tick(n_active=1)
        rec.note("admit", i)
        rec.end_tick()
    rec.loop_edge()
    roots = [sp for sp in _Span.made if sp.name == tm.TICK_SPAN]
    gaps = [sp for sp in _Span.made if sp.name == tm.LOOP_GAP_SPAN]
    assert len(roots) == 3 and len(gaps) == 3 and not any(sp.open for sp in _Span.made)
    # one reading an edge, shared by the gap that ends and the tick that starts there
    assert [sp.metadata["cpu_us"] for sp in roots] == [2000, 2000, 2000]
    assert [sp.metadata.get("cpu_us") for sp in gaps] == [2000, 2000, None]     # the last one closed at the loop's end
    assert [t["cpu_ms"] for t in rec.snapshot()["ticks"]] == [2.0, 2.0, 2.0]
    assert len(both) == 1                # the process's clock: the thread's first sample alone


def test_the_cpu_clocks_are_sampled_and_a_stall_is_reckoned_against_the_usual_rate():
    """No profiler: one reading of both clocks a sampling period, none a
    tick; a stalled tick's CPU time is what the window since the last
    reading spent, less the usual rate over the part of it outside the
    tick."""
    clk = _Clock(step_ns=250_000)                        # a tick every 0.75 ms: its two edges and its decision
    cpu = {"thread": 0, "proc": 0, "reads": 0}

    def cpu_clock():
        cpu["reads"] += 1
        return cpu["thread"], cpu["proc"]

    rec = flightrec.FlightRecorder(clock=clk, cpu_clock=cpu_clock)

    def ticks(n):
        for i in range(n):
            rec.begin_tick(n_active=1)
            rec.note("admit", i)
            rec.end_tick(compiles=0, loads=0)
            cpu["thread"] += 75_000                      # 0.1 ms of the thread's CPU a ms of wall, 0.3 of the process's
            cpu["proc"] += 225_000

    ticks(3600)                                          # 2.7 s of the loop's life: the first reading and two samples
    assert cpu["reads"] == 3 and "cpu_ms" not in rec.snapshot()["ticks"][-1]
    assert rec._cpu_usual == pytest.approx((0.1, 0.3), rel=0.01)
    # 0.15 s on, a tick that sits 300 ms in a phase: the loop thread asleep, another thread busy
    ticks(200)
    rec.begin_tick(n_active=1)
    with rec.tick_phase("emit"):
        clk.t += 300_000_000
        cpu["proc"] += 290_000_000
    rec.note("admit", 0)
    rec.end_tick(compiles=0, loads=0)
    (stall,) = rec.snapshot()["stalls"]
    assert 1000.0 < stall["cpu_window_ms"] < 1300.0                          # ordinary ticks since the last sample, and the tick
    assert stall["cpu_ms"] == pytest.approx(0.0, abs=1.0)                    # what was read is the usual tenth of the rest
    assert stall["proc_cpu_ms"] == pytest.approx(290.0, abs=2.0)             # read, less the usual three tenths of the rest
    assert stall["cause"] == "other_thread" and cpu["reads"] == 4


def test_gap_is_forgotten_at_a_loop_edge_and_across_threads():
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)

    def tick():
        rec.begin_tick()
        rec.note("admit", 1)
        rec.end_tick()

    tick()
    clk.t += 400_000_000
    rec.loop_edge()                      # another scheduler's loop starts
    tick()
    clk.t += 400_000_000
    other = threading.Thread(target=tick)    # CPU clocks of two threads do not subtract
    other.start()
    other.join(30)
    assert not other.is_alive()
    assert [t["gap_before_ms"] for t in rec.snapshot()["ticks"]] == [0.0, 0.0, 0.0]
    assert rec.snapshot()["stalls"] == []


def test_an_overslept_idle_tick_is_dropped_but_leaves_its_stall(capsys):
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    stalls = tm.registry().counter(tm.LOOP_STALLS)
    ms = tm.registry().counter(tm.LOOP_STALL_MS)
    n0, ms0 = stalls.total(where="idle_wait"), ms.total(where="idle_wait")
    rec.begin_tick(queue_depth=3, n_active=0)
    with rec.tick_phase("idle_wait"):
        clk.t += 400_000_000
    rec.end_tick()
    clk.t += 500_000_000                 # and half a second before the next tick
    rec.begin_tick(queue_depth=4, n_active=2)
    rec.end_tick()
    snap = rec.snapshot()
    assert snap["ticks"] == []           # both idle: dropped
    idle, gap = snap["stalls"]
    assert idle["where"] == "idle_wait" and idle["tick"] == 1 and idle["queue_depth"] == 3
    assert idle["ms"] == pytest.approx(400.1) and idle["cause"] == "process_stood_still"
    assert gap["where"] == tm.BETWEEN_TICKS and gap["tick"] == 2 and gap["n_active"] == 2
    assert gap["ms"] == pytest.approx(500.1) and gap["t_start_ns"] == idle["t_start_ns"] + 400_200_000
    assert "between" not in gap
    assert stalls.total(where="idle_wait") == n0 + 1
    assert stalls.total(where="idle_wait", cause="process_stood_still") >= 1
    assert ms.total(where="idle_wait") == pytest.approx(ms0 + 400.1)
    # one line a second: the second stall ended 0.5 s after the first
    err = capsys.readouterr().err
    assert err.count("loop stall") == 1 and "400 ms in idle_wait, tick 1" in err
    assert "-> process_stood_still" in err


def test_a_gap_between_two_phases_names_the_phases_on_either_side():
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    rec.begin_tick(n_active=1)
    with rec.tick_phase("admit_begin"):
        pass
    clk.t += 300_000_000
    with rec.tick_phase("step_prepare"):
        pass
    rec.note("admit", 1)
    clk.t += 260_000_000
    rec.end_tick(compiles=0, loads=0)
    seam, tail = rec.snapshot()["stalls"]
    assert seam["where"] == tail["where"] == tm.BETWEEN_PHASES
    assert seam["between"] == ["admit_begin", "step_prepare"] and seam["ms"] == pytest.approx(300.1)
    assert tail["between"] == ["step_prepare", "tick_end"]
    t = rec.snapshot()["ticks"][-1]
    assert t["unphased_ms"] >= seam["ms"] + tail["ms"]


@pytest.mark.parametrize("queued_ms,stalled", [(300.0, False), (100.0, True), (None, True)])
def test_a_wait_behind_queued_chunks_is_held_to_what_it_lasted_beyond_them(queued_ms, stalled):
    """A long prompt's chunks are enqueued in a burst and the first step
    waits for all of them: 400 ms behind 300 ms of queued work is no stall,
    behind 100 ms it is."""
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    rec.begin_tick(n_active=1)
    with rec.tick_phase("step_wait"):
        clk.t += 400_000_000
    with rec.tick_phase("emit"):
        if queued_ms is not None:
            rec.note_queued(queued_ms)
        rec.note("first_token", 1)
    rec.end_tick(compiles=0, loads=0)
    stalls = rec.snapshot()["stalls"]
    assert [st["where"] for st in stalls] == (["step_wait"] if stalled else [])
    assert all(st["ms"] == pytest.approx(400.1) for st in stalls)


# (where, ms, cpu_ms, proc_cpu_ms, gc_ns, compiles, loads) -> cause: the rule's
# table row by row, then where two rows apply
_CAUSE_ROWS = [
    (("step_dispatch", 900.0, 850.0, 880.0, (0, 0, 0), 1, 0), "compile"),
    (("step_dispatch", 400.0, 2.0, 3.0, (0, 0, 0), 0, 2), "compile"),                # a load from the store
    (("emit", 300.0, 280.0, 290.0, (0, 0, 200_000_000), 0, 0), "collector"),
    (("emit", 300.0, 290.0, 295.0, (0, 0, 0), 0, 0), "own_code"),
    (("emit", 300.0, 5.0, 280.0, (0, 0, 0), 0, 0), "other_thread"),
    (("between_ticks", 1000.0, 1.0, 900.0, (0, 0, 0), 0, 0), "other_thread"),
    (("step_wait", 2580.0, 1.2, 3.9, (0, 0, 0), 0, 0), "device_wait"),
    (("step_upload", 300.0, 0.1, 0.2, (0, 0, 0), 0, 0), "device_wait"),
    (("step_dispatch", 300.0, 0.1, 0.2, (0, 0, 0), 0, 0), "device_wait"),
    (("prefill_dispatch", 300.0, 0.1, 0.2, (0, 0, 0), 0, 0), "device_wait"),
    (("idle_wait", 1000.0, 0.3, 4.0, (0, 0, 0), 0, 0), "process_stood_still"),
    (("between_ticks", 1000.0, 0.3, 4.0, (0, 0, 0), 0, 0), "process_stood_still"),
    (("between_phases", 300.0, 0.3, 4.0, (0, 0, 0), 0, 0), "process_stood_still"),
    (("emit", 300.0, 100.0, 120.0, (0, 0, 0), 0, 0), "unknown"),                    # neither small nor half
    (("step_wait", 300.0, 5.0, 100.0, (0, 0, 0), 0, 0), "unknown"),
    (("step_wait", 300.0, None, None, (0, 0, 0), 0, 0), "unknown"),                 # this thread's first reading
    # precedence: a compile that also collected and burned CPU is a compile; a
    # collection on the loop thread is CPU of its own and still the collector's
    (("step_dispatch", 900.0, 850.0, 880.0, (0, 0, 600_000_000), 3, 0), "compile"),
    (("emit", 300.0, 290.0, 295.0, (1_000_000, 0, 250_000_000), 0, 0), "collector"),
    (("step_wait", 300.0, 200.0, 290.0, (0, 0, 0), 0, 0), "own_code"),
]


@pytest.mark.parametrize("row,cause", _CAUSE_ROWS, ids=[f"{c}-{i}" for i, (_r, c) in enumerate(_CAUSE_ROWS)])
def test_stall_cause_table(row, cause, capsys):
    where, ms, cpu_ms, proc_cpu_ms, gc_ns, compiles, loads = row
    rec = flightrec.FlightRecorder().note_stall(
        where, 5_000_000_000, ms, tick=7, n_active=13, cpu_ms=cpu_ms, proc_cpu_ms=proc_cpu_ms,
        gc_ns=gc_ns, compiles=compiles, loads=loads)
    assert rec["cause"] == cause and cause in tm.STALL_CAUSES
    assert rec["gc_ms"] == pytest.approx(sum(gc_ns) / 1e6)
    assert rec["gc_gen"] == (max(g for g, ns in enumerate(gc_ns) if ns) if any(gc_ns) else None)
    line = capsys.readouterr().err
    assert f"loop stall {ms:.0f} ms in {where}, tick 7, 13 rows" in line and line.rstrip().endswith("-> " + cause)


def test_every_cause_is_reached_by_the_table():
    assert {c for _r, c in _CAUSE_ROWS} == set(tm.STALL_CAUSES)


def test_no_stall_in_ordinary_ticks_the_ring_is_bounded_and_reset_empties_it(capsys):
    clk = _Clock(step_ns=1_000_000)      # 1 ms a clock read: ticks of ~30 ms
    rec = flightrec.FlightRecorder(clock=clk)
    for i in range(200):
        rec.begin_tick(queue_depth=1, n_active=2)
        for ph in ("deadlines", "admit_begin", "step_prepare", "step_upload", "step_dispatch",
                   "step_wait", "emit", "bookkeeping"):
            with rec.tick_phase(ph):
                pass
        rec.note("admit", i)
        clk.t += 200_000_000             # no ONE interval reaches a quarter second
        rec.end_tick(compiles=0, loads=0)
        clk.t += 200_000_000
    assert rec.snapshot()["stalls"] == [] and capsys.readouterr().err == ""
    for i in range(flightrec.RING_STALLS + 6):
        rec.note_stall("step_wait", clk(), 300.0, tick=i)
    stalls = rec.snapshot()["stalls"]
    assert len(stalls) == flightrec.RING_STALLS and stalls[-1]["tick"] == flightrec.RING_STALLS + 5
    assert rec.payload("test")["stalls"] == stalls
    rec.reset()
    assert rec.snapshot()["stalls"] == []


def test_a_slow_collection_inside_a_phase_is_the_collectors(monkeypatch):
    clk = _Clock()
    rec = flightrec.FlightRecorder(clock=clk)
    rec.begin_tick(n_active=1)           # installs the one gc hook
    reads = []

    def collector_clock():               # the hook reads it at a collection's start and stop
        reads.append(clk.t)
        if len(reads) % 2 == 0:
            clk.t += 300_000_000         # the collection took 0.3 s
        return clk()

    monkeypatch.setattr(flightrec._collector, "clock", collector_clock)
    gc.disable()                         # no collection but the forced one moves the clock
    try:
        with rec.tick_phase("emit"):
            gc.collect()
        rec.note("admit", 1)
        rec.end_tick(compiles=0, loads=0)
    finally:
        gc.enable()
    (stall,) = rec.snapshot()["stalls"]
    assert stall["where"] == "emit" and stall["cause"] == "collector"
    assert stall["gc_gen"] == 2 and 300.0 <= stall["gc_ms"] <= stall["ms"]
    assert gc.callbacks.count(flightrec._collector._on_gc) == 1


def test_timeline_carries_the_stalls_as_instants():
    rec = flightrec.FlightRecorder(clock=_Clock())
    rec.begin_tick(queue_depth=1)
    rec.note("admit", 1)
    rec.end_tick()
    rec.note_stall(tm.BETWEEN_PHASES, 2_000_000_000, 310.0, tick=1, cpu_ms=0.1, proc_cpu_ms=0.4,
                   cpu_window_ms=330.0, between=["emit", "bookkeeping"])
    trace = json.loads(json.dumps(flightrec.to_chrome_trace(dict(rec.snapshot(), spans=[])), allow_nan=False))
    assert flightrec.validate_chrome_trace(trace) == []
    (inst,) = [e for e in trace["traceEvents"] if e.get("cat") == "stall"]
    assert inst["ph"] == "i" and (inst["pid"], inst["tid"]) == (1, 0) and inst["ts"] == 2_000_000.0
    assert inst["args"]["cause"] == "process_stood_still" and inst["args"]["between"] == ["emit", "bookkeeping"]


class _PlainEdges(flightrec.FlightRecorder):
    """The tick's two edges as they were before PR 56 (no gap, no CPU clock,
    no stall check): what the micro-benchmark below is held against."""

    def begin_tick(self, queue_depth=0, n_admissions=0, n_active=0):
        with self._lock:
            self._tick_seq += 1
            seq = self._tick_seq
            self._cur = {"tick": seq, "t_start_ns": self._clock(), "queue_depth": queue_depth,
                         "n_admissions": n_admissions, "decisions": [], "dispatch_ms": 0.0,
                         "prefill_ms": 0.0, "prefill_tokens": 0, "decode_tokens": 0, "n_active": 0,
                         "phase_spans": []}
        if flightrec._annotate is not None:
            self._root = flightrec._annotate(tm.TICK_SPAN, tick=seq, n_active=n_active)
            self._root.__enter__()

    def end_tick(self, blocks=None, **extra):
        root, self._root = self._root, None
        if root is not None:
            root.__exit__(None, None, None)
        with self._lock:
            cur, self._cur = self._cur, None
            cur["t_end_ns"] = self._clock()
            cur["phases"] = flightrec._phase_sums(cur["phase_spans"])
            cur.update(extra)
            if not (cur["decisions"] or cur["dispatch_ms"] or cur["prefill_ms"] or cur["prefill_tokens"]):
                return
            self._ticks.append(cur)
        self._m_ticks.inc()


def test_tick_edge_cost():
    """Microseconds a tick the two edges cost with and without the gap, the
    CPU clocks and the stall check (``python -m pytest -k tick_edge_cost -s``
    prints them; CHANGES.md keeps the chip host's). Not a timing assertion:
    only that the edges stay microseconds."""
    def per_tick_us(rec, n=3000):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                rec.begin_tick(queue_depth=0, n_admissions=0, n_active=1)
                rec.end_tick(blocks=None, slots=None, prefill_budget=256, compiles=0, loads=0)
            best = min(best, (time.perf_counter_ns() - t0) / n / 1e3)
        return best

    plain, whole = per_tick_us(_PlainEdges()), per_tick_us(flightrec.FlightRecorder())
    print(f"\ntick_edge_cost: {whole:.2f} us a tick with the gap, CPU clocks and stall check, "
          f"{plain:.2f} us without: {whole - plain:+.2f} us")
    assert whole < 1000.0


# -- golden chrome-trace fixture ---------------------------------------------


def test_golden_fixture_converts_to_valid_chrome_trace():
    """The checked-in mini-run dump converts to strict, Perfetto-shaped
    trace JSON: monotonic per-track timestamps, every submitted request
    a complete flow, tick/counter/slot tracks all present."""
    data = json.loads(GOLDEN.read_text(encoding="utf-8"))
    trace = flightrec.to_chrome_trace(data)
    # strict JSON round-trip (no NaN/Inf, no non-serializable leftovers)
    trace = json.loads(json.dumps(trace, allow_nan=False))
    rids = {e["rid"] for e in data["events"] if e["event"] == "submit"}
    assert rids == {0, 1, 2}
    assert flightrec.validate_chrome_trace(trace, expect_rids=rids) == []
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"tick 1", "queue_depth", "active_slots", "kv_blocks"} <= names
    # per-slot request tracks: slices for both slots under pid 2
    assert {e["tid"] for e in evs if e.get("pid") == 2 and e["ph"] == "X"} \
        == {0, 1}
    # every phase of the vocabulary the fixture uses is rendered
    phases = {e["args"]["phase"] for e in evs
              if e["ph"] == "X" and e.get("pid") == 2}
    assert {"queue", "admit", "prefill", "prefill_chunk", "decode"} <= phases


def test_golden_fixture_divides_every_tick_into_phases():
    """The fixture's ticks carry ``phases``/``phase_spans`` from the closed
    TICK_PHASES vocabulary, and the timeline nests one slice per span under
    its tick on the scheduler track."""
    data = json.loads(GOLDEN.read_text(encoding="utf-8"))
    for t in data["ticks"]:
        assert t["phases"] and set(t["phases"]) <= set(tm.TICK_PHASES)
        assert sum(ms for _n, _o, ms in t["phase_spans"]) \
            == pytest.approx(sum(t["phases"].values()))
    evs = flightrec.to_chrome_trace(data)["traceEvents"]
    ticks = {e["name"]: e for e in evs if e.get("cat") == "tick"}
    slices = [e for e in evs if e.get("cat") == "tick_phase"]
    assert len(slices) == sum(len(t["phase_spans"]) for t in data["ticks"])
    assert {"admit_begin", "prefill_dispatch", "admit_commit", "step_wait",
            "emit"} <= {e["name"] for e in slices}
    for e in slices:
        t = ticks[f"tick {e['args']['tick']}"]
        assert (e["pid"], e["tid"]) == (t["pid"], t["tid"])
        assert t["ts"] <= e["ts"] and e["ts"] + e["dur"] <= t["ts"] + t["dur"] + 1e-6


def test_validator_catches_regressions_and_broken_flows():
    data = json.loads(GOLDEN.read_text(encoding="utf-8"))
    trace = flightrec.to_chrome_trace(data)
    # missing request
    probs = flightrec.validate_chrome_trace(trace, expect_rids={0, 99})
    assert any("request 99" in p for p in probs)
    # ts regression on a track
    bad = json.loads(json.dumps(trace))
    xs = [e for e in bad["traceEvents"] if e["ph"] == "X"]
    xs[-1]["ts"] = 0.0
    assert any("regressed" in p
               for p in flightrec.validate_chrome_trace(bad))
    # broken flow chain
    bad2 = json.loads(json.dumps(trace))
    for e in bad2["traceEvents"]:
        if e["ph"] == "f" and e.get("id") == 1:
            e["ph"] = "t"
    assert any("flow 1" in p for p in flightrec.validate_chrome_trace(bad2))


def test_timeline_cli_converts_offline(tmp_path):
    from dllama_tpu.serve.cli import main

    out = tmp_path / "trace.json"
    rc = main(["timeline", "--dump", str(GOLDEN), "--out", str(out)])
    assert rc == 0
    trace = json.loads(out.read_text())
    assert trace["traceEvents"]
    assert flightrec.validate_chrome_trace(trace) == []


# -- the ISSUE-7 acceptance run ----------------------------------------------


def _run_wave(engine, sched, prompts, max_tokens=8):
    """Submit a wave, recording an INDEPENDENT wall-TTFT observation per
    request (this thread's clock at the submit call → the first on_token
    callback) — read at different sites than the scheduler's attribution
    stamps, so the ≤5% reassembly assertion is a real cross-check, not
    algebra on the same numbers."""
    t_sub, t_first = {}, {}
    reqs = []
    for i, p in enumerate(prompts):
        ids = engine.tokenizer.encode(p, is_start=True)

        def cb(tok, piece, i=i):
            t_first.setdefault(i, tm.now_ns())

        t_sub[i] = tm.now_ns()
        reqs.append(sched.submit(ids, max_tokens, stop_on_eos=False,
                                 on_token=cb))
    for r in reqs:
        assert r.done.wait(timeout=300)
        assert r.error is None, r.error
    walls = {i: (t_first[i] - t_sub[i]) / 1e6 for i in t_first}
    return reqs, walls


def test_continuous_run_attribution_trace_and_zero_post_steady_compiles(
        paged_engine):
    """6 requests through 2 paged slots (queueing, chunked-prefill
    interleave, a shared prefix): every request's TTFT attribution
    phases sum to within 5% of its wall TTFT, the live rings export a
    validating Chrome trace containing every request as a complete flow,
    and the compile ledger shows ZERO post-steady compiles with the
    recorder on."""
    sched = BatchScheduler(paged_engine, n_slots=2)
    scope = paged_engine.introspection_scope
    led = introspection.ledger()
    retrace = tm.registry().counter(tm.RETRACE_UNEXPECTED)
    try:
        prompts = ["hello world hello world", "hello", " world hello",
                   "hello world hello", "hell", "he"]
        reqs, walls = _run_wave(paged_engine, sched, prompts)

        # -- TTFT attribution: phases reassemble the INDEPENDENTLY
        # measured wall TTFT (≤ 5%; small absolute floor for clock-site
        # skew on sub-ms walls) --
        for i, r in enumerate(reqs):
            bd = r.ttft_breakdown()
            assert bd is not None, r.rid
            total = (bd["queue_ms"] + bd["admission_ms"]
                     + bd["prefill_ms"] + bd["first_decode_ms"])
            assert abs(total - walls[i]) <= 0.05 * walls[i] + 2.0, \
                (r.rid, total, walls[i])
        # the histogram twins were recorded once per request
        h = tm.registry().histogram(tm.TTFT_ATTRIB_MS)
        for ph in ("queue", "admission", "prefill", "first_decode"):
            assert h.count(phase=ph) >= len(reqs), ph
        itl = tm.registry().histogram(tm.ITL_ATTRIB_MS)
        assert itl.count(cause="step") >= 1
        assert itl.count(cause="preempt") >= 1

        # -- flight ring: ticks with decisions + block occupancy --
        snap = flightrec.recorder().snapshot()
        assert snap["ticks"], "no work-carrying ticks recorded"
        assert any(t.get("blocks") for t in snap["ticks"])
        assert any(t.get("dispatch_ms", 0) > 0 for t in snap["ticks"])
        events = snap["events"]
        for r in reqs:
            got = {e["event"] for e in events if e["rid"] == r.rid}
            assert {"submit", "admit", "decode_armed", "first_token",
                    "retire"} <= got, (r.rid, got)

        # -- Chrome trace export of the live rings --
        data = dict(snap)
        data["spans"] = tm.tracer().raw_spans()
        trace = json.loads(json.dumps(flightrec.to_chrome_trace(data),
                                      allow_nan=False))
        assert flightrec.validate_chrome_trace(
            trace, expect_rids={r.rid for r in reqs}) == []

        # -- zero post-steady compiles with the recorder enabled --
        assert led.steady(scope), "scheduler never reached steady state"
        compiles_at_steady = led.compile_count(scope)
        r_before = retrace.total()
        _run_wave(paged_engine, sched, ["hello world", " world"])
        assert led.compile_count(scope) == compiles_at_steady
        assert retrace.total() == r_before
    finally:
        sched.close()


# -- a stall driven through the real scheduler ----------------------------------


@pytest.fixture
def warm_sched(paged_engine):
    """A hand-driven scheduler (this thread is the loop) whose programs are
    compiled, and a recorder that has forgotten the warm-up."""
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        _drive(sched, [_submit(paged_engine, sched, "hello world hello")])
        flightrec.recorder().reset()
        yield sched
    finally:
        failpoints.registry().clear()
        sched.close()


def _submit(engine, sched, prompt, max_tokens=4, **kw):
    return sched.submit(engine.tokenizer.encode(prompt, is_start=True), max_tokens, stop_on_eos=False, **kw)


def _drive(sched, reqs, limit=400):
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            return
        sched._tick()
    raise AssertionError("requests did not finish")


def _by_the_rule(stall: dict) -> str:
    return flightrec.stall_cause(stall["where"], stall["ms"], stall["cpu_ms"], stall["proc_cpu_ms"],
                                 stall["gc_ms"], stall["compiles"], stall["loads"])


def test_a_sleep_between_two_phases_leaves_one_attributed_record(paged_engine, warm_sched, capsys):
    """The ``step`` failpoint fires between ``_tick_body``'s phases: no span
    of the tick lies over its 0.3 s, and until PR 56 nothing recorded it."""
    failpoints.arm("step", "sleep", times=1, delay_s=0.3)
    _drive(warm_sched, [_submit(paged_engine, warm_sched, "gamma delta")])
    snap = flightrec.recorder().snapshot()
    (stall,) = [r for r in snap["stalls"] if r["where"] == tm.BETWEEN_PHASES]
    assert 300.0 <= stall["ms"] < 600.0 and stall["between"][1] == "step_prepare"
    assert stall["cause"] == _by_the_rule(stall)
    assert stall["cause"] in ("process_stood_still", "unknown")       # asleep: neither CPU clock moved
    assert stall["cpu_ms"] < 30.0 and stall["compiles"] == stall["loads"] == 0
    tick = next(t for t in snap["ticks"] if t["tick"] == stall["tick"])
    assert tick["unphased_ms"] >= stall["ms"]
    assert stall["ms"] <= stall["cpu_window_ms"] <= stall["ms"] + 1e-6 * flightrec.CPU_SAMPLE_NS + 100.0
    assert 0.0 <= stall["proc_cpu_ms"]
    assert tick["t_start_ns"] <= stall["t_start_ns"] <= tick["t_end_ns"]
    err = capsys.readouterr().err
    assert err.count("loop stall") == 1 and "in between_phases (" in err


def test_a_hang_inside_a_guarded_dispatch_names_the_phase_it_sat_in(paged_engine, warm_sched):
    failpoints.arm("step_hang", "sleep", times=1, delay_s=0.3)
    _drive(warm_sched, [_submit(paged_engine, warm_sched, "epsilon zeta")])
    (stall,) = flightrec.recorder().snapshot()["stalls"]
    assert stall["where"] in ("prefill_dispatch", "step_upload") and "between" not in stall
    assert stall["cause"] == _by_the_rule(stall) and stall["cause"] in ("device_wait", "unknown")


def test_a_busy_callback_is_the_loops_own_code(paged_engine, warm_sched):
    burned = []

    def on_token(_tok, _piece):
        if not burned:                   # 0.3 s of this thread's CPU, once
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 300_000_000:
                pass
            burned.append(1)

    _drive(warm_sched, [_submit(paged_engine, warm_sched, "eta theta", on_token=on_token)])
    (stall,) = flightrec.recorder().snapshot()["stalls"]
    assert stall["where"] == "emit" and stall["cpu_ms"] >= 300.0
    assert stall["cause"] == _by_the_rule(stall)
    assert stall["cause"] == "own_code" or stall["ms"] >= 2 * stall["cpu_ms"]    # unless the machine stole half


def test_stats_line_shows_blocks_and_attribution(paged_engine):
    """Satellite: the periodic --stats line surfaces the paged block-pool
    gauges (blocks=used/total shared=N) and the TTFT attribution p50s."""
    sched = BatchScheduler(paged_engine, n_slots=2)
    try:
        _run_wave(paged_engine, sched, ["hello world", "hello"])
        line = tm.stats_line()
        assert "blocks=" in line and "/" in line.split("blocks=")[1]
        assert "shared=" in line
        assert "ttft[q/a/p/d]=" in line
    finally:
        sched.close()


# -- HTTP surface: /debug/flight, /debug/timeline, the timing block ----------


@pytest.fixture(scope="module")
def flight_server(tmp_path_factory):
    from http.server import ThreadingHTTPServer

    from dllama_tpu.serve.api import BatchedApiState, make_handler

    d = tmp_path_factory.mktemp("flight_api")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(37)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     rng)
    td = byte_vocab_tokenizer()
    td.chat_template = "<|start_header_id|>"  # detected as llama3
    tfile.write_tfile(tpath, td)
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, temperature=0.0,
                          seed=3, kv_block_size=16)
    state = BatchedApiState(eng, n_slots=2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()
    state.close()
    eng.close()


def test_debug_flight_timeline_routes_and_timing_block(flight_server):
    url = flight_server
    body = {"messages": [{"role": "user", "content": "hello world"}],
            "max_tokens": 4, "timing": True}
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        out = json.loads(r.read())
    # opt-in timing block: phases sum to the reported wall TTFT
    t = out["timing"]
    parts = (t["queue_ms"] + t["admission_ms"] + t["prefill_ms"]
             + t["first_decode_ms"])
    assert abs(parts - t["ttft_ms"]) <= 0.05 * max(t["ttft_ms"], 1e-3)
    assert "decode_step_ms" in t and "preempt_ms" in t
    # without the opt-in the response stays OpenAI-shaped
    del body["timing"]
    req = urllib.request.Request(
        url + "/v1/chat/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        assert "timing" not in json.loads(r.read())

    with urllib.request.urlopen(url + "/debug/flight", timeout=30) as r:
        flight = json.loads(r.read())
    assert flight["ticks"] and flight["events"]
    with urllib.request.urlopen(url + "/debug/timeline", timeout=30) as r:
        trace = json.loads(r.read())
    assert trace["traceEvents"]
    assert flightrec.validate_chrome_trace(trace) == []
