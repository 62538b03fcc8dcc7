"""The four per-layer metrics that read the loop's life outside its phases
(PR 56), each the way ``benchmark/run.py`` reads it (its
``layer_metrics/<name>.json`` names the reader and its arguments):
``loop_stalled_share`` / ``loop_stall_ms_max`` from the flight recorder's stall
ring on a synthetic context, ``idle_between_ticks_share`` / ``tick_cpu_ms_p50``
from ``benchmark/fixtures/tiny_loop.xplane.pb``, whose numbers are worked by
hand in ``make_tiny_loop_xplane.py``'s docstring."""

import importlib.util
import json
import os
import sys
import types

import pytest

from dllama_tpu.runtime import flightrec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
LOOP = os.path.join(BENCH, "fixtures", "tiny_loop.xplane.pb")
BEFORE = os.path.join(BENCH, "fixtures", "tiny_spans.xplane.pb")    # a program before PR 56
WINDOW_S = 0.020


@pytest.fixture(scope="module")
def bench():
    """``benchmark/`` on the path, as ``run.py`` puts it."""
    sys.path.insert(0, BENCH)
    try:
        import program_spans
        import trace_reduce
        yield types.SimpleNamespace(spans=program_spans, reduce=trace_reduce)
    finally:
        sys.path.remove(BENCH)


def _read(metric: str, ctx: dict):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    path = os.path.join(BENCH, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, **spec.get("args", {}))


def _traced(bench, trace_file: str) -> dict:
    return {"trace": bench.reduce.reduce(trace_file, WINDOW_S), "cell": {"name": "fixture"},
            "program_spans": bench.spans.load(trace_file)}


def _sent(*spans_s):
    """What the load generator keeps of a request, as far as the reader looks:
    ``(t_submit, last token's time)`` in seconds of the monotonic clock."""
    return [types.SimpleNamespace(t_submit=a, token_times=[a + 0.1, b]) for a, b in spans_s]


def test_fixture_file_is_what_its_generator_writes(bench):
    from jax.profiler import ProfileData

    gen = os.path.join(BENCH, "fixtures", "make_tiny_loop_xplane.py")
    mod_spec = importlib.util.spec_from_file_location("make_tiny_loop_xplane", gen)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)

    def events(pd):
        return [(plane.name, ln.name, ev.name, round(ev.start_ns), round(ev.duration_ns), sorted(dict(ev.stats).items()))
                for plane in pd.planes for ln in plane.lines for ev in ln.events]

    made = ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(mod.TEXT))
    assert events(ProfileData.from_file(LOOP)) == events(made) and len(events(made)) == 21


@pytest.mark.parametrize("metric,want", [("idle_between_ticks_share", 100 * 2.3 / 20),
                                         ("tick_cpu_ms_p50", 1.5)])
def test_span_readers_on_the_fixture(bench, metric, want):
    assert _read(metric, _traced(bench, LOOP)) == pytest.approx(want, rel=1e-6)


def test_tick_cpu_reads_a_level_off_a_clock_that_ticks_at_ten_milliseconds(bench):
    """On the chip's host a tick's ``cpu_us`` is 0 or 10,000: the median tick
    reads 0, a run of 50 reads the level, and the median over runs leaves a
    run that burned CPU all through (a stall of the loop's own) out."""
    ticks = [{"tick": i, "start": i * 0.016, "end": i * 0.016 + 0.015,
              "children": [("step_wait", i * 0.016 + 0.001, i * 0.016 + 0.015, {})]} for i in range(170)]
    cpu_us = {i: (10_000.0 if i % 8 == 0 or 100 <= i < 150 else 0.0) for i in range(170)}      # 1.25 ms a tick; 10 in run 2
    ctx = {"trace": {}, "cell": {"name": "fixture"}, "program_spans": {"ticks": ticks, "idle": None, "path": None},
           "loop_life": {"gaps": [], "cpu_us": cpu_us, "busy": []}}
    # runs of 50: ticks 0-49 hold 7 tens (1.4 ms a tick), 50-99 hold 6 (1.2), 100-149 read 10 each; 150-169 are left over
    assert _read("tick_cpu_ms_p50", ctx) == pytest.approx(1.4)


def test_between_ticks_idle_is_part_of_the_unspanned_idle(bench):
    """The old reader, untouched, calls everything outside a phase unspanned
    (2.4 ms of the fixture's hull); the gaps hold 2.3 of it."""
    ctx = _traced(bench, LOOP)
    unspanned = ctx["program_spans"]["idle"]["unspanned_s"]
    assert unspanned == pytest.approx(2.4e-3, rel=1e-6)
    assert _read("idle_between_ticks_share", ctx) * WINDOW_S / 100 <= unspanned


@pytest.mark.parametrize("metric", ["idle_between_ticks_share", "tick_cpu_ms_p50"])
def test_span_readers_find_nothing_in_a_program_without_the_spans(bench, metric):
    assert _read(metric, _traced(bench, BEFORE)) is None             # spans, no gap and no cpu_us
    assert _read(metric, {"trace": None, "cell": {"name": "fixture"}}) is None      # an untraced run


@pytest.fixture
def ring(monkeypatch):
    """A recorder of the test's own in the process-wide one's place."""
    rec = flightrec.FlightRecorder()
    monkeypatch.setattr(flightrec, "recorder", lambda: rec)
    return rec


def _stall(rec, at_s: float, ms: float):
    return rec.note_stall("step_wait", int(at_s * 1e9), ms, tick=1)


def test_stall_readers_count_what_began_inside_the_window(bench, ring, capsys):
    _stall(ring, 90.0, 700.0)          # warm-up's: before the first submit
    _stall(ring, 120.0, 2580.0)
    _stall(ring, 146.0, 300.0)         # after the last token
    ctx = {"sent": _sent((100.0, 130.0), (101.0, 145.0))}
    assert _read("loop_stalled_share", ctx) == pytest.approx(100 * 2.58 / 45)      # 5.7
    assert _read("loop_stall_ms_max", ctx) == 2580.0
    assert "loop stall" in capsys.readouterr().err


def test_stall_readers_read_zero_where_nothing_stalled(bench, ring):
    ctx = {"sent": _sent((100.0, 130.0))}
    assert _read("loop_stalled_share", ctx) == 0.0 and _read("loop_stall_ms_max", ctx) == 0.0


def test_stall_readers_find_nothing_in_a_program_without_the_ring(bench, monkeypatch):
    before = types.SimpleNamespace(snapshot=lambda: {"tick_seq": 0, "ticks": [], "events": [], "dumps": []})
    monkeypatch.setattr(flightrec, "recorder", lambda: before)
    ctx = {"sent": _sent((100.0, 130.0))}
    assert _read("loop_stalled_share", ctx) is None and _read("loop_stall_ms_max", ctx) is None
