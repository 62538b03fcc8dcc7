"""Tick phases (``FlightRecorder.tick_phase``, ``telemetry.TICK_PHASES``):
the scheduler's tick divided three ways at once — profiler annotations on
the device trace's clock, the flight tick record's ``phases``, and
``dllama_tick_phase_ms_total``. A tiny paged ``BatchScheduler`` serves a
few requests on the CPU; nothing here is a timing claim, only that the
three records exist, agree, and cost no compile."""

import glob
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.serving import BatchScheduler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

PROMPTS = ["hello world hello world", "hello", " world hello",
           "hello world hello", "hell", "he"]


@pytest.fixture(autouse=True)
def _fresh_recorder():
    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()


@pytest.fixture(scope="module")
def paged_engine(tmp_path_factory):
    d = tmp_path_factory.mktemp("tickphases")
    mpath, tpath = d / "m.m", d / "t.t"
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=96),
                     np.random.default_rng(41))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    eng = InferenceEngine(str(mpath), str(tpath), tp=1, temperature=0.0,
                          seed=3, kv_block_size=16)
    yield eng
    eng.close()


def _wave(engine, sched, prompts, max_tokens=8):
    reqs = [sched.submit(engine.tokenizer.encode(p, is_start=True), max_tokens,
                         stop_on_eos=False) for p in prompts]
    for r in reqs:
        assert r.done.wait(timeout=300)
        assert r.error is None, r.error
    return reqs


def _closed_ticks():
    """The ring's finished ticks (a live loop's open tick is a partial)."""
    return [t for t in flightrec.recorder().snapshot()["ticks"] if not t.get("open")]


def _drive(sched, reqs, limit=400):
    """Hand-driven scheduler (no loop thread): tick until every request is
    done; returns the number of ticks."""
    for n in range(limit):
        if all(r.done.is_set() for r in reqs):
            return n
        sched._tick()
    raise AssertionError("requests did not finish")


def test_recorded_ticks_are_tiled_by_the_closed_vocabulary(paged_engine):
    """Every work-carrying tick's ``phases`` use only TICK_PHASES names and
    never exceed the tick's own wall, and in the median tick sum to it within
    5% (a small absolute floor for ticks of a few hundred microseconds); its
    ``phase_spans`` lie inside it, in order, without overlap."""
    sched = BatchScheduler(paged_engine, n_slots=2)
    try:
        _wave(paged_engine, sched, PROMPTS)
        _wave(paged_engine, sched, PROMPTS[:3])     # warm: no compile in these
        ticks = _closed_ticks()
    finally:
        sched.close()
    assert len(ticks) >= 10
    seen, uncovered = set(), []
    for t in ticks:
        wall = (t["t_end_ns"] - t["t_start_ns"]) / 1e6
        assert set(t["phases"]) <= set(tm.TICK_PHASES), t["phases"]
        seen |= set(t["phases"])
        total = sum(t["phases"].values())
        assert total <= wall + 1e-6, (t["tick"], total, wall)
        uncovered.append(max(0.0, wall - total - 0.1) / wall)
        cursor = 0.0
        for name, off_ms, ms in t["phase_spans"]:
            assert name in tm.TICK_PHASES
            assert off_ms >= cursor - 1e-6 and off_ms + ms <= wall + 1e-6
            cursor = off_ms + ms
        assert sum(ms for _n, _o, ms in t["phase_spans"]) == pytest.approx(total)
    # between two phases lie microseconds; a CPU tick is 2 ms, and a loaded
    # machine can preempt the loop just there, so the bound is on the median tick
    assert statistics.median(uncovered) <= 0.05, sorted(uncovered)[-5:]
    # a serving wave walks the whole decode side and both halves of admission
    assert {"deadlines", "admit_begin", "prefill_dispatch", "admit_commit",
            "step_prepare", "step_dispatch", "step_wait", "emit",
            "bookkeeping"} <= seen


@pytest.mark.parametrize("wall_of", ["ticks", "loop"])
def test_counter_is_monotone_and_sums_to_the_loops_wall(paged_engine, wall_of):
    """``dllama_tick_phase_ms_total``: every series only grows, and over a
    hand-driven run (this thread is the loop) the phases add up to the wall
    spent inside ``_tick``: idle waits included, nothing counted twice
    (``ticks``). With what lies between two phases and between two ticks
    (``telemetry.LOOP_GAPS``) the series reach the loop's WHOLE wall, from
    the first ``_tick``'s start to the last one's end, the ``while``'s own
    condition included, within 1% + 0.5 ms (``loop``)."""
    c = tm.registry().counter(tm.TICK_PHASE_MS)
    names = tm.TICK_PHASES if wall_of == "ticks" else (*tm.TICK_PHASES, *tm.LOOP_GAPS)
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        reqs = [sched.submit(paged_engine.tokenizer.encode(p, is_start=True), 6,
                             stop_on_eos=False) for p in PROMPTS[:4]]
        before = {p: c.total(phase=p) for p in names}
        prev, wall_ns = dict(before), 0
        t_first = time.monotonic_ns()
        while not all(r.done.is_set() for r in reqs):
            t0 = time.monotonic_ns()
            sched._tick()
            t_last = time.monotonic_ns()
            wall_ns += t_last - t0
            now = {p: c.total(phase=p) for p in names}
            assert all(now[p] >= prev[p] for p in names)
            prev = now
        if wall_of == "loop":
            wall_ns = t_last - t_first
        sched._tick()                                # one idle tick: idle_wait counts
        wall_ns += 50_000_000
    finally:
        sched.close()
    grown = {p: prev[p] - before[p] for p in names}
    assert c.total(phase="idle_wait") > before["idle_wait"]
    total, wall = sum(grown.values()), (wall_ns - 50_000_000) / 1e6
    assert total <= wall + 1e-6
    if wall_of == "ticks":
        assert wall - total <= 0.05 * wall + 0.5, (total, wall, grown)
    else:
        assert grown[tm.BETWEEN_TICKS] > 0 and grown[tm.BETWEEN_PHASES] > 0
        assert wall - total <= 0.01 * wall + 0.5, (total, wall, grown)
    assert c.total() == pytest.approx(sum(c.total(phase=p) for p in (*tm.TICK_PHASES, *tm.LOOP_GAPS)))


def test_metrics_render_every_phase_from_startup(paged_engine):
    tm.registry().reset()
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        text = tm.registry().render()
    finally:
        sched.close()
    for p in (*tm.TICK_PHASES, *tm.LOOP_GAPS):
        assert f'{tm.TICK_PHASE_MS}{{phase="{p}"}}' in text, p


@pytest.fixture(scope="module")
def capture(paged_engine, tmp_path_factory):
    """A ``jax.profiler`` capture around a few hand-driven ticks, read back:
    the roots, their children and the between-ticks spans as ``(plane, line,
    start_ns, end_ns, ...)``, the number of ticks driven and the recorder's
    tick number going in."""
    import jax
    from jax.profiler import ProfileData

    tmp_path = tmp_path_factory.mktemp("capture")
    flightrec.recorder().reset()
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    try:
        _drive(sched, [sched.submit(paged_engine.tokenizer.encode(PROMPTS[0], is_start=True), 4,
                                    stop_on_eos=False)])           # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            seq0 = flightrec.recorder().snapshot()["tick_seq"]
            n = _drive(sched, [sched.submit(paged_engine.tokenizer.encode(p, is_start=True), 4,
                                            stop_on_eos=False) for p in PROMPTS[1:3]])
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.close()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    roots, kids, gaps = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                at = (plane.name, li, ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == tm.TICK_SPAN:
                    roots.append((*at, dict(ev.stats)))
                elif ev.name.startswith(tm.TICK_SPAN + "."):
                    kids.append((*at, ev.name[len(tm.TICK_SPAN) + 1:], dict(ev.stats)))
                elif ev.name == tm.LOOP_GAP_SPAN:
                    gaps.append((*at, dict(ev.stats)))
    return {"roots": roots, "kids": kids, "gaps": gaps, "n": n, "seq0": seq0}


def test_profiler_capture_holds_the_tick_with_its_children(capture):
    """A ``jax.profiler`` capture around a few hand-driven ticks: the
    ``dllama.tick`` spans carry the flight recorder's tick number, every
    ``dllama.tick.<phase>`` span lies inside one of them on the same line,
    and an admitting ``admit_begin`` says how many it admitted."""
    roots, kids, n, seq0 = (capture[k] for k in ("roots", "kids", "n", "seq0"))
    assert len(roots) == n
    assert sorted(int(r[4]["tick"]) for r in roots) == list(range(seq0 + 1, seq0 + n + 1))
    assert all("n_active" in r[4] for r in roots)
    assert kids and {k[4] for k in kids} <= set(tm.TICK_PHASES)
    for plane, li, s, e, _name, _stats in kids:
        assert any(p == plane and l == li and rs <= s and e <= re
                   for p, l, rs, re, _st in roots)
    admitted = [int(k[5]["admitted"]) for k in kids if k[4] == "admit_begin"]
    assert sum(admitted) == 2 and len(admitted) == n


def test_profiler_capture_tiles_the_loops_life_and_carries_cpu_time(capture):
    """Between every two roots on the loop thread's line lies ONE
    ``dllama.loop.between_ticks`` span, overlapping neither, and every root
    carries ``cpu_us`` (the loop thread's CPU time over the tick, at most its
    wall but for the clocks' grain); a gap carries its own."""
    roots = sorted(capture["roots"], key=lambda r: r[2])
    gaps = sorted(capture["gaps"], key=lambda g: g[2])
    assert len({(r[0], r[1]) for r in roots}) == 1 and {(g[0], g[1]) for g in gaps} == {roots[0][:2]}
    assert len(gaps) >= len(roots) - 1
    for before, after in zip(roots, roots[1:]):
        inside = [g for g in gaps if before[3] <= g[2] and g[3] <= after[2]]
        assert len(inside) == 1, (before[4], after[4], inside)
        assert "cpu_us" in inside[0][4]
    for _plane, _li, s, e, _stats in gaps:
        assert not any(rs < e and s < re for _p, _l, rs, re, _st in roots)
    for _plane, _li, s, e, stats in roots:
        assert 0 <= int(stats["cpu_us"]) <= (e - s) / 1e3 + 500.0


def test_zero_post_steady_compiles_with_the_spans_in(paged_engine):
    """The spans are host bookkeeping: once the ledger calls the scope
    steady, a second identical wave compiles nothing."""
    sched = BatchScheduler(paged_engine, n_slots=2)
    scope = paged_engine.introspection_scope
    led = introspection.ledger()
    retrace = tm.registry().counter(tm.RETRACE_UNEXPECTED)
    try:
        _wave(paged_engine, sched, PROMPTS)
        assert led.steady(scope), "scheduler never reached steady state"
        at_steady, r_before = led.compile_count(scope), retrace.total()
        _wave(paged_engine, sched, PROMPTS)
        assert led.compile_count(scope) == at_steady
        assert retrace.total() == r_before
    finally:
        sched.close()


def test_prefill_cost_is_settled_by_the_step_that_waited(paged_engine):
    """The paged prefill dispatch only enqueues; its cost is charged when
    the next step's fetch has waited for it: to the request's own
    ``ms_prefill``, to ``dllama_prefill_chunk_ms`` and to the tick record —
    and the TTFT phases still sum to the wall TTFT."""
    h = tm.registry().histogram(tm.PREFILL_CHUNK_MS)
    n0 = h.count()
    sched = BatchScheduler(paged_engine, n_slots=2)
    try:
        # no prompt is a prefix of another: every request prefills a chunk
        reqs = _wave(paged_engine, sched, ["alpha beta", "gamma", "delta eps", "zeta"])
        ticks = _closed_ticks()
    finally:
        sched.close()
    assert h.count() >= n0 + len(reqs)
    assert not sched.gen._chunks_pending
    assert sum(t["prefill_tokens"] for t in ticks) > 0
    assert sum(t["prefill_ms"] for t in ticks) > 0
    for r in reqs:
        assert r.ms_prefill > 0
        bd = r.ttft_breakdown()
        parts = (bd["queue_ms"] + bd["kvmigrate_ms"] + bd["pagein_ms"] + bd["admission_ms"]
                 + bd["prefill_ms"] + bd["first_decode_ms"])
        assert parts == pytest.approx(bd["ttft_ms"], abs=1e-6)
    spans = [s for s in tm.tracer().raw_spans() if s["phase"] == "prefill_chunk"]
    assert spans and all(s["end_ns"] >= s["start_ns"] for s in spans)


def test_timeline_nests_phase_slices_under_their_tick(paged_engine):
    sched = BatchScheduler(paged_engine, n_slots=2)
    try:
        _wave(paged_engine, sched, PROMPTS[:3])
        snap = flightrec.recorder().snapshot()
    finally:
        sched.close()
    data = dict(snap, spans=tm.tracer().raw_spans())
    trace = json.loads(json.dumps(flightrec.to_chrome_trace(data), allow_nan=False))
    assert flightrec.validate_chrome_trace(trace) == []
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X" and e["pid"] == 1]
    ticks = {e["name"]: e for e in xs if e["cat"] == "tick"}
    phases = [e for e in xs if e["cat"] == "tick_phase"]
    assert phases and {e["name"] for e in phases} <= set(tm.TICK_PHASES)
    for e in phases:
        t = ticks[f"tick {e['args']['tick']}"]
        assert t["ts"] - 1e-3 <= e["ts"] and e["ts"] + e["dur"] <= t["ts"] + t["dur"] + 1e-3
    assert all("phases" in t["args"] for t in ticks.values())


def test_engine_startup_stamps(paged_engine):
    """``engine.startup_s``: the build's phases in seconds, the serving
    generator's added when it is built; one log line names them."""
    base = {"header", "mesh_plan", "hbm_budget", "weight_load", "kv_and_programs"}
    assert base <= set(paged_engine.startup_s)
    assert all(v >= 0.0 for v in paged_engine.startup_s.values())
    sched = BatchScheduler(paged_engine, n_slots=2, _start_thread=False)
    sched.close()
    assert {"pool_fit", "generator"} <= set(paged_engine.startup_s)
    line = introspection.startup_line(paged_engine)
    assert "start-up" in line and "weight_load" in line and "generator" in line


def test_recorder_and_telemetry_import_without_jax():
    """The two modules import stdlib and each other only (the package's
    ``__init__`` pulls the KV cache, hence jax, so bare package shells stand
    in for it); without an annotation factory the phases still feed the
    tick record."""
    code = ("import sys, types\n"
            "for name, path in (('dllama_tpu', 'dllama_tpu'),\n"
            "                   ('dllama_tpu.runtime', 'dllama_tpu/runtime')):\n"
            "    shell = types.ModuleType(name)\n"
            "    shell.__path__ = [path]\n"
            "    sys.modules[name] = shell\n"
            "from dllama_tpu.runtime import telemetry, flightrec\n"
            "rec = flightrec.FlightRecorder()\n"
            "rec.begin_tick(n_active=1)\n"
            "with rec.tick_phase('emit') as ph:\n"
            "    ph.set(k=1)\n"
            "rec.note('admit', 1)\n"
            "rec.end_tick()\n"
            "t = rec.snapshot()['ticks'][-1]\n"
            "assert list(t['phases']) == ['emit'] and t['phase_spans'][0][0] == 'emit'\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
