"""Flight recorder, latency attribution, and Perfetto timeline export
(runtime/flightrec.py + the serving/engine wiring).

The ISSUE-7 acceptance criterion lives here: a continuous-batching run
on the CPU mesh (staggered arrivals through the paged scheduler) must
export a Perfetto-loadable Chrome trace in which every request's TTFT
attribution phases sum to within 5% of the measured wall TTFT — and the
compile ledger must show zero post-steady compiles with the recorder
enabled (recording is trace-invisible)."""

import json
import pathlib
import threading
import urllib.request

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import flightrec, introspection
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
