"""XLA compile introspection (runtime/introspection): the compile ledger,
retrace sentinel, HBM startup report, and the /debug/* HTTP surface.

Acceptance tier (ISSUE 3): a steady-state batched-serving test drives TWO
engines, asserts ``dllama_retrace_unexpected_total`` stays 0 across
steady-state traffic, that ``GET /debug/compiles`` lists every compiled
program with nonzero HBM bytes, and that ``POST /debug/profile`` returns a
parseable eval/sync summary — all on the CPU mesh, no silicon."""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import introspection, telemetry
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.serve.api import BatchedApiState, make_handler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model


@pytest.fixture(scope="module")
def model_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("introspect")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(21)
    # seq_len 256: the llama3 template wraps a short user message into
    # ~90-110 prompt tokens, and the profile test decodes 60 more on top
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=256),
                     rng)
    td = byte_vocab_tokenizer()
    td.chat_template = "<|start_header_id|>"  # detected as llama3
    tfile.write_tfile(tpath, td)
    return str(mpath), str(tpath)


# -- ledger unit tier ---------------------------------------------------------


def test_sig_diff_reports_changed_leaves():
    old = {"a": "i32[1,32]", "b": "f32[4]", "gone": "i32[2]"}
    new = {"a": "i32[1,1]", "b": "f32[4]", "new": "f32[8]"}
    diff = introspection._sig_diff(old, new)
    assert "~ a: i32[1,32] -> i32[1,1]" in diff
    assert "+ new = f32[8]" in diff
    assert "- gone = i32[2]" in diff
    assert not any("b" == d.split()[1] for d in diff)
    assert introspection._sig_diff(None, new) == \
        ["(first compile in scope — no prior signature)"]
    # identical signatures still explain themselves (sharding-keyed compile)
    assert "identical leaf shapes" in introspection._sig_diff(old, old)[0]


def test_ledger_records_compiles_hits_and_analysis(model_files):
    led = introspection.ledger()
    prev_analyze = led.analyze
    led.analyze = True
    try:
        e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                            seed=3, tp=1)
        r = e.generate("hello world", 4, stop_on_eos=False)
        assert len(r.tokens) == 4
        snap = led.snapshot()
        mine = {p["program"]: p for p in snap["programs"]
                if p["scope"] == e.introspection_scope}
        # prefill (forward) and fused greedy decode both compiled exactly once
        assert mine["forward"]["compiles"] == 1
        assert mine["greedy_step"]["compiles"] == 1
        # 4 decode tokens = 1 compile + 3 cache hits
        assert mine["greedy_step"]["hits"] >= 2
        # per-miss AOT analysis delivered nonzero HBM bytes and FLOPs
        for prog in ("forward", "greedy_step"):
            assert mine[prog]["hbm_total_bytes"] > 0
            assert mine[prog]["analysis"]["flops"] > 0
        # events carry plan + wall time; this scope is not yet steady
        evs = [ev for ev in snap["events"]
               if ev["scope"] == e.introspection_scope]
        assert evs and all(ev["compile_s"] > 0 for ev in evs)
        assert all(not ev["unexpected"] for ev in evs)
        assert snap["steady"][e.introspection_scope] is False
        # metrics side: counter and histogram moved
        reg = telemetry.registry()
        assert reg.counter(telemetry.COMPILE_TOTAL).total(
            scope=e.introspection_scope) >= 2
        assert reg.histogram(telemetry.COMPILE_SECONDS).count() >= 2
        assert reg.gauge(telemetry.PROGRAM_HBM_BYTES).value(
            scope=e.introspection_scope, program="greedy_step",
            kind="output") > 0
        e.close()
    finally:
        led.analyze = prev_analyze


def test_q40_path_counts_per_program_line_and_gauge(model_files, capsys):
    """linear() notes the path of every Q40 matmul it traces; the ledger
    files the counts under the program that was tracing, prints them on one
    line (start-up report, compile report) and exports them as
    ``dllama_q40_matmul_paths{scope,program,path}``. Off a TPU ``auto`` is
    the XLA path for all seven planes of the scanned layer and the head."""
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=3, tp=1)
    scope = e.introspection_scope
    assert introspection.q40_paths_line(scope) == ""   # nothing traced yet
    e.generate("hello world", 3, stop_on_eos=False)
    paths = introspection.ledger().q40_paths(scope)
    assert paths["greedy_step"] == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 8}
    assert paths["forward"] == {"chunk": 0, "fused": 0, "tiled": 0, "grouped": 0, "xla": 8}
    line = introspection.q40_paths_line(scope)
    assert line.startswith("🧮 q40 matmuls: ")
    assert "greedy_step 0 chunk / 0 fused / 0 tiled / 8 xla" in line
    g = telemetry.registry().gauge(telemetry.Q40_MATMUL_PATHS)
    assert g.value(scope=scope, program="greedy_step", path="xla") == 8
    assert g.value(scope=scope, program="greedy_step", path="fused") == 0
    snap = {p["program"]: p for p in introspection.ledger().snapshot()["programs"]
            if p["scope"] == scope}
    assert snap["greedy_step"]["q40_paths"]["xla"] == 8   # /debug/compiles
    introspection.compile_report(scope)
    assert line in capsys.readouterr().out
    # a dispatch that hits the executable cache traces nothing and must not
    # wipe the counts; outside any program a note is dropped
    e.generate("hello again", 2, stop_on_eos=False)
    introspection.note_q40_path("fused")
    assert introspection.ledger().q40_paths(scope)["greedy_step"]["xla"] == 8
    e.close()


def test_aot_lowering_counts_q40_paths_too(model_files):
    """The start-up report AOT-lowers its programs (``ObservedJit.lower``):
    that trace is counted as a dispatch's would be."""
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=3, tp=1)
    out = []
    introspection.hbm_startup_report(e, emit=out.append)
    line = introspection.q40_paths_line(e.introspection_scope)
    assert "greedy_step 0 chunk / 0 fused / 0 tiled / 8 xla" in line
    assert line in out
    e.close()


def test_retrace_sentinel_fires_after_steady(model_files, capsys):
    led = introspection.ledger()
    reg = telemetry.registry()
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=3, tp=1)
    e.generate("hi there", 4, stop_on_eos=False)
    led.mark_steady(e.introspection_scope)
    assert led.steady(e.introspection_scope)
    before = reg.counter(telemetry.RETRACE_UNEXPECTED).total()
    # force a program this scope never compiled: the sampled step
    e.sampler.set_temp(0.7)
    e.reset()
    e.generate("hello", 2, stop_on_eos=False)
    after = reg.counter(telemetry.RETRACE_UNEXPECTED).total()
    assert after > before
    assert "unexpected recompile after steady state" in capsys.readouterr().out
    evs = [ev for ev in led.snapshot()["events"]
           if ev["scope"] == e.introspection_scope and ev["unexpected"]]
    assert evs and evs[-1]["diff"]  # the shape/plan diff is recorded
    e.close()


def test_new_engine_scope_does_not_inherit_steadiness(model_files):
    led = introspection.ledger()
    reg = telemetry.registry()
    e1 = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                         seed=3, tp=1)
    e1.generate("hi", 3, stop_on_eos=False)
    led.mark_steady(e1.introspection_scope)
    before = reg.counter(telemetry.RETRACE_UNEXPECTED).total()
    # a second engine's warm-up compiles are expected, not retraces
    e2 = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                         seed=3, tp=1)
    assert e2.introspection_scope != e1.introspection_scope
    e2.generate("hi", 3, stop_on_eos=False)
    assert reg.counter(telemetry.RETRACE_UNEXPECTED).total() == before
    assert led.steady(e1.introspection_scope)       # e1 untouched
    assert not led.steady(e2.introspection_scope)   # e2 still warming
    e1.close()
    e2.close()


def test_hbm_startup_report(model_files):
    e = InferenceEngine(model_files[0], model_files[1], temperature=0.0,
                        seed=3, tp=2)
    lines: list[str] = []
    rep = introspection.hbm_startup_report(e, emit=lines.append)
    assert rep["weights_bytes"] > 0 and rep["kv_bytes"] > 0
    assert rep["need_per_device"] > rep["weights_bytes"] // 2  # margin+fixed
    for name in ("decode", "prefill"):
        info = rep["programs"][name]
        assert info["hbm_bytes"]["output"] > 0
        assert info["hbm_bytes"]["argument"] > 0
        assert info["flops"] > 0
    # prefill runs a whole chunk per dispatch: strictly more FLOPs
    assert rep["programs"]["prefill"]["flops"] > \
        rep["programs"]["decode"]["flops"]
    assert any("HBM budget/device" in ln for ln in lines)
    assert sum("program" in ln for ln in lines) >= 2
    # gauges published under the ledger's (scope, program) labels — two
    # engines share program NAMES, so scope must disambiguate
    g = telemetry.registry().gauge(telemetry.PROGRAM_HBM_BYTES)
    sc = e.introspection_scope
    assert g.value(scope=sc, program="greedy_step", kind="argument") > 0
    assert g.value(scope=sc, program="forward", kind="argument") > 0
    e.close()


# -- acceptance tier: steady-state batched serving + /debug endpoints ---------


def _post(url, payload=None, timeout=120):
    data = json.dumps(payload).encode() if payload is not None else b""
    req = urllib.request.Request(url, data=data,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _chat(base, text, max_tokens=8):
    return _post(base + "/v1/chat/completions",
                 {"messages": [{"role": "user", "content": text}],
                  "max_tokens": max_tokens, "temperature": 0})


@pytest.fixture(scope="module")
def two_servers(model_files):
    led = introspection.ledger()
    prev_analyze = led.analyze
    led.analyze = True
    servers = []
    try:
        for tp in (1, 2):
            engine = InferenceEngine(model_files[0], model_files[1],
                                     temperature=0.0, seed=3, tp=tp)
            state = BatchedApiState(engine, n_slots=2)
            httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                        make_handler(state))
            threading.Thread(target=httpd.serve_forever, daemon=True).start()
            servers.append((f"http://127.0.0.1:{httpd.server_address[1]}",
                            engine, state, httpd))
        yield servers
    finally:
        led.analyze = prev_analyze
        for _, engine, state, httpd in servers:
            httpd.shutdown()
            state.close()
            engine.close()


def test_steady_state_batched_serving_two_engines(two_servers):
    led = introspection.ledger()
    reg = telemetry.registry()
    # warm both engines: identical request shapes, several requests each so
    # the schedulers see compile-quiet ticks and mark their scopes steady
    for base, _, _, _ in two_servers:
        for _ in range(3):
            status, out = _chat(base, "hello world")
            assert status == 200
            assert out["usage"]["completion_tokens"] >= 1
    for _, engine, _, _ in two_servers:
        assert led.steady(engine.introspection_scope), \
            f"{engine.introspection_scope} never reached steady state"

    # steady-state traffic of the same shape: ZERO unexpected retraces
    before = reg.counter(telemetry.RETRACE_UNEXPECTED).total()
    for base, _, _, _ in two_servers:
        for _ in range(2):
            status, _out = _chat(base, "hello world")
            assert status == 200
    assert reg.counter(telemetry.RETRACE_UNEXPECTED).total() == before

    # GET /debug/compiles lists every compiled program with nonzero HBM bytes
    base0 = two_servers[0][0]
    status, snap = _get(base0 + "/debug/compiles")
    assert status == 200
    scopes = {e.introspection_scope for _, e, _, _ in two_servers}
    listed = [p for p in snap["programs"] if p["scope"] in scopes]
    compiled = [p for p in listed if p["compiles"] > 0]
    assert len(compiled) >= 4  # ≥2 programs per engine (prefill + ragged)
    for p in compiled:
        assert p["hbm_total_bytes"] > 0, \
            f"{p['scope']}/{p['program']} has no HBM analysis"
        assert p["total_compile_s"] > 0
    for scope in scopes:
        assert snap["steady"][scope] is True
    assert all("last_sig" not in p for p in snap["programs"])  # bounded dump


def test_debug_profile_returns_parseable_split(two_servers):
    base = two_servers[0][0]
    reg = telemetry.registry()

    def _decode_steps() -> int:
        # the same step count live_split_summary diffs across its window
        return (reg.histogram(telemetry.BATCH_STEP_MS).count()
                + reg.histogram(telemetry.DECODE_STEP_MS).count())

    # A single 400 ms window RACES the background request under full-suite
    # load: the tiny model can finish decoding before the capture opens,
    # or the scheduler thread can be starved past the whole window (the
    # PR8-era flake — passed in isolation, failed under load). So each
    # attempt starts a FRESH background generation, waits until its decode
    # steps are observably flowing, THEN opens the window — and because
    # load can still starve any one attempt, the overlap assertion is on
    # "some attempt", bounded, not on a single roll of the dice.
    summary = None
    for attempt in range(6):
        bg_done = threading.Event()

        def _bg():
            try:
                _chat(base, f"profile me while I decode {attempt}",
                      max_tokens=96)
            finally:
                bg_done.set()

        n0 = _decode_steps()
        threading.Thread(target=_bg, daemon=True).start()
        deadline = time.monotonic() + 60
        while (_decode_steps() == n0 and not bg_done.is_set()
               and time.monotonic() < deadline):
            time.sleep(0.005)
        status, s = _post(base + "/debug/profile?ms=400")
        assert status == 200
        for key in ("duration_ms", "n_steps", "eval_ms", "sync_ms",
                    "sync_frac", "n_lanes"):
            assert key in s, s
            assert isinstance(s[key], (int, float))
        assert s["duration_ms"] == pytest.approx(400.0)
        assert 0.0 <= s["sync_frac"] <= 1.0
        # static collective accounting rides along (tp=1: present, empty)
        assert "collective_traffic" in s
        bg_done.wait(timeout=120)
        if s["n_steps"] >= 1:
            summary = s
            break
    # at least one window overlapped live decode steps
    assert summary is not None, "6 profile windows all missed decode steps"

    # the per-op view (?ops=1) returns the op-class attribution shape on
    # the same live path (content is backend-dependent; shape is not)
    status, s = _post(base + "/debug/profile?ms=50&ops=1")
    assert status == 200
    assert "op_attribution" in s
    for key in ("classes", "top_ops", "total_ms_per_step", "n_lanes"):
        assert key in s["op_attribution"]

    # bad/oversized windows are client errors, not captures
    for q in ("ms=nope", "ms=999999", "ms=1", "ms=100&ops=x"):
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base + f"/debug/profile?{q}")
        assert err.value.code == 400


def test_debug_requests_timeline(two_servers):
    base = two_servers[0][0]
    _chat(base, "leave a span trail")
    status, out = _get(base + "/debug/requests")
    assert status == 200
    assert out["requests"], "span ring is empty after a completion"
    # the ring is process-global and request ids are per-scheduler counters,
    # so other engines' spans (rid -1 single-sequence spans from earlier
    # tests in the suite) can interleave — find a batched completion's
    # timeline instead of pinning the newest entry (documented best-effort)
    tl = next(t for t in out["requests"]
              if {"queue", "prefill", "decode"}
              <= {p["phase"] for p in t["phases"]})
    assert {"request_id", "total_ms", "phases"} <= set(tl)
    assert tl["total_ms"] > 0
    for p in tl["phases"]:
        assert p["ms"] >= 0 and p["start_ms"] >= 0


def test_debug_routes_have_their_own_metric_labels(two_servers):
    base = two_servers[0][0]
    _get(base + "/debug/compiles")
    with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
        assert r.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        text = r.read().decode()
    # per-route labels, not folded into "other" (satellite: closed-world
    # route labels; the query-string form must still label /debug/profile)
    assert 'route="/debug/compiles",status="200"' in text
    assert 'route="/debug/profile",status="200"' in text
    assert 'route="/debug/requests",status="200"' in text


# -- cost_analysis version compat ---------------------------------------------

class _FakeCompiled:
    """cost_analysis() return shape varies by jax version: a dict on new
    jax, [dict] on 0.4.x. The shared accessor must normalize both."""

    def __init__(self, ret):
        self._ret = ret

    def cost_analysis(self):
        return self._ret


@pytest.mark.parametrize("ret, want", [
    ({"flops": 7.0}, {"flops": 7.0}),        # newer jax: one dict
    ([{"flops": 7.0}], {"flops": 7.0}),      # 0.4.x: one-element list
    (({"flops": 7.0},), {"flops": 7.0}),     # tuple variant
    ([], {}),                                # no analysis available
    (None, {}),
])
def test_cost_analysis_dict_normalizes_every_shape(ret, want):
    assert introspection.cost_analysis_dict(_FakeCompiled(ret)) == want


def test_cost_analysis_dict_is_what_the_moe_flops_test_consumes():
    """The satellite contract: tests/test_moe.py measures FLOPs through
    THIS accessor, so `[dict]`-returning jax can never TypeError it
    again. Keyed access on the normalized dict must work."""
    ca = introspection.cost_analysis_dict(_FakeCompiled([{"flops": 3.5}]))
    assert ca["flops"] == 3.5
