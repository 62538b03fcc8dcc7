"""Roofline observatory (runtime/roofline) + per-op attribution
(profiling.op_attribution).

On the CPU mesh, ``GET /debug/roofline`` returns per-program entries
whose achieved bytes/FLOPs are derived from the compile ledger's measured
values, with zero post-steady compiles while the observatory is
snapshotting."""

import json
import os
import threading
import urllib.request
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from dllama_tpu.formats import tfile
from dllama_tpu.runtime import introspection, profiling, roofline, telemetry
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.serve.api import _DEBUG_INDEX, _ROUTES, BatchedApiState, \
    make_handler

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_XPLANE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "goldens", "synthetic.xplane.pb")

CEIL = roofline.Ceilings(hbm_gbps=770.0, tflops=70.0, source="test")


# -- unit tier: the roofline math ---------------------------------------------


def test_attribute_memory_bound_program():
    # 8 GB streamed in 29 ms at a 770 GB/s ceiling ≈ 36% of roofline
    out = roofline.attribute(8.5e9, 16e9, 29.0, CEIL)
    assert out["bound"] == "memory"
    assert out["achieved_hbm_gbps"] == pytest.approx(8.5e9 / 0.029 / 1e9,
                                                     rel=1e-4)
    assert out["bw_fraction"] == pytest.approx(
        out["achieved_hbm_gbps"] / 770.0, abs=1e-3)
    assert out["roofline_fraction"] == out["bw_fraction"]
    assert 0.0 < out["roofline_fraction"] <= 1.0
    assert "raw_fraction" not in out
    # operational intensity + ridge ride along for plotting
    assert out["flops_per_byte"] == pytest.approx(16e9 / 8.5e9, abs=1e-3)
    assert out["ridge_flops_per_byte"] == pytest.approx(70e12 / 770e9,
                                                        abs=1e-3)


def test_attribute_compute_bound_program():
    # huge FLOPs over few bytes: compute fraction dominates
    out = roofline.attribute(1e6, 5e12, 100.0, CEIL)
    assert out["bound"] == "compute"
    assert out["roofline_fraction"] == out["compute_fraction"]


def test_attribute_zero_flop_program_is_memory_bound():
    # a pure gather/copy program (cost_analysis reports 0 FLOPs) is
    # legitimate: classified on its bandwidth fraction alone
    out = roofline.attribute(1e9, 0.0, 10.0, CEIL)
    assert out["bound"] == "memory"
    assert out["achieved_tflops"] == 0.0
    assert out["compute_fraction"] == 0.0
    assert out["roofline_fraction"] > 0.0
    assert "flops_per_byte" not in out


def test_attribute_fraction_clamped_to_unity():
    # over-counted bytes (e.g. aliased arguments) would put the raw
    # fraction above 1 — the published fraction clamps, the raw is kept
    out = roofline.attribute(770e9, 0.0, 100.0, CEIL)  # 7.7 TB/s "achieved"
    assert out["roofline_fraction"] == 1.0
    assert out["raw_fraction"] == pytest.approx(10.0, rel=1e-3)


def test_attribute_no_evidence_paths():
    assert "no_evidence" in roofline.attribute(1e9, 1e9, None, CEIL)
    assert "no_evidence" in roofline.attribute(1e9, 1e9, 0.0, CEIL)
    assert "no_evidence" in roofline.attribute(0, 0.0, 10.0, CEIL)


def test_snapshot_missing_memory_analysis_is_no_evidence():
    led = introspection.ledger()
    entry = led.register("rooftest-scope", "mystery_step")
    try:
        entry["compiles"] = 1  # compiled but never analyzed
        snap = roofline.snapshot(ceilings=CEIL, scope="rooftest-scope",
                                 publish=False)
        progs = {p["program"]: p for p in snap["programs"]}
        assert "mystery_step" in progs
        assert "no_evidence" in progs["mystery_step"]
        assert "roofline_fraction" not in progs["mystery_step"]
    finally:
        # surgical cleanup — a full ledger reset would wipe every other
        # engine's history from this process-global record
        with led._lock:
            led._programs.pop(("rooftest-scope", "mystery_step"), None)
            led._steady.pop("rooftest-scope", None)


# -- ceilings: the nameplate table --------------------------------------------


def test_nameplate_ceilings_by_device_kind():
    c = roofline.nameplate_ceilings("TPU v5e chip")
    assert (c.tflops, c.hbm_gbps) == (197.0, 819.0)
    assert c.source == "nameplate:v5e"
    # "TPU v5 lite" is the device_kind jax reports for a v5e chip: its own
    # row, same published peaks — never a default
    c = roofline.nameplate_ceilings("TPU v5 lite")
    assert c.source == "nameplate:v5 lite"
    assert (c.tflops, c.hbm_gbps) == (197.0, 819.0)
    assert roofline.nameplate_ceilings("cpu").source == "nameplate:cpu"
    # an accelerator the table does not hold is an error, not a v5e-class row
    for kind in ("TPU v9 hypothetical", "NVIDIA H100", ""):
        with pytest.raises(roofline.UnknownDeviceKind):
            roofline.nameplate_ceilings(kind)


# -- per-op attribution vs the checked-in xplane fixture ----------------------


def test_op_attribution_against_golden_xplane():
    xs = profiling._load_xplane(GOLDEN_XPLANE)
    out = profiling.op_attribution(xspace=xs, n_steps=1)
    # two device lanes; the primary (largest union) is TPU:0 with 7 ms busy
    assert out["n_lanes"] == 2
    assert out["device_busy_ms_per_step"] == pytest.approx(7.0, abs=1e-6)
    # primary-lane per-op sums: fusion.1(4) + all-reduce.1(2) +
    # wait:rendezvous(1) + fusion.2(2) = 9 ms; ExecuteHelper is noise
    assert out["total_ms_per_step"] == pytest.approx(9.0, abs=1e-6)
    assert not any(o["name"] == "ExecuteHelper" for o in out["top_ops"])
    # class rollup: the collective family (all-reduce + rendezvous wait)
    # is 3 ms of 9; the opaque fusions land honestly in "other"
    assert out["classes"]["collective"]["ms_per_step"] == pytest.approx(
        3.0, abs=1e-6)
    assert out["classes"]["collective"]["frac"] == pytest.approx(3 / 9,
                                                                 abs=1e-4)
    assert out["classes"]["other"]["ms_per_step"] == pytest.approx(6.0,
                                                                   abs=1e-6)
    # sum-vs-union reconcile: nested rows double-count in the sum
    assert out["sum_over_union"] == pytest.approx(9 / 7, abs=0.01)
    top = out["top_ops"][0]
    assert top["name"] == "fusion.1" and top["class"] == "other"


def test_op_attribution_class_regexes():
    cases = {
        "all-reduce.3": "collective",
        "ppermute.1": "collective",
        "dot_general.7": "gemv/matmul",
        "convert_element_type.2": "dequant",
        "top_k.1": "sampling",
        "sort.4": "sampling",
        "argmax.1": "sampling",
        "flash_attention_kernel": "attention",
        "softmax.2": "attention",
        "fusion.12": "other",
    }
    for name, want in cases.items():
        assert profiling.classify_op(name) == want, name


def test_op_attribution_empty_and_missing():
    with pytest.raises(RuntimeError):
        profiling.op_attribution(os.path.join(REPO, "tests", "goldens",
                                              "definitely-not-a-dir"))
    with pytest.raises(ValueError):
        profiling.op_attribution()


# -- acceptance tier: /debug/roofline on the CPU mesh -------------------------


@pytest.fixture(scope="module")
def roofline_server(tmp_path_factory):
    d = tmp_path_factory.mktemp("roofline")
    mpath, tpath = d / "m.m", d / "t.t"
    rng = np.random.default_rng(37)
    write_tiny_model(mpath, tiny_header_params(vocab_size=268, seq_len=256),
                     rng)
    td = byte_vocab_tokenizer()
    td.chat_template = "<|start_header_id|>"  # detected as llama3
    tfile.write_tfile(tpath, td)

    led = introspection.ledger()
    prev_analyze = led.analyze
    led.analyze = True  # the observatory joins against the ledger analysis
    engine = InferenceEngine(str(mpath), str(tpath), temperature=0.0,
                             seed=3, tp=1)
    state = BatchedApiState(engine, n_slots=2)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        yield f"http://127.0.0.1:{httpd.server_address[1]}", engine
    finally:
        led.analyze = prev_analyze
        httpd.shutdown()
        state.close()
        engine.close()


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _chat(base, text, max_tokens=8):
    req = urllib.request.Request(
        base + "/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": text}],
                         "max_tokens": max_tokens,
                         "temperature": 0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, json.loads(r.read())


def test_debug_roofline_joins_ledger_measurements(roofline_server):
    base, engine = roofline_server
    led = introspection.ledger()
    scope = engine.introspection_scope
    # warm to steady state: same-shaped requests until the scheduler marks
    # the scope steady (two compile-quiet ticks)
    for _ in range(3):
        status, _ = _chat(base, "hello roofline")
        assert status == 200
    assert led.steady(scope)
    compiles_before = led.compile_count(scope)

    status, snap = _get(base + "/debug/roofline")
    assert status == 200
    assert snap["ceilings"]["hbm_gbps"] > 0
    assert snap["ceilings"]["source"].startswith(("probe:", "nameplate:"))
    mine = {p["program"]: p for p in snap["programs"]
            if p["scope"] == scope}
    assert mine, "no per-program entries for the serving engine"

    # every achieved number is DERIVED FROM the compile ledger's measured
    # values: the entry's bytes/FLOPs must equal the ledger analysis, and
    # achieved GB/s must be exactly bytes / wall
    led_snap = led.snapshot()
    led_mine = {p["program"]: p for p in led_snap["programs"]
                if p["scope"] == scope}
    attributed = {n: p for n, p in mine.items()
                  if "roofline_fraction" in p}
    assert attributed, f"no attributed programs in {list(mine)}"
    for name, p in attributed.items():
        analysis = led_mine[name]["analysis"]
        assert p["hbm_bytes"] == analysis["hbm_total_bytes"]
        assert p["flops"] == pytest.approx(analysis.get("flops", 0.0))
        # entries round to 3 decimals; tolerate that plus the rounding
        # of wall_ms itself
        assert p["achieved_hbm_gbps"] == pytest.approx(
            p["hbm_bytes"] / (p["wall_ms"] / 1e3) / 1e9, rel=0.02,
            abs=1e-3)
        assert 0.0 < p["roofline_fraction"] <= 1.0
        assert p["bound"] in ("memory", "compute")
    # the decode program is attributed (the ROADMAP #2 target) and the
    # summary names a decode-family program
    decode_named = [n for n, p in attributed.items()
                    if p["family"] == "decode"]
    assert decode_named
    assert snap.get("summary", {}).get("roofline_fraction", 0) > 0

    # the gauges published the same numbers
    reg = telemetry.registry()
    some = decode_named[0]
    assert reg.gauge(telemetry.ROOFLINE_FRACTION).value(
        scope=scope, program=some) == attributed[some]["roofline_fraction"]
    assert reg.gauge(telemetry.ACHIEVED_HBM_GBPS).value(
        scope=scope, program=some) > 0

    # the observatory is trace-invisible: snapshotting (HTTP + direct),
    # the stats fragment, and more steady traffic cause ZERO compiles
    roofline.snapshot(publish=True)
    telemetry.stats_line(reg)
    status, _ = _chat(base, "hello roofline")
    assert status == 200
    status, _ = _get(base + "/debug/roofline")
    assert status == 200
    assert led.compile_count(scope) == compiles_before, \
        "the roofline observatory caused a recompile"


def test_stats_line_carries_roofline_fraction(roofline_server):
    base, _engine = roofline_server
    _chat(base, "warm for stats")
    line = telemetry.stats_line(telemetry.registry())
    assert "roofline=" in line
    assert "%" in line


def test_debug_index_lists_every_debug_route(roofline_server):
    base, _engine = roofline_server
    status, out = _get(base + "/debug")
    assert status == 200
    eps = out["endpoints"]
    debug_routes = {r for r in _ROUTES if r.startswith("/debug/")}
    assert set(eps) == debug_routes == set(_DEBUG_INDEX)
    assert "/debug/roofline" in eps
    for path, desc in eps.items():
        assert isinstance(desc, str) and desc.strip(), path
    # the index route has its own metric label (not folded into "other")
    with urllib.request.urlopen(base + "/metrics", timeout=60) as r:
        text = r.read().decode()
    assert 'route="/debug",status="200"' in text
    assert 'route="/debug/roofline",status="200"' in text
