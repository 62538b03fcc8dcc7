"""The readers that join a step's spans to its device program (``benchmark/
readers/step_account.py``, ``readers/phase_ms.py``) against ``benchmark/
fixtures/tiny_step.xplane.pb`` and its parent-shaped twin, whose numbers are
worked by hand in ``make_tiny_step_xplane.py``'s docstring. Each metric is read
the way ``benchmark/run.py`` reads it: its ``layer_metrics/<name>.json`` names
the reader file and its arguments."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
STEP = os.path.join(BENCH, "fixtures", "tiny_step.xplane.pb")
PARENT = os.path.join(BENCH, "fixtures", "tiny_step_parent.xplane.pb")
OLD_SPANS = os.path.join(BENCH, "fixtures", "tiny_spans.xplane.pb")     # PR 25's fixture: no step_upload
CELLS = ["mistral-7b-v0.3.batch-decode", "qwen3-4b.chat", "mistral-7b-v0.3.long-prompt",
         "olmo-hybrid-7b.long-prompt", "mistral-7b-v0.3.single-stream", "mistral-7b-v0.3.mixed-queue",
         "laguna-s-2.1.mixed-queue", "falcon-h1-34b.chat"]

# metric -> the fixture's value in ms
EXPECTED = {
    "step_upload_ms_p50": 0.8,
    "step_call_ms_p50": 0.7,
    "step_launch_lag_ms_p50": 0.0,
    "step_fetch_tail_ms_p50": 1.0,
    "fetch_copy_ms_p50": 0.25,
    "step_inner_gap_ms_p50": 0.375,
}


def _file_module(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def account():
    """``benchmark/`` on the path, as ``run.py`` puts it; the reader as a module."""
    sys.path.insert(0, BENCH)
    try:
        yield _file_module("reader_step_account", os.path.join(BENCH, "readers", "step_account.py"))
    finally:
        sys.path.remove(BENCH)


def _ctx(account, trace_file: str) -> dict:
    """What ``run.py`` hands a reader, with both parses made from ``trace_file``
    in place of the run's own trace directory."""
    import program_spans
    import trace_reduce

    return {"trace": trace_reduce.reduce(trace_file, 0.032), "cell": {"name": "fixture"},
            "program_spans": program_spans.load(trace_file), "step_account": account.load(trace_file)}


def _read(metric: str, ctx: dict):
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    reader = _file_module("reader_" + spec["reader"], os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    return reader.read(ctx, **spec.get("args", {}))


def _events(pd):
    return [(plane.name, ln.name, ev.name, round(ev.start_ns), round(ev.duration_ns), sorted(dict(ev.stats).items()))
            for plane in pd.planes for ln in plane.lines for ev in ln.events]


@pytest.mark.parametrize("name", ["tiny_step.xplane.pb", "tiny_step_parent.xplane.pb"])
def test_fixture_files_are_what_their_generator_writes(name):
    """Event for event (a serialized proto map has no fixed byte order)."""
    from jax.profiler import ProfileData

    gen = _file_module("make_tiny_step_xplane", os.path.join(BENCH, "fixtures", "make_tiny_step_xplane.py"))
    made = ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(gen.TEXTS[name]))
    on_disk = _events(ProfileData.from_file(os.path.join(BENCH, "fixtures", name)))
    assert on_disk == _events(made) and len(on_disk) == {"tiny_step.xplane.pb": 39, "tiny_step_parent.xplane.pb": 29}[name]


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_metric_reads_the_fixtures_number(account, metric):
    assert _read(metric, _ctx(account, STEP)) == pytest.approx(EXPECTED[metric], abs=1e-9)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
@pytest.mark.parametrize("trace", [PARENT, OLD_SPANS], ids=["parent-shaped", "pr25-fixture"])
def test_a_trace_without_step_upload_gives_nothing(account, metric, trace):
    """A parent commit's ``step_dispatch`` holds the uploads: neither reader
    pairs it with the change's, and neither raises."""
    assert _read(metric, _ctx(account, trace)) is None


def test_an_untraced_run_gives_nothing(account):
    ctx = {"trace": None, "cell": {"name": "fixture"}}
    assert all(_read(m, ctx) is None for m in EXPECTED)


def test_parts_add_up_to_step_wait_to_the_nanosecond(account):
    """Per tick ``lag + module + tail`` is the tick's ``step_wait`` on whole
    nanoseconds, the lag signed: tick 2's program starts half a millisecond
    before its call returns."""
    ticks = {t["tick"]: t for t in account.load(STEP)["ticks"]}
    assert sorted(ticks) == [1, 2]
    waits = {1: 7_000_000, 2: 5_500_000}
    for n, t in ticks.items():
        assert all(isinstance(t[k], int) for k in ("lag", "module", "tail", "inner", "outside"))
        assert t["lag"] + t["module"] + t["tail"] == waits[n]
    assert (ticks[1]["lag"], ticks[1]["module"], ticks[1]["tail"]) == (500_000, 5_500_000, 1_000_000)
    assert (ticks[2]["lag"], ticks[2]["module"], ticks[2]["tail"]) == (-500_000, 5_000_000, 1_000_000)


def test_the_inner_gap_is_the_program_less_its_ops(account):
    ticks = {t["tick"]: t for t in account.load(STEP)["ticks"]}
    assert ticks[1]["inner"] == 500_000          # 5.5 - (1.5 + 3.5): .4 between the ops, .1 behind the last
    assert ticks[2]["inner"] == 250_000
    assert ticks[1]["outside"] == 300_000 and ticks[2]["outside"] == 0      # the scatter's op after tick 1's step


def test_a_copy_is_a_fetch_that_starts_after_the_program_ends(account):
    """The first fetch of a tick starts before the program ends and waits for
    the device: it is no copy, however long. Tick 2's two later fetches both
    count."""
    ticks = {t["tick"]: t for t in account.load(STEP)["ticks"]}
    assert ticks[1]["copy"] == 300_000           # nonfinite [8.5, 8.8]; tokens [2, 8.4] began before M's end at 8
    assert ticks[2]["copy"] == 150_000 + 50_000


def test_a_tick_with_no_late_fetch_has_no_copy(account, tmp_path):
    """One ``device_get`` of every output (the routed decoder's step) starts
    before the program ends: the tick has an account and no ``copy``."""
    from jax.profiler import ProfileData

    gen = _file_module("make_tiny_step_xplane", os.path.join(BENCH, "fixtures", "make_tiny_step_xplane.py"))
    host = gen.plane("/host:CPU", [("python", gen.tick(
        1, 0, 10, 1, [("step_upload", .3, 1.3), ("step_dispatch", 1.3, 2), ("step_wait", 2, 9)],
        [("tokens/nonfinite/moe_stats", 2, 8.9)]))])
    path = tmp_path / "one_fetch.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join([gen.DEVICE, host])))
    found = account.load(str(path))
    assert [t["copy"] for t in found["ticks"]] == [None] and found["ticks"][0]["tail"] == 1_000_000
    assert account.median_ms(found["ticks"], "copy") is None
    assert account.median_ms(found["ticks"], "tail") == pytest.approx(1.0)


def test_a_container_op_hides_the_gaps_of_its_body_from_inner_alone(account, tmp_path):
    """``inner`` counts a ``while`` as busy from end to end, as ``busy_s`` does;
    ``inner_leaf`` (the command line's) leaves containers out and sees the gap
    between the two ops of its body. A program that "starts" before its call
    began reads a negative ``since_call``: the device lane's clock runs early."""
    from jax.profiler import ProfileData

    gen = _file_module("make_tiny_step_xplane", os.path.join(BENCH, "fixtures", "make_tiny_step_xplane.py"))
    loop = "%while.5 = (s32[], bf16[16,4096]{1,0}) while(%tuple.3), condition=%cond, body=%body"
    device = gen.plane("/device:TPU:0", [
        ("XLA Ops", [(gen.FUSION, 1.2, 1.5), (loop, 1.5, 7.5), (gen.FUSION, 1.5, 4), (gen.FUSION, 4.6, 7.5),
                     (gen.FUSION, 7.5, 8)]),
        ("XLA Modules", [(gen.STEP, 1.2, 8)])])
    host = gen.plane("/host:CPU", [("python", gen.tick(
        1, 0, 10, 1, [("step_upload", .3, 1.3), ("step_dispatch", 1.3, 2), ("step_wait", 2, 9)],
        [("tokens", 2, 8.4), ("nonfinite", 8.5, 8.8)]))])
    path = tmp_path / "while.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join([device, host])))
    (t,) = account.load(str(path))["ticks"]
    assert (t["inner"], t["inner_leaf"]) == (0, 600_000)
    assert (t["lag"], t["since_call"], t["tail"]) == (-800_000, -100_000, 1_000_000)
    assert t["lag"] + t["module"] + t["tail"] == 7_000_000


def test_the_chunk_tick_is_left_out(account):
    """Tick 3 has a ``prefill_dispatch``: its wait holds the chunk's device
    time, as ``step_wait_ms_p50`` leaves it out too."""
    found = account.load(STEP)
    assert [t["tick"] for t in found["ticks"]] == [1, 2] and found["skipped"] == 0
    ctx = _ctx(account, STEP)
    assert _read("step_wait_ms_p50", ctx) == pytest.approx((7.0 + 5.5) / 2)
    # the medians of the same ticks close the account: lag + module + tail = step_wait
    parts = [account.median_ms(found["ticks"], k) for k in ("lag", "module", "tail")]
    assert sum(parts) == pytest.approx(6.25)


def test_a_tick_whose_program_is_not_in_the_slice_is_counted_not_read(account, tmp_path):
    from jax.profiler import ProfileData

    gen = _file_module("make_tiny_step_xplane", os.path.join(BENCH, "fixtures", "make_tiny_step_xplane.py"))
    device = gen.plane("/device:TPU:0", [("XLA Ops", [(gen.FUSION, 2.5, 4)]),
                                         ("XLA Modules", [(gen.FORWARD, 2.5, 8)])])
    path = tmp_path / "no_program.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace("\n".join([device, gen.host(True)])))
    found = account.load(str(path))
    assert found["ticks"] == [] and found["skipped"] == 2
    assert account.read({"step_account": found}, "lag") is None


def test_an_unknown_part_is_refused(account):
    with pytest.raises(ValueError):
        account.read({"step_account": account.load(STEP)}, "nonsense")


def test_the_command_line_prints_the_median_account():
    out = subprocess.run([sys.executable, os.path.join(BENCH, "readers", "step_account.py"), STEP],
                         capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout)
    assert got["ticks"] == 2 and got["lag_ms_min"] == pytest.approx(-0.5)
    assert list(got["phase_ms_p50"]) == ["step_prepare", "step_upload", "step_dispatch", "step_wait", "emit",
                                         "bookkeeping"]
    assert got["phase_ms_p50"]["step_upload"] == pytest.approx(0.9)
    assert {k: got[k] for k in ("lag_ms_p50", "module_ms_p50", "inner_ms_p50", "tail_ms_p50", "copy_ms_p50",
                                "outside_ms_p50")} == pytest.approx(
        {"lag_ms_p50": 0.0, "module_ms_p50": 5.25, "inner_ms_p50": 0.375, "tail_ms_p50": 1.0,
         "copy_ms_p50": 0.25, "outside_ms_p50": 0.15})
    assert got["outside_s_by_program"] == pytest.approx({"scatter_kv_blocks": 0.0003})
    assert got["slice_s_by_program"] == pytest.approx(
        {"paged_sampled_step_guarded": 0.01525, "forward": 0.0044, "scatter_kv_blocks": 0.0003})
    # the account's own checks: the program starts 1.2 and 1.0 ms after its call began (never before), and the
    # tokens are on the host .4 and .2 ms after its last op
    assert (got["since_call_ms_p50"], got["since_call_ms_min"]) == pytest.approx((1.1, 1.0))
    assert got["first_ms_p50"] == pytest.approx(0.3) and got["inner_leaf_ms_p50"] == pytest.approx(0.375)
    parent = subprocess.run([sys.executable, os.path.join(BENCH, "readers", "step_account.py"), PARENT],
                            capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert parent.returncode == 0 and json.loads(parent.stdout) is None


def test_manifest_entries_are_appended_with_the_accepted_layers():
    """The six metrics were appended together behind what the manifest had
    (later PRs append behind them), each with a file of its own, the layer
    strings those of ``step_wait_ms_p50`` and ``decode_device_ms`` letter for
    letter, every cell PR 40 knew listed but the routed decoder's for the
    copy. A cell added since is appended where the metric reads in it:
    ``a.x-k1.agent-sessions`` (PR 42) to the two that need no chunk-free
    step tick, of which its traced slice has none."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("step_upload_ms_p50")
    assert names[first:first + 6] == [
        "step_upload_ms_p50", "step_call_ms_p50", "step_launch_lag_ms_p50", "step_fetch_tail_ms_p50",
        "fetch_copy_ms_p50", "step_inner_gap_ms_p50"]
    assert [w["name"] for w in manifest["workloads"]][:len(CELLS)] == CELLS
    # and ``lfm2-24b-a2b.batch-generate`` (PR 44) to all six: its slice is chunk-free step ticks four times in five
    # and ``nemotron-3-super-120b-a12b.reasoning`` (PR 51) behind it: the same kind of slice; and
    # ``granite-4.0-h-small.doc-qa`` (PR 54): most of its ticks carry a chunk, a chunk and a step are two programs
    # there, and the ticks between two admissions are enough for every median to read (its traced line has all six);
    # and ``solar-open2-250b.long-doc`` (PR 58): three ticks in five are chunk-free steps (its traced line has all six)
    # and ``mellum2-12b-a2.5b.ide-agent`` (PR 60) to the two that a.x-k1's cell reads too: its traced lines were read
    # with those two and the other four were not tried there
    later = {name: (["a.x-k1.agent-sessions"] if name in ("step_upload_ms_p50", "step_call_ms_p50") else [])
             + ["lfm2-24b-a2b.batch-generate", "nemotron-3-super-120b-a12b.reasoning", "granite-4.0-h-small.doc-qa",
                "solar-open2-250b.long-doc"]
             + (["mellum2-12b-a2.5b.ide-agent"] if name in ("step_upload_ms_p50", "step_call_ms_p50") else [])
             for name in EXPECTED}
    for name in EXPECTED:
        m = by_name[name]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".json"))
        assert (m["unit"], m["better"], m["moves"]) == ("ms", "lower", "itl_mean_ms")
        inner = name == "step_inner_gap_ms_p50"
        assert m["layer"] == by_name["decode_device_ms" if inner else "step_wait_ms_p50"]["layer"]
        assert m["source"] == ("device_trace" if inner else "program_span")
        assert m["workloads"] == [c for c in CELLS if not (name == "fetch_copy_ms_p50" and c.startswith("laguna"))] \
            + later.get(name, [])
