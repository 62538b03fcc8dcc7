"""The benchmark's readers of the program's own spans (``benchmark/
program_spans.py``, ``readers/idle_by_phase.py``, ``tick_phase.py``,
``engine_build.py``) against ``benchmark/fixtures/tiny_spans.xplane.pb``, whose
numbers are worked by hand in ``make_tiny_spans_xplane.py``'s docstring. Each
metric is read the way ``benchmark/run.py`` reads it: its
``layer_metrics/<name>.json`` names the reader file and its arguments."""

import importlib.util
import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SPANS = os.path.join(BENCH, "fixtures", "tiny_spans.xplane.pb")
NO_SPANS = os.path.join(BENCH, "fixtures", "tiny.xplane.pb")      # PR 24's fixture: bench.* spans only
WINDOW_S = 0.024

# metric -> the fixture's value (ms or % of the 24 ms window)
EXPECTED = {
    "idle_host_share": 100 * 4.3 / 24,
    "idle_no_work_share": 100 * 2.8 / 24,
    "idle_unspanned_share": 100 * 0.6 / 24,
    "tick_host_ms_p50": 2.0,
    "admit_begin_ms_p50": 1.0,
    "step_wait_ms_p50": 4.0,
}


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


def _ctx(bench, trace_file: str, window_s: float = WINDOW_S, engine=None) -> dict:
    """What ``run.py`` hands a reader, with the spans parsed from
    ``trace_file`` in place of the run's own trace directory."""
    return {"trace": bench.reduce.reduce(trace_file, window_s), "cell": {"name": "fixture"},
            "engine": engine, "program_spans": bench.spans.load(trace_file)}


def _events(pd):
    return [(plane.name, ln.name, ev.name, round(ev.start_ns), round(ev.duration_ns), sorted(dict(ev.stats).items()))
            for plane in pd.planes for ln in plane.lines for ev in ln.events]


def test_fixture_file_is_what_its_generator_writes(bench):
    """Event for event (a serialized proto map has no fixed byte order)."""
    from jax.profiler import ProfileData

    gen = os.path.join(BENCH, "fixtures", "make_tiny_spans_xplane.py")
    mod_spec = importlib.util.spec_from_file_location("make_tiny_spans_xplane", gen)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    made = ProfileData.from_serialized_xspace(ProfileData.text_proto_to_serialized_xspace(mod.TEXT))
    assert _events(ProfileData.from_file(SPANS)) == _events(made) and len(_events(made)) == 35


def test_ticks_are_grouped_with_their_children(bench):
    ticks = bench.spans.load(SPANS)["ticks"]
    assert [int(t["tick"]) for t in ticks] == [7, 8, 9, 10]
    assert [len(t["children"]) for t in ticks] == [7, 8, 3, 2]
    assert [int(t["n_active"]) for t in ticks] == [2, 2, 0, 3]
    assert [t["tick"] for t in bench.spans.work_ticks(ticks)] == [7, 8, 10]     # 9 slept in idle_wait
    assert bench.spans.has(ticks[1], "prefill_dispatch") and not bench.spans.has(ticks[0], "prefill_dispatch")
    assert [round(bench.spans.coverage(t), 4) for t in ticks] == [round(5.9 / 6, 4), 1.0, 1.0, 1.0]
    admits = [(int(st["admitted"]), round(1e3 * (e - s), 6)) for t in ticks
              for n, s, e, st in t["children"] if n == "admit_begin"]
    assert admits == [(0, 0.1), (1, 1.0), (0, 0.1)]


def test_idle_is_split_by_the_phase_over_it(bench):
    idle = bench.spans.load(SPANS)["idle"]
    assert idle["window"] == pytest.approx((0.0, 0.024))
    assert idle["idle_s"] == pytest.approx(0.0077)
    want = {"emit": 1.4, "admit_begin": 1.1, "bookkeeping": 0.5, "step_dispatch": 0.5,
            "prefill_dispatch": 0.4, "deadlines": 0.2, "step_wait": 0.2, "idle_wait": 2.8}
    assert {k: round(1e3 * v, 6) for k, v in idle["by_phase"].items()} == want
    assert idle["unspanned_s"] == pytest.approx(0.0006)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_against_the_fixture(bench, metric):
    assert _read(metric, _ctx(bench, SPANS)) == pytest.approx(EXPECTED[metric])


def test_idle_shares_add_up_to_the_device_idle_share(bench):
    """The identity PERF.md states, at the window the host measured and at
    one a millisecond longer than the trace's own extent: the difference goes
    to ``idle_unspanned_share``, never into a host phase."""
    for window_s, unspanned in ((WINDOW_S, 100 * 0.6 / 24), (0.025, 100 * 1.6 / 25)):
        ctx = _ctx(bench, SPANS, window_s)
        parts = [_read(m, ctx) for m in ("idle_host_share", "idle_no_work_share", "idle_unspanned_share")]
        assert sum(parts) == pytest.approx(_read("device_idle_share", ctx))
        assert parts[2] == pytest.approx(unspanned)
        assert parts[0] == pytest.approx(100 * 4.3 / (1e3 * window_s))


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_trace_without_tick_spans_gives_none(bench, metric):
    """A program that writes no ``dllama.tick`` span (the parent commit): no
    number, and no error."""
    assert bench.spans.load(NO_SPANS) is None
    assert _read(metric, _ctx(bench, NO_SPANS, 0.010)) is None


def test_untraced_run_gives_none(bench):
    ctx = {"trace": None, "cell": {"name": "fixture"}, "engine": None}
    assert all(_read(m, ctx) is None for m in EXPECTED)


def test_spans_are_found_where_run_py_traces_into(bench, tmp_path, monkeypatch):
    """``of_run`` takes the newest ``.xplane.pb`` under
    ``.bench_work/trace/<cell name>/`` and parses it once."""
    work = tmp_path / "benchmark"
    trace_dir = tmp_path / ".bench_work" / "trace" / "some.cell" / "plugins" / "profile" / "run1"
    trace_dir.mkdir(parents=True)
    shutil.copy(SPANS, trace_dir / "host.xplane.pb")
    monkeypatch.setattr(bench.spans, "__file__", str(work / "program_spans.py"))
    ctx = {"trace": bench.reduce.reduce(SPANS, WINDOW_S), "cell": {"name": "some.cell"}}
    found = bench.spans.of_run(ctx)
    assert found is not None and len(found["ticks"]) == 4
    assert bench.spans.of_run(ctx) is found
    assert bench.spans.of_run({"trace": ctx["trace"], "cell": {"name": "another.cell"}}) is None


def test_engine_build_reads_the_startup_stamps(bench):
    eng = types.SimpleNamespace(startup_s={"header": 0.25, "weight_load": 1.5, "generator": 0.75})
    assert _read("engine_build_s", {"engine": eng}) == pytest.approx(2.5)
    assert _read("engine_build_s", {"engine": types.SimpleNamespace()}) is None       # the parent's engine


def test_manifest_names_each_new_metric_with_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for name in [*EXPECTED, "engine_build_s"]:
        m = per_layer[name]
        assert m["source"] == "program_span" and set(m["workloads"]) <= cells
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            assert os.path.exists(os.path.join(BENCH, "readers", json.load(f)["reader"] + ".py"))
    assert per_layer["engine_build_s"]["moves"] == "setup_s"
    assert "mistral-7b-v0.3.batch-decode" not in per_layer["idle_no_work_share"]["workloads"]
