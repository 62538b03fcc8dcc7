"""Where a traced slice lies (``traffic.trace_slice``, ``traffic.slice_fault``),
the note for a metric left out of the line (``run.read_metrics``), and what
the breakdown names (``trace_reduce``), on the CPU with no chip:
``python3 -m pytest benchmark/selftest/test_trace_slice.py`` (``selftest.py``
runs it too).

A slice is anchored on arrivals, never on a lull: a request's work begins at
its arrival whatever the program's speed, so a faster program pulls work
towards the arrivals and never in front of them. ``long-prompt`` at the old
default, 9-17 s, is kept here as the case that must fail: no arrival from
3.23 to 16.65 s, so the slice held only the first burst's tail, and a program
a second faster emptied it of decode steps (PERF.md, PR 27)."""

import glob
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MIXES = sorted(os.path.splitext(os.path.basename(p))[0] for p in glob.glob(os.path.join(BENCH, "traffic", "*.json")))

sys.path.insert(0, BENCH)
import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    MANIFEST = json.load(f)
RUN_SECONDS = float(MANIFEST["run_seconds"])


def _mix(name: str) -> dict:
    return traffic.load(os.path.join(BENCH, "traffic", name + ".json"))


def _plan(name: str, seed: int = 7):
    return traffic.plan(_mix(name), seed=seed, seconds=RUN_SECONDS, vocab_size=1000)


def test_every_cell_mix_is_covered():
    assert {w["traffic"] for w in MANIFEST["workloads"]} <= set(MIXES) and len(MIXES) >= 3


@pytest.mark.parametrize("name", MIXES)
def test_the_slice_a_traced_run_uses_is_anchored_or_the_loop_is_closed(name):
    start_s, slice_s = traffic.trace_slice(_mix(name), RUN_SECONDS)
    for seed in (7, 3000000011):       # sizes and gaps come from sizes_seed: no seed moves an arrival
        assert traffic.slice_fault(_plan(name, seed), start_s, slice_s, RUN_SECONDS) is None


@pytest.mark.parametrize("name", MIXES)
def test_the_slice_lies_inside_the_window_and_is_the_default_where_none_is_stated(name):
    mix = _mix(name)
    start_s, slice_s = traffic.trace_slice(mix, RUN_SECONDS)
    assert 0 <= start_s and slice_s > 0 and start_s + slice_s <= RUN_SECONDS
    if "trace_slice" in mix:
        assert (start_s, slice_s) == (mix["trace_slice"]["start_s"], mix["trace_slice"]["seconds"])
    else:
        assert (start_s, slice_s) == (0.2 * RUN_SECONDS, 8.0) == (9.0, 8.0)


def test_the_default_is_a_fifth_in_and_eight_seconds_or_half_the_window():
    assert traffic.trace_slice({}, 45.0) == (9.0, 8.0)
    assert traffic.trace_slice({}, 10.0) == (2.0, 5.0)
    assert traffic.trace_slice({"trace_slice": {"start_s": 16, "seconds": 8}}, 45.0) == (16.0, 8.0)


@pytest.mark.parametrize("name, start_s, anchored", [
    ("long-prompt", 9.0, False),     # the regression: the lull after the first burst
    ("long-prompt", 16.0, True),     # six arrivals in 16-20 s, the first 0.65 s in
    ("long-prompt", 2.5, True),      # the alternative: three at 3.22-3.23 s
    ("long-prompt", 30.0, False),    # one arrival in 30-34 s
    ("chat", 9.0, True),             # 9.57, 10.23, 10.37, 10.40, 10.64, 10.64, 12.29 ...
    ("chat", 16.0, False),           # five in 16-20 s, but the earliest 1.57 s in
    ("batch-decode", 9.0, True),     # a closed loop is loaded throughout
    ("batch-decode", 30.0, True),
])
def test_anchored_on_arrivals(name, start_s, anchored):
    fault = traffic.slice_fault(_plan(name), start_s, 8.0, RUN_SECONDS)
    assert (fault is None) == anchored
    if fault:       # with every arrival printed
        assert "not anchored on arrivals" in fault and f"{_plan(name).requests[-1].due_s:.2f} s" in fault


def test_a_slice_outside_the_window_is_a_fault_for_any_loop():
    for name in ("batch-decode", "long-prompt"):
        assert "does not lie inside" in traffic.slice_fault(_plan(name), 40.0, 8.0, RUN_SECONDS)
        assert "does not lie inside" in traffic.slice_fault(_plan(name), -1.0, 8.0, RUN_SECONDS)


def test_long_prompt_slice_holds_the_arrivals_perf_md_counts():
    due = [r.due_s for r in _plan("long-prompt").requests]
    assert [round(t, 2) for t in due if 16.0 <= t < 24.0] == [16.65, 16.73, 19.83, 19.91, 19.92, 19.98, 21.08, 21.64]
    assert not [t for t in due if 3.24 <= t < 16.6]


def test_a_metric_left_out_of_the_line_is_named_with_its_reader():
    """``window_compiles`` reads a counter; ``step_wait_ms_p50`` and
    ``decode_device_ms`` read the trace and find none in this stub."""
    entries = [m for m in MANIFEST["per_layer"]
               if m["name"] in ("window_compiles", "step_wait_ms_p50", "decode_device_ms")]
    ctx = {"trace": None, "cell": {"name": "stub"}, "window_compiles": 0}
    metrics, missing = run.read_metrics(entries, "layer_metrics", ctx)
    assert metrics == {"window_compiles": {"value": 0.0, "unit": "count"}}
    assert sorted(missing) == [("decode_device_ms", "module_time"), ("step_wait_ms_p50", "tick_phase")]


def test_run_py_refuses_to_trace_a_lull(tmp_path):
    """The whole command, on the CPU at the self-test's tiny size: a mix whose
    slice lies in a lull stops before the engine is built, with the arrivals
    printed and no result line."""
    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / sub)
    with open(os.path.join(HERE, "configs", "tiny.json"), encoding="utf-8") as f:
        (tmp_path / "configs" / "tiny.json").write_text(f.read())
    mix = traffic.load(os.path.join(HERE, "traffic", "open.json"))
    mix["trace_slice"] = {"start_s": 1.0, "seconds": 1.0}      # arrivals at 0.96 and 1.63 s: none in 1.0-1.5 s
    (tmp_path / "traffic" / "lull.json").write_text(json.dumps(mix))
    manifest = {"run_seconds": 3, "end_to_end": [], "per_layer": [],
                "configs": [{"name": "tiny", "file": "configs/tiny.json"}],
                "workloads": [{"name": "tiny.lull", "config": "tiny", "traffic": "lull", "chips": 1}]}
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tiny.lull", "--seed", "5",
                        "--seconds", "3", "--trace", "1", "--manifest", str(tmp_path / "manifest.json")],
                       env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and not p.stdout.strip()
    assert "not anchored on arrivals" in p.stderr and "0.96, 1.63" in p.stderr


# -- the breakdown: which program, which phase ------------------------------------

TINY = os.path.join(BENCH, "fixtures", "tiny.xplane.pb")
TINY_SPANS = os.path.join(BENCH, "fixtures", "tiny_spans.xplane.pb")


def test_device_ops_say_which_program_ran_them():
    """``fusion.1`` is a fusion of the step ([0, 4] ms) and of a prefill chunk
    ([9, 10] ms): two rows, as on the chip ``dynamic-slice_convert_fusion.8``
    is one fusion of ``paged_sampled_step_guarded`` and another of ``forward``."""
    ops = dict(trace_reduce.reduce(TINY, 0.010)["device_ops"])
    assert ops == pytest.approx({"paged_sampled_step_guarded/fusion.1 fusion": 0.004,
                                 "paged_sampled_step_guarded/all-reduce.3 all-reduce": 0.002,
                                 "paged_sampled_step_guarded/fusion.2 fusion": 0.002,
                                 "paged_sampled_step_guarded/wait.1 custom-call": 0.001,
                                 "forward/fusion.1 fusion": 0.001})


def test_idle_gaps_say_which_tick_phase_lay_over_them():
    """Both of the fixture's idle stretches lie between ops, so the split is
    ``program_spans.idle_by_phase``'s own, and the 0.6 ms no phase covers go to
    the benchmark's span over most of the stretch (the generator asleep)."""
    gaps = dict(trace_reduce.reduce(TINY_SPANS, 0.024)["idle_gaps"])
    by_phase = program_spans.load(TINY_SPANS)["idle"]["by_phase"]
    rest = gaps.pop("bench.sleep")
    assert gaps == pytest.approx({"dllama.tick." + k: v for k, v in by_phase.items()})
    assert rest == pytest.approx(0.0006) and max(gaps, key=gaps.get) == "dllama.tick.idle_wait"


def test_a_slice_that_begins_or_ends_idle_counts_that_stretch_too():
    """long-prompt's slice begins 0.65 s before its first arrival: that idle
    lies before the first op, under ``idle_wait``."""
    busy = [(1.0, 2.0), (2.5, 3.0)]
    phases = [("dllama.tick.idle_wait", 0.2, 0.9), ("dllama.tick.step_wait", 0.9, 3.4)]
    assert trace_reduce.idle_stretches(busy, phases) == [(0.2, 1.0), (2.0, 2.5), (3.0, 3.4)]
    assert trace_reduce.idle_stretches(busy, []) == [(1.0, 1.0), (2.0, 2.5), (3.0, 3.0)]
    assert trace_reduce._label_gap(0.2, 1.0, phases, []) == pytest.approx(
        {"dllama.tick.idle_wait": 0.7, "dllama.tick.step_wait": 0.1})


def test_a_trace_without_tick_spans_keeps_the_benchmarks_labels():
    assert trace_reduce.reduce(TINY, 0.010)["idle_gaps"] == [["bench.on_token", pytest.approx(0.002)]]


@pytest.mark.parametrize("path, window_s, busy_s, idle_share, modules", [
    (TINY, 0.010, 0.006, 0.4, {"jit_paged_sampled_step_guarded(123)": [0.007], "jit_forward(77)": [0.001],
                               "jit_forward(78)": [0.003]}),
    (TINY_SPANS, 0.024, 0.0163, 7.7 / 24, {"jit_paged_sampled_step_guarded(1)": [0.005, 0.0045, 0.0038],
                                          "jit_forward(2)": [0.003]}),
])
def test_what_the_readers_take_from_the_reduction_is_unchanged(path, window_s, busy_s, idle_share, modules):
    r = trace_reduce.reduce(path, window_s)
    assert r["busy_s"] == pytest.approx(busy_s) and r["idle_share"] == pytest.approx(idle_share)
    assert r["window_s"] == window_s and set(r["modules"]) == set(modules)
    for name, durations in modules.items():
        assert r["modules"][name] == pytest.approx(durations)
