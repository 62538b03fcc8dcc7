"""The seam through which a configuration brings its ``reference``, ``weights``
and ``counts`` modules (``run.load_modules``, ``run.model_view``; README,
"Adding things"), on the CPU with no chip and no engine:
``python3 -m pytest benchmark/selftest/test_modules.py`` (``selftest.py`` runs
it too). The whole command with the self-test's routed feed-forward, honest and
under each control, is ``selftest.py``'s part.

What must not move: the model the dense modules see for the two configurations
the benchmark has, and every count of the three cells to the byte
(``counts_frozen.json``: what ``peaks.py``'s functions gave before they moved
to ``counts.py``). A cell that came later froze its counts in a file of its
configuration's own (``<configuration>/selftest/counts_frozen.json``, held by
``tests/test_benchmark_selftest.py``), so the two tests that once ran over
every cell run over the cells this file's ``FROZEN`` holds and over the cells
whose configuration names no module (PR 49).

Also here, because tier-1 collects this file's tests and names no other:
what ``BENCHMARK.json`` must keep (one judged tail a cell, lists that name
cells, a file an end-to-end metric and the reverse) and ``ladder.py``'s rule
on ladders made from a seed."""

import glob
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

sys.path.insert(0, BENCH)
import ladder  # noqa: E402
import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    MANIFEST = json.load(f)
with open(os.path.join(HERE, "counts_frozen.json"), encoding="utf-8") as f:
    FROZEN = json.load(f)
SELFTEST_MANIFEST = os.path.join(HERE, "manifest.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
FROZEN_CELLS = sorted({r["cell"] for r in FROZEN["rows"]})
MOE = "tiny-qwen3-moe.closed"


def _names_modules(entry: dict) -> bool:
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        return "modules" in json.load(f)


DENSE_CONFIGS = {c["name"] for c in MANIFEST["configs"] if not _names_modules(c)}
DENSE_CELLS = [w["name"] for w in MANIFEST["workloads"] if w["config"] in DENSE_CONFIGS]


def _resolve(workload: str, manifest_path: str = os.path.join(ROOT, "BENCHMARK.json")):
    with open(manifest_path, encoding="utf-8") as f:
        return run.resolve_cell(json.load(f), manifest_path, workload)


@pytest.mark.parametrize("config", sorted(FROZEN["model_keys"]))
def test_the_dense_modules_see_the_nine_keys_they_saw(config):
    cell = next(w["name"] for w in MANIFEST["workloads"] if w["config"] == config)
    _cell, conf, _traffic, _mods = _resolve(cell)
    want = FROZEN["model_keys"][config]
    assert len(want) == 9 and {k: conf["model"][k] for k in want} == want
    assert conf["model"]["norm_epsilon"] == conf["rms_norm_eps"]
    assert {k: conf["model"][k] for k in conf["program"]} == conf["program"]


@pytest.mark.parametrize("cell", FROZEN_CELLS)
def test_counts_through_the_seam_are_what_peaks_py_gave_to_the_byte(cell):
    _cell, conf, _traffic, mods = _resolve(cell)
    rows = [r for r in FROZEN["rows"] if r["cell"] == cell]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


@pytest.mark.parametrize("cell", DENSE_CELLS)
def test_a_configuration_that_names_no_module_gets_the_dense_decoders(cell):
    _cell, conf, _traffic, mods = _resolve(cell)
    assert "modules" not in conf
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == \
        {"reference": "reference.py", "weights": "weights.py", "counts": "counts.py"}
    import reference

    assert mods["reference"] is reference      # loaded once: a module that imports it shares its jits


def test_a_configuration_that_names_its_modules_gets_them():
    _cell, conf, _traffic, mods = _resolve(MOE, SELFTEST_MANIFEST)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"]
    assert "misroute" in mods["reference"].CONTROLS and "misroute" not in importlib.import_module("reference").CONTROLS
    for fn in ("reference_gaps", "tolerance"):
        assert callable(getattr(mods["reference"], fn))
    for fn in ("write_sparse_model", "install_seam"):
        assert callable(getattr(mods["weights"], fn))
    for fn in ("decode_step_bytes", "decode_step_flops", "prefill_chunk_bytes", "prefill_chunk_flops"):
        assert callable(getattr(mods["counts"], fn))


def test_the_model_view_carries_every_published_key_and_no_section_of_the_harness():
    _cell, conf, _traffic, _mods = _resolve(MOE, SELFTEST_MANIFEST)
    model = conf["model"]
    assert (model["num_experts"], model["num_experts_per_tok"], model["moe_intermediate_size"],
            model["norm_topk_prob"], model["model_type"]) == (4, 2, 32, True, "qwen3_moe")
    assert "rope_scaling" in model and model["rope_scaling"] is None          # null passes
    assert not set(model) & {"program", "engine", "device", "reduced", "reduced_why", "assumed", "deployment",
                             "memory", "modules", "name", "source"}
    assert model["arch"] == "qwen3" and model["norm_epsilon"] == 1e-05
    _cell, real, _traffic, _mods = _resolve("qwen3-4b.chat")
    assert real["model"]["tie_word_embeddings"] is True and "reduced_why" not in real["model"]


def _run_with_config(tmp_path, edit) -> subprocess.CompletedProcess:
    """The whole command on a copy of the tiny configuration that ``edit`` changed."""
    for sub in ("configs", "traffic"):
        os.makedirs(tmp_path / sub)
    with open(os.path.join(HERE, "configs", "tiny.json"), encoding="utf-8") as f:
        conf = json.load(f)
    edit(conf)
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(conf))
    with open(os.path.join(HERE, "traffic", "closed.json"), encoding="utf-8") as f:
        (tmp_path / "traffic" / "closed.json").write_text(f.read())
    manifest = {"run_seconds": 1, "end_to_end": [], "per_layer": [],
                "configs": [{"name": "tiny", "file": "configs/tiny.json"}],
                "workloads": [{"name": "tiny.closed", "config": "tiny", "traffic": "closed", "chips": 1}]}
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tiny.closed", "--seed", "3",
                           "--seconds", "1", "--manifest", str(tmp_path / "manifest.json")],
                          env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)


def test_a_missing_module_file_stops_the_run_before_the_engine_and_names_the_path(tmp_path):
    p = _run_with_config(tmp_path, lambda c: c.update(modules={"reference": "selftest/moe/no_such_reference.py"}))
    assert p.returncode == 2 and not p.stdout.strip()
    assert os.path.join(BENCH, "selftest", "moe", "no_such_reference.py") in p.stderr
    assert "engine built" not in p.stderr


@pytest.mark.parametrize("modules, said", [({"reference": "../dllama_tpu/models/llama.py"}, "under"),
                                           ({"referee": "reference.py"}, "referee")])
def test_a_module_outside_benchmark_or_of_an_unknown_kind_is_refused(tmp_path, modules, said):
    p = _run_with_config(tmp_path, lambda c: c.update(modules=modules))
    assert p.returncode == 2 and not p.stdout.strip() and said in p.stderr


def test_a_control_the_reference_does_not_list_is_refused(tmp_path):
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", "tiny.closed", "--control",
                        "misroute", "--manifest", SELFTEST_MANIFEST], env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and not p.stdout.strip() and "misroute" in p.stderr and "dropblock" in p.stderr


def test_the_harness_names_no_module_and_no_configuration():
    """ISSUE 29's grep: ``run.py`` and the readers reach ``reference``,
    ``weights``, ``counts`` and ``peaks`` only through what ``resolve_cell``
    returned; none holds a configuration's, a cell's or an arch's name."""
    names = {c["name"] for c in MANIFEST["configs"]} | set(CELLS) | {"llama", "qwen3", "mistral"}
    for path in [os.path.join(BENCH, "run.py")] + sorted(glob.glob(os.path.join(BENCH, "readers", "*.py"))):
        with open(path, encoding="utf-8") as f:
            src = f.read()
        assert not re.search(r"^\s*(import|from) (reference|weights|peaks|counts)\b", src, re.M), path
        code = "\n".join(ln.split("#")[0] for ln in src.split('"""')[::2] for ln in ln.splitlines())
        assert not [n for n in names if re.search(r"['\"]" + re.escape(n) + r"['\"]", code)], path


def test_the_two_share_readers_ask_the_cells_counts_and_read_what_they_read():
    """A stub of a traced run's context: the values are what the readers of
    PR 28 (``import peaks``) gave for it."""
    _cell, conf, _traffic, mods = _resolve("mistral-7b-v0.3.batch-decode")

    class Sent:
        def __init__(self, n):
            self.prompt, self.req = [0] * n, object()

    class Engine:
        prefill_buckets = (1, 64, 256)

    ctx = {"trace": {"modules": {"jit_paged_sampled_step_guarded": [0.0355, 0.0356, 0.0354],
                                 "jit_forward": [0.058, 0.0581, 0.0582], "jit_forward.1": [0.01]}},
           "counters": {"steps": 100, "tokens": 1523}, "samples": {"kv_used_mean": 430.2}, "conf": conf,
           "model": conf["model"], "chips": 1, "sent": [Sent(300), Sent(511), Sent(128)], "engine": Engine(),
           "counts": mods["counts"], "peaks": importlib.import_module("peaks").peaks("TPU v5 lite")}
    entries = [{"name": n, "unit": "%"} for n in ("decode_hbm_share", "prefill_mxu_share")]
    metrics, missing = run.read_metrics(entries, "layer_metrics", ctx)
    assert not missing
    assert metrics["decode_hbm_share"]["value"] == 29.532010808646756
    assert metrics["prefill_mxu_share"]["value"] == 31.404675760923315

    class Doubled:                       # the readers take the counts they are handed, not a module of their own
        @staticmethod
        def decode_step_bytes(model, **kw):
            return 2 * mods["counts"].decode_step_bytes(model, **kw)

    assert run.read_metrics(entries[:1], "layer_metrics", dict(ctx, counts=Doubled))[0]["decode_hbm_share"]["value"] \
        == 2 * 29.532010808646756


def test_the_routed_counts_on_numbers_worked_by_hand():
    _cell, conf, _traffic, mods = _resolve(MOE, SELFTEST_MANIFEST)
    counts, m = mods["counts"], conf["model"]
    attention = 64 * 64 + 2 * 64 * 32 + 64 * 64            # q 4x16, kv 2x16
    expert = 3 * 64 * 32
    assert counts.experts_touched(m, 1) == 2.0 and counts.experts_touched(m, 2) == 3.0     # 4 (1 - 1/2^rows)
    want = 2 * ((attention + 3.0 * expert) * (1 + 2 / 32) + 4 * 64 * 4) + 256 * 64 * 2 + 2 * 2 * 32 * 2 * 100 + 2 * 64 * 2
    assert counts.decode_step_bytes(m, rows=2, context_tokens=100) == want
    row = 2.0 * 2 * (attention + 4 * 64 + 2 * expert)
    assert counts.decode_step_flops(m, rows=2, context_tokens=100) == 2 * (row + 2.0 * 256 * 64) + 4.0 * 2 * 64 * 100
    assert counts.prefill_chunk_flops(m, chunk=16, context_before=8) == 16 * row + 4.0 * 2 * 64 * (16 * 8 + 16 * 17 / 2)


def test_the_routed_sparse_file_is_the_size_the_program_walks(tmp_path):
    """The module owns header and walk size: the program's own reader opens
    the file (``ModelFile.open`` raises on a size its tensor walk does not
    reach) and reads the expert counts back."""
    _cell, conf, _traffic, mods = _resolve(MOE, SELFTEST_MANIFEST)
    path = str(tmp_path / "moe.m")
    mods["weights"].write_sparse_model(path, conf["model"])
    from dllama_tpu.formats import ModelFile

    mf = ModelFile.open(path)
    try:
        h = mf.header
        assert (h.n_experts, h.n_active_experts, h.moe_norm_topk, h.hidden_dim, h.dim) == (4, 2, 1, 32, 64)
        assert "block_moe_gate.0" in mf.tensors and "block_expert_w2.1.3" in mf.tensors
    finally:
        mf.close()


def test_every_listed_metric_has_its_file_and_reader_and_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for section, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in MANIFEST[section]:
            with open(os.path.join(BENCH, folder, m["name"] + ".json"), encoding="utf-8") as f:
                spec = json.load(f)
            assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py")), m["name"]
    for m in MANIFEST["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS)), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_a_judged_tail_of_the_gap(cell):
    """A mean alone lets a change through that shortens most gaps and lengthens
    the longest (two chunks packed into a tick): every cell is also held to ONE
    percentile of its gaps, one of those a file in ``end_to_end/`` makes a
    metric (``ladder.candidates``), whichever ``ladder.py`` gives the cell
    (PERF.md section 2: which, and why)."""
    tails = [m["name"] for m in MANIFEST["end_to_end"] if re.fullmatch(r"itl_p\d+_ms", m["name"]) and cell in m["workloads"]]
    assert len(tails) == 1 and tails[0] in {ladder.tail_name(q) for q in ladder.candidates()}, tails


def _tails_say_what_their_names_say():
    """An ``itl_p<q>_ms`` entry, and no other, reads percentile q of the gaps; it lists its cells, and the lists deal every cell once."""
    on = []
    for m in MANIFEST["end_to_end"]:
        named = re.fullmatch(r"itl_p(\d+)_ms", m["name"])
        with open(os.path.join(BENCH, "end_to_end", m["name"] + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        reads = spec["reader"] == "loadgen_percentile" and spec["args"]["what"] == "itl_ms"
        assert bool(named) == reads, m["name"]
        if named:
            assert spec["args"]["q"] == int(named.group(1)) and m["workloads"], m["name"]
            on += m["workloads"]
    assert sorted(on) == sorted(CELLS)


def _lists_name_cells():
    for section in ("end_to_end", "per_layer"):
        for m in MANIFEST[section]:
            listed = m.get("workloads", [])
            assert set(listed) <= set(CELLS) and len(set(listed)) == len(listed), (m["name"], set(listed) - set(CELLS))


def _a_file_an_entry_and_the_reverse():
    for section, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        files = sorted(os.path.splitext(f)[0] for f in os.listdir(os.path.join(BENCH, folder)))
        assert files == sorted(m["name"] for m in MANIFEST[section]), section


@pytest.mark.parametrize("holds", [_tails_say_what_their_names_say, _lists_name_cells, _a_file_an_entry_and_the_reverse],
                         ids=lambda f: f.__name__.strip("_"))
def test_benchmark_json_keeps_what_a_moved_tail_could_break(holds):
    """Moving a cell from one tail's list to another is an edit of two lists by
    hand: a cell left on both or on neither, a name mistyped, a metric's file
    without its entry would each pass the driver's check of the form."""
    holds()


# -- ladder.py: where a judged percentile may stand ---------------------------------

def _ladder_set(kind: str, runs: int = 6, seed: int = 49) -> list:
    """Gaps made from a seed. ``cliff``: a class of steps at 17 ms and a plateau
    of carried chunks at 38 ms that holds 11.6% of the gaps, half a point more
    or less from run to run (mixed-queue since PR 47); ``ramp``: 400 gaps whose
    upper 8% are strewn between 35 and 42 ms (chat above its 92nd, PR 29);
    ``few``: the cliff's shape in 180 gaps, nine of them beyond the 95th."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = {"cliff": 2177, "ramp": 400, "few": 180}[kind]
    out = []
    for _ in range(runs):
        if kind == "ramp":
            upper = rng.uniform(35.0, 42.0, size=round(0.08 * n))
        else:
            upper = rng.normal(38.0, 0.3, size=round(n * (0.116 + rng.uniform(-0.005, 0.005))))
        out.append(np.concatenate([rng.normal(17.0, 0.4, size=n - len(upper)), upper]).tolist())
    return out


@pytest.mark.parametrize("kind, q, verdict", [("cliff", 90, "cliff below"), ("cliff", 95, "steady"), ("cliff", 85, "steady"),
                                              ("ramp", 95, "spreads"), ("few", 95, "too few beyond")])
def test_the_ladder_rule_on_gaps_made_from_a_seed(kind, q, verdict):
    runs = [{"itl_ms": g, "metrics": {"itl_mean_ms": sum(g) / len(g), "setup_s": 20.0 + 0.1 * i}}
            for i, g in enumerate(_ladder_set(kind))]
    manifest = {"end_to_end": [{"name": "itl_p95_ms", "bound": 0.0275, "workloads": ["b"]},
                               {"name": "itl_p90_ms", "bound": 0.0225, "workloads": ["a"]},
                               {"name": "itl_mean_ms", "bound": 0.06}, {"name": "setup_s", "bound": 0.1}]}
    report = ladder.read(runs, manifest, workload="a", qs=(95, 90, 85))
    found = report["candidates"][q]
    assert found["verdict"] == verdict, found
    assert found["bound"] == {95: 0.0275, 90: 0.0225, 85: ladder.TAIL_BOUND}[q]          # no metric reads the 85th here
    assert len(report["runs"]) == 6 and report["runs"][0]["n"] == len(runs[0]["itl_ms"]) and set(ladder.RUNGS) < set(report["runs"][0])
    assert set(report["line"]) == {"itl_mean_ms", "setup_s"} and report["line"]["setup_s"]["within"]
    # a cell on the 90th's list goes to the highest candidate that reads steady: the 95th off the cliff, the 85th under a ramp or where the 95th has too few
    assert (report["judged"], report["chosen"]) == (90, {"cliff": 95, "ramp": 85, "few": 85}[kind])
    if kind == "cliff":
        # the sweep finds the two classes and nothing on the cliff between them
        assert {84, 85, 94, 95} <= set(report["steady_at"]) and not {87, 88, 89, 90} & set(report["steady_at"])
        assert ladder.choose(report["candidates"], 85) == 85 and ladder.choose(report["candidates"], None) == 95
        text = ladder.render(report, [f"run{i}" for i in range(6)])
        assert "itl_p90_ms: median 3" in text and text.endswith("judged by itl_p90_ms; the rule gives itl_p95_ms")
        ladder_table, judged_table = ladder.markdown(report, "a", "six seeds").split("\n\n")
        assert [len(row.split(" | ")) for row in ladder_table.splitlines()] == [3 + len(ladder.TABLE_RUNGS) + 3] * 3
        assert "| `a` | six seeds | 6 x 2,177-2,177 | 1" in ladder_table and ladder_table.endswith("| itl_p90_ms -> itl_p95_ms |")
        assert "| `itl_p90_ms` (half 1.12): 37." in judged_table and "| `itl_mean_ms` (half 3.00): 19." in judged_table
        assert "**over**" not in judged_table and "setup_s" not in judged_table       # on the plateau here: the cliff is its lower flank's
    with pytest.raises(ValueError):
        ladder.read(runs[:2], manifest)


STEADY, SPREADS, CLIFF, FEW = "steady", "spreads", "cliff above, spreads", "too few beyond"


@pytest.mark.parametrize("said, judged, chosen", [
    ({95: (STEADY, 0.2), 90: (STEADY, 0.9), 88: (STEADY, 0.1)}, 90, 90),          # a steady tail stays, whatever else is steady
    ({95: (CLIFF, 0.2), 90: (SPREADS, 2.0), 88: (STEADY, 0.5)}, 90, 88),          # one that spreads goes to one that is steady,
    ({95: (STEADY, 0.5), 90: (CLIFF, 2.0), 88: (STEADY, 0.1)}, 90, 95),           # the highest of them
    ({95: (CLIFF, 1.5), 90: (SPREADS, 2.2), 88: (SPREADS, 1.9)}, 90, 90),         # every rung spreads: no move cures that (chat, laguna)
    ({95: (SPREADS, 2.1), 90: (CLIFF, 5.2), 88: (CLIFF, 3.7)}, 90, 95),           # off a cliff to the one candidate that has none (mixed-queue)
    ({95: (SPREADS, 1.4), 90: (CLIFF, 2.0), 88: (CLIFF, 0.7), 85: (SPREADS, 1.2)}, 90, 85),   # never ONTO a cliff for its smaller spread
    ({95: (CLIFF, 1.4), 90: (CLIFF, 2.0), 88: (FEW, 0.7)}, 90, 90),               # a fault everywhere: it stays, and needs a file of its own
    ({95: (CLIFF, 1.4), 90: (CLIFF, 2.0)}, None, None),                           # a new cell, the same
    ({95: (SPREADS, 1.4), 90: (SPREADS, 1.1)}, None, 90),                         # a new cell, nothing steady: the soundest
])
def test_the_rule_moves_a_tail_off_a_fault_and_never_onto_one(said, judged, chosen):
    found = {q: {"verdict": verdict, "drivers": spread / 100} for q, (verdict, spread) in said.items()}
    assert ladder.choose(found, judged) == chosen


def test_the_candidates_are_the_percentiles_a_file_makes_a_metric():
    """A cell whose class no candidate meets is one data file (and its entry) away from one that does."""
    listed = sorted((int(m["name"][5:-3]) for m in MANIFEST["end_to_end"] if re.fullmatch(r"itl_p\d+_ms", m["name"])), reverse=True)
    assert ladder.candidates() == tuple(listed) and len(listed) >= 2


def test_the_mean_gap_is_taken_over_every_gap_of_the_window():
    ctx = {"summary": {"itl_ms": [40.0, 40.0, 100.0, 2500.0], "ttft_ms": []}}
    e2e, _ = run.read_metrics([{"name": "itl_mean_ms", "unit": "ms"}], "end_to_end", ctx)
    assert e2e == {"itl_mean_ms": {"value": 670.0, "unit": "ms"}}       # a stall counts at its whole length
    tails, _ = run.read_metrics([{"name": n, "unit": "ms"} for n in ("itl_p90_ms", "itl_p95_ms")], "end_to_end", ctx)
    assert 100.0 < tails["itl_p90_ms"]["value"] < tails["itl_p95_ms"]["value"] < 2500.0    # a percentile does not
    layer, _ = run.read_metrics([{"name": "itl_p99_ms", "unit": "ms"}], "layer_metrics", ctx)
    assert tails["itl_p95_ms"]["value"] < layer["itl_p99_ms"]["value"] < 2500.0
    empty, missing = run.read_metrics([{"name": "itl_mean_ms", "unit": "ms"}], "end_to_end", {"summary": {"itl_ms": []}})
    assert empty == {} and missing == [("itl_mean_ms", "loadgen_mean")]
