"""The seam through which a configuration brings its ``reference``, ``weights``
and ``counts`` modules (``run.load_modules``, ``run.model_view``; README,
"Adding things"), on the CPU with no chip and no engine:
``python3 -m pytest benchmark/selftest/test_modules.py`` (``selftest.py`` runs
it too). The whole command with the self-test's routed feed-forward, honest and
under each control, is ``selftest.py``'s part.

What must not move: the model the dense modules see for the two configurations
the benchmark has, and every count of the three cells to the byte
(``counts_frozen.json``: what ``peaks.py``'s functions gave before they moved
to ``counts.py``)."""

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
import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    MANIFEST = json.load(f)
with open(os.path.join(HERE, "counts_frozen.json"), encoding="utf-8") as f:
    FROZEN = json.load(f)
SELFTEST_MANIFEST = os.path.join(HERE, "manifest.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
MOE = "tiny-qwen3-moe.closed"


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


@pytest.mark.parametrize("cell", CELLS)
def test_counts_through_the_seam_are_what_peaks_py_gave_to_the_byte(cell):
    _cell, conf, _traffic, mods = _resolve(cell)
    rows = [r for r in FROZEN["rows"] if r["cell"] == cell]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


@pytest.mark.parametrize("cell", CELLS)
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
    stale = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(BENCH, "end_to_end"))} - set(e2e)
    assert not stale, f"end_to_end/ holds files of metrics BENCHMARK.json does not list: {stale}"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_a_judged_tail_of_the_gap(cell):
    """A mean alone lets a change through that shortens most gaps and lengthens
    the longest (two chunks packed into a tick): every cell is also held to a
    percentile of its gaps at the 90th or above (PERF.md section 2: which, and why)."""
    tails = []
    for m in MANIFEST["end_to_end"]:
        with open(os.path.join(BENCH, "end_to_end", m["name"] + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        if (spec["reader"] == "loadgen_percentile" and spec["args"]["what"] == "itl_ms" and spec["args"]["q"] >= 90
                and cell in m.get("workloads", CELLS)):
            tails.append(m["name"])
    assert len(tails) == 1, tails


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
