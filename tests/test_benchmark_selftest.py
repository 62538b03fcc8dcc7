"""Tier-1's view of the benchmark's seam: ``benchmark/selftest/test_modules.py``
(the seam through which a configuration brings its modules, the frozen
counts), ``test_broken_path.py`` (the whole command with the program altering
tokens: ``correct`` false) and ``test_trace_slice.py`` (where a traced slice
lies, the note for a metric left out, what the breakdown names), collected AS
THEY ARE: imported from where they lie, no copy, no test rebuilt. ``python3
benchmark/selftest/selftest.py`` still runs them with the rest of the
self-test; before PR 30 nothing under ``tests/`` did, so tier-1 could not see
the seam regress.

Two of ``test_modules.py``'s tests run over the cells of ``BENCHMARK.json``
that name no modules and have rows in ``selftest/counts_frozen.json`` (PR 29's
three). What they would hold a cell that brings its own modules to is held
here instead, from a file of that cell's own PR (PR 30's is
``benchmark/olmo_hybrid/selftest/counts_frozen.json``): the tests at the end.
"""

import importlib
import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
SELFTEST = os.path.join(BENCH, "selftest")
NEW_CELLS = ("olmo-hybrid-7b.long-prompt", "mistral-7b-v0.3.single-stream")
PR34_CELLS = ("laguna-s-2.1.mixed-queue", "mistral-7b-v0.3.mixed-queue")
PR36_CELL = "falcon-h1-34b.chat"
PR42_CELL = "a.x-k1.agent-sessions"
PR44_CELL = "lfm2-24b-a2b.batch-generate"
PR51_CELL = "nemotron-3-super-120b-a12b.reasoning"
PR54_CELL = "granite-4.0-h-small.doc-qa"
PR58_CELL = "solar-open2-250b.long-doc"
PR60_CELL = "mellum2-12b-a2.5b.ide-agent"

sys.path.insert(0, SELFTEST)
try:
    for _module in ("test_modules", "test_broken_path", "test_trace_slice"):
        _tests = {n: t for n, t in vars(importlib.import_module(_module)).items() if n.startswith("test_")}
        assert not set(_tests) & set(globals()), (_module, sorted(set(_tests) & set(globals())))
        globals().update(_tests)
    import test_modules as _seam
finally:
    sys.path.remove(SELFTEST)


@pytest.fixture(autouse=True)
def _engine_loader_put_back():
    """``test_broken_path`` runs the whole command in this process, and the
    command's weight-maker replaces the engine's tensor-reading call: hand it
    back, or the files this worker runs next load seeded weights."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.models.llama import load_params_from_mfile

    yield
    # the function the engine imported, not "what was there before": a
    # module-scoped engine is built (and the seam installed) before a
    # function-scoped fixture could look
    engine_mod.load_params_from_mfile = load_params_from_mfile


# -- what test_modules.py's two tests would hold PR 30's cells to -------------------

with open(os.path.join(BENCH, "olmo_hybrid", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    NEW_FROZEN = json.load(_f)


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_the_new_cells_counts_through_the_seam_are_what_pr30_froze(cell):
    _cell, conf, _traffic, mods = _seam._resolve(cell)
    rows = [r for r in NEW_FROZEN["rows"] if r["cell"] == cell]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_hybrid_cell_gets_the_modules_it_names_and_single_stream_the_dense_ones():
    _cell, conf, _traffic, mods = _seam._resolve(NEW_CELLS[0])
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "olmo_hybrid/reference.py", "weights": "olmo_hybrid/weights.py", "counts": "olmo_hybrid/counts.py"}
    assert "bf16state" in mods["reference"].CONTROLS and callable(mods["counts"].kernel_counts)
    _cell, conf, _traffic, mods = _seam._resolve(NEW_CELLS[1])
    assert "modules" not in conf and os.path.relpath(mods["counts"].__file__, BENCH) == "counts.py"


# -- and PR 34's cells, from a file of PR 34's own ---------------------------------

with open(os.path.join(BENCH, "laguna", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR34_FROZEN = json.load(_f)


@pytest.mark.parametrize("cell", PR34_CELLS)
def test_pr34s_cells_counts_through_the_seam_are_what_pr34_froze(cell):
    _cell, conf, _traffic, mods = _seam._resolve(cell)
    rows = [r for r in PR34_FROZEN["rows"] if r["cell"] == cell]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_routed_cell_gets_the_modules_it_names_and_mixed_queue_the_dense_ones():
    _cell, conf, _traffic, mods = _seam._resolve(PR34_CELLS[0])
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "laguna/reference.py", "weights": "laguna/weights.py", "counts": "laguna/counts.py"}
    assert {"misroute", "noshared", "nogate", "nowindow"} <= set(mods["reference"].CONTROLS)
    assert mods["counts"].kernel_counts(conf["model"], "expert_gemv", rows=16)["layers"] == 23
    _cell, conf, _traffic, mods = _seam._resolve(PR34_CELLS[1])
    assert "modules" not in conf and os.path.relpath(mods["counts"].__file__, BENCH) == "counts.py"


# -- and PR 36's cell, from a file of PR 36's own -----------------------------------

with open(os.path.join(BENCH, "falcon_h1", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR36_FROZEN = json.load(_f)


def test_pr36s_cell_counts_through_the_seam_are_what_pr36_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR36_CELL)
    rows = [r for r in PR36_FROZEN["rows"] if r["cell"] == PR36_CELL]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_side_by_side_cell_gets_the_modules_it_names():
    _cell, conf, _traffic, mods = _seam._resolve(PR36_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "falcon_h1/reference.py", "weights": "falcon_h1/weights.py", "counts": "falcon_h1/counts.py"}
    assert {"dropstate", "nodecay", "dropssm", "bf16state", "shift"} <= set(mods["reference"].CONTROLS)
    assert mods["counts"].kernel_counts(conf["model"], "ssd_step", rows=16)["calls_per_program"] == 12
    assert conf["reduced"] == ["num_hidden_layers", "max_position_embeddings"] and conf["vocab_size"] == 261120


# -- and PR 42's cell, from a file of PR 42's own -----------------------------------

with open(os.path.join(BENCH, "a_x_k1", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR42_FROZEN = json.load(_f)


def test_pr42s_cell_counts_through_the_seam_are_what_pr42_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR42_CELL)
    rows = [r for r in PR42_FROZEN["rows"] if r["cell"] == PR42_CELL]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_latent_cell_gets_the_modules_it_names_and_its_traffic_is_the_issues():
    cell, conf, traffic_path, mods = _seam._resolve(PR42_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "a_x_k1/reference.py", "weights": "a_x_k1/weights.py", "counts": "a_x_k1/counts.py"}
    assert {"nogroups", "bf16router", "nomscale", "norope", "nocnorm", "noshared", "latent8", "dropblock"} \
        <= set(mods["reference"].CONTROLS)
    counts = mods["counts"]
    assert counts.kernel_counts(conf["model"], "mla_paged_step", rows=16)["calls_per_program"] == 9
    assert counts.kernel_counts(conf["model"], "expert_gemv", rows=16)["layers"] == 8
    assert conf["reduced"] == ["n_routed_experts", "vocab_size", "num_hidden_layers", "max_position_embeddings"]
    assert (cell["chips"], cell["traffic"], len(cell["why"]) <= 200) == (1, "agent-sessions-a.x-k1", True)
    with open(traffic_path, encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sessions"], mix["shared_prefix"], mix["sizes_seed"]) == (
        "closed", 16, {"turns": [4, 4], "think_s": 0.5}, {"share": 1.0, "tokens": 4096}, 808)
    assert mix["engine"] == {"slots": 16, "max_seq_len": 17408} and mix["sampling"]["temperature"] == 0.0
    assert [(c["prompt_tokens"], c["output_tokens"]) for c in mix["mix"]] == [
        ({"dist": "uniform", "low": 1024, "high": 3072}, {"dist": "uniform", "low": 64, "high": 192})]
    import traffic
    plan = traffic.plan(mix, seed=2 ** 31 + 5, seconds=45.0, vocab_size=conf["model"]["vocab_size"])
    assert plan.max_context == 4096 + 4 * (3072 + 192) + 192 == 17344 <= mix["engine"]["max_seq_len"]
    first = [r for r in plan.requests if r.session < 16]
    assert all(len(r.new_tokens) >= 4096 + 1024 for r in first if r.turn == 0) and {r.turn for r in first} == {0, 1, 2, 3}
    assert all(r.new_tokens[:4096] == first[0].new_tokens[:4096] for r in first if r.turn == 0)     # ONE system prompt
    assert max(t for r in plan.requests for t in r.new_tokens[:64]) < 20480
    # every new per-layer metric of the cell names a reader that is there
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    # (PR 60's cell, the second with a prefix to hit, was appended behind it in that list)
    mine = [m["name"] for m in manifest["per_layer"] if m.get("workloads") in ([PR42_CELL], [PR42_CELL, PR60_CELL])]
    assert mine == ["mla_step_share", "mla_step_hbm_share", "mla_step_mxu_share", "mla_chunk_share", "mla_chunk_mxu_share",
                    "prefix_hit_share"]
    for name in mine:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            assert os.path.isfile(os.path.join(BENCH, "readers", json.load(f)["reader"] + ".py")), name
    listed = {m["name"] for m in manifest["per_layer"] if PR42_CELL in m.get("workloads", ())}
    assert {"expert_gemv_share", "expert_gemv_hbm_share", "moe_held_pair_share", "moe_expert_load_max_over_mean"} <= listed
    assert not {"ssd_step_share", "gated_delta_step_share", "window_blocks_returned_share"} & listed


# -- the form the driver holds BENCHMARK.json to before any run --------------------

_NAME = r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}"
_KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
         "workloads": {"name", "config", "traffic", "chips", "why"},
         "end_to_end": {"name", "unit", "better", "bound", "source"},
         "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


@pytest.mark.parametrize("section", sorted(_KEYS))
def test_benchmark_json_keeps_the_form_the_driver_refuses_a_file_for(section):
    """A ``why`` of 201 characters refuses the whole PR before a single run
    (PR 34's first draft had one): names, units, one-line texts of at most 200
    characters, the keys an entry may have, 64 KiB."""
    import re

    path = os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path, encoding="utf-8") as f:
        entries = json.load(f)[section]
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        assert set(e) - {"workloads"} == _KEYS[section] and ("workloads" not in e or section not in ("configs", "workloads")), e
        for key in ("name", "config", "traffic", "moves", *e.get("reduced", ()), *e.get("workloads", ())):
            assert re.fullmatch(_NAME, e.get(key, key)), (e["name"], key)
        for key in ("why", "layer", "source"):
            text = e.get(key, "x")
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, (e["name"], key, len(text))
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", e.get("unit", "ms")), e
        assert e.get("better", "lower") in ("lower", "higher") and e.get("chips", 1) in (1, 4) and len(e.get("reduced", ())) <= 16
        assert re.fullmatch(r"benchmark/[A-Za-z0-9_.\-/]+", e.get("file", "benchmark/x")), e


# -- and PR 44's cell, from a file of PR 44's own -----------------------------------

with open(os.path.join(BENCH, "lfm2", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR44_FROZEN = json.load(_f)


def test_pr44s_cell_counts_through_the_seam_are_what_pr44_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR44_CELL)
    rows = [r for r in PR44_FROZEN["rows"] if r["cell"] == PR44_CELL]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_short_conv_cell_gets_the_modules_it_names_and_its_traffic_is_the_issues():
    cell, conf, traffic_path, mods = _seam._resolve(PR44_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "lfm2/reference.py", "weights": "lfm2/weights.py", "counts": "lfm2/counts.py"}
    assert {"nobias", "biasweight", "convsilu", "notail", "noqknorm", "bf16router", "shift", "droplayer", "dropblock"} \
        <= set(mods["reference"].CONTROLS)
    counts = mods["counts"]
    assert counts.kernel_counts(conf["model"], "paged_ragged_attention", rows=32)["calls_per_program"] == 4
    assert counts.kernel_counts(conf["model"], "expert_chunk", rows=32)["layers"] == 16
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["model"]["norm_epsilon"] == conf["norm_eps"] == 1e-05
    assert (cell["chips"], cell["traffic"], len(cell["why"]) <= 200) == (1, "batch-generate-lfm2", True)
    import traffic
    with open(traffic_path, encoding="utf-8") as f:
        mix = json.load(f)
    plans = [traffic.plan(mix, seed=seed, seconds=45.0, vocab_size=conf["model"]["vocab_size"]) for seed in (3, 2 ** 31 + 5)]
    sizes = [[(len(r.new_tokens), r.max_tokens) for r in p.requests] for p in plans]
    assert sizes[0] == sizes[1] and plans[0].max_context <= 896 <= conf["engine"]["max_seq_len"]      # the seed moves no size
    assert all(64 <= n <= 256 and 256 <= m <= 640 for n, m in sizes[0]) and plans[0].clients == 32
    assert max(t for r in plans[1].requests for t in r.new_tokens) > 60000               # ids from the whole vocabulary


# -- and PR 51's cell, from a file of PR 51's own -----------------------------------

with open(os.path.join(BENCH, "nemotron_h", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR51_FROZEN = json.load(_f)


def test_pr51s_cell_counts_through_the_seam_are_what_pr51_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR51_CELL)
    rows = [r for r in PR51_FROZEN["rows"] if r["cell"] == PR51_CELL]
    assert len(rows) == 24
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_pattern_cell_gets_the_modules_it_names_and_the_lists_it_was_appended_to():
    cell, conf, traffic_path, mods = _seam._resolve(PR51_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "nemotron_h/reference.py", "weights": "nemotron_h/weights.py", "counts": "nemotron_h/counts.py"}
    assert {"none", "shift", "droplayer", "dropblock", "dropstate", "nodecay", "bf16state", "misroute", "noshared",
            "bf16router", "nolatent", "gated", "nobias", "rope"} == set(mods["reference"].CONTROLS)
    counts = mods["counts"]
    assert counts.kernel_counts(conf["model"], "ssd_step", rows=32)["calls_per_program"] == 10
    assert counts.kernel_counts(conf["model"], "expert_chunk", rows=32)["layers"] == 10
    assert counts.kernel_counts(conf["model"], "paged_ragged_attention", rows=32)["calls_per_program"] == 2
    assert (cell["chips"], cell["traffic"], len(cell["why"]) <= 200) == (1, "reasoning-nemotron-3-super", True)
    assert os.path.basename(traffic_path) == "reasoning-nemotron-3-super.json"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    assert len(manifest["workloads"]) >= 11 and len(manifest["configs"]) >= 8 and manifest["workloads"][10]["name"] == PR51_CELL
    # the metrics the cell brought: its name leads their lists (later cells are appended behind it)
    mine = [m["name"] for m in manifest["per_layer"] if (m.get("workloads") or [None])[0] == PR51_CELL]
    assert mine == ["moe_planes_fetched_share"]
    with open(os.path.join(BENCH, "layer_metrics", mine[0] + ".json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec == {"reader": "slice_counters", "args": {"what": "ratio", "over": ["moe_planes"],
                                                          "under": ["moe_plane_slots"], "scale": 100.0}}
    # appended to every list lfm2's cell stands in (its judged tail apart: the ladder's) and to falcon's two
    lists = {m["name"]: m["workloads"] for s in ("end_to_end", "per_layer") for m in manifest[s] if "workloads" in m}
    beside_lfm2 = {n for n, w in lists.items() if PR44_CELL in w} - {"itl_p90_ms"}
    assert all(lists[n][lists[n].index(PR44_CELL) + 1 if PR44_CELL in lists[n] else 1] == PR51_CELL
               for n in beside_lfm2 | {"ssd_step_share", "ssd_step_hbm_share"})
    assert sum(PR51_CELL in lists[n] for n in ("itl_p88_ms", "itl_p90_ms", "itl_p95_ms")) == 1      # ONE judged tail
    assert not {"gated_delta_step_share", "mla_step_share", "expert_gemv_share", "window_blocks_returned_share"} & {
        n for n, w in lists.items() if PR51_CELL in w}


# -- and PR 54's cell, from a file of PR 54's own -----------------------------------

with open(os.path.join(BENCH, "granite_hybrid", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR54_FROZEN = json.load(_f)


def test_pr54s_cell_counts_through_the_seam_are_what_pr54_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR54_CELL)
    rows = [r for r in PR54_FROZEN["rows"] if r["cell"] == PR54_CELL]
    assert len(rows) == 18
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_granite_cell_gets_the_modules_it_names_and_the_lists_it_was_appended_to():
    cell, conf, traffic_path, mods = _seam._resolve(PR54_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "granite_hybrid/reference.py", "weights": "granite_hybrid/weights.py",
        "counts": "granite_hybrid/counts.py"}
    assert {"none", "shift", "droplayer", "dropblock", "noresmult", "noembmult", "sqrtscale", "nologitscale", "rope",
            "bf16state", "bf16router", "softmaxall", "misroute", "noshared", "dropstate", "secondhalf"} == set(
        mods["reference"].CONTROLS)
    counts = mods["counts"]
    assert counts.kernel_counts(conf["model"], "ssd_step", rows=16)["calls_per_program"] == 9
    assert counts.kernel_counts(conf["model"], "expert_chunk", rows=16)["layers"] == 10
    assert counts.kernel_counts(conf["model"], "paged_ragged_attention", rows=16)["calls_per_program"] == 1
    assert (cell["chips"], cell["traffic"], len(cell["why"]) <= 200) == (1, "doc-qa-granite-4.0-h-small", True)
    assert os.path.basename(traffic_path) == "doc-qa-granite-4.0-h-small.json"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    assert len(manifest["workloads"]) >= 12 and len(manifest["configs"]) >= 9 and manifest["workloads"][11]["name"] == PR54_CELL
    assert manifest["configs"][8]["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    mine = [m["name"] for m in manifest["per_layer"] if (m.get("workloads") or [None])[0] == PR54_CELL]
    assert mine == ["prefill_xla_share", "expert_chunk_prefill_share", "expert_chunk_prefill_hbm_share"]
    assert all(m["moves"] == "itl_mean_ms" and m["unit"] == "%" for m in manifest["per_layer"] if m["name"] in mine)
    specs = {}
    for name in mine:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            specs[name] = json.load(f)
    assert specs == {
        "prefill_xla_share": {"reader": "program_xla_share", "args": {"program": "forward"}},
        "expert_chunk_prefill_share": {"reader": "kernel_roofline",
                                       "args": {"kernel": "expert_chunk", "program": "forward", "share": "time"}},
        "expert_chunk_prefill_hbm_share": {"reader": "expert_planes_span_roofline",
                                           "args": {"kernel": "expert_chunk", "program": "forward",
                                                    "field": "moe_chunk_planes"}}}
    # appended behind PR 51's cell in every list that cell stands in but the one metric that is its own
    lists = {m["name"]: m["workloads"] for s in ("end_to_end", "per_layer") for m in manifest[s] if "workloads" in m}
    beside_pr51 = {n for n, w in lists.items() if PR51_CELL in w} - {"moe_planes_fetched_share", "itl_p88_ms",
                                                                      "itl_p90_ms", "itl_p95_ms"}
    assert beside_pr51 and all(lists[n][lists[n].index(PR51_CELL) + 1] == PR54_CELL for n in beside_pr51)
    assert sum(PR54_CELL in lists[n] for n in ("itl_p88_ms", "itl_p90_ms", "itl_p95_ms")) == 1      # ONE judged tail
    assert not {"gated_delta_step_share", "mla_step_share", "expert_gemv_share", "window_blocks_returned_share",
                "moe_planes_fetched_share"} & {n for n, w in lists.items() if PR54_CELL in w}


def test_the_xla_share_reader_counts_what_is_not_a_custom_call():
    spec = importlib.util.spec_from_file_location("program_xla_share", os.path.join(BENCH, "readers", "program_xla_share.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    trace = {"device_ops": [["forward/fusion.12 fusion", 3.0], ["forward/expert_chunk.9 custom-call", 5.0],
                            ["forward/copy.3 copy", 1.0], ["paged_sampled_step_guarded/fusion.12 fusion", 40.0],
                            ["forward_and_step/fusion.1 fusion", 7.0]],
             "modules": {"jit_forward(123)": [4.0, 6.0], "jit_paged_sampled_step_guarded(9)": [50.0]}}
    assert reader.read({"trace": trace}, program="forward") == 100.0 * 4.0 / 10.0
    assert reader.read({"trace": None}, program="forward") is None
    assert reader.read({"trace": {"device_ops": [], "modules": {}}}, program="forward") is None


# -- and PR 58's cell, from a file of PR 58's own -----------------------------------

with open(os.path.join(BENCH, "solar_open2", "selftest", "counts_frozen.json"), encoding="utf-8") as _f:
    PR58_FROZEN = json.load(_f)


def test_pr58s_cell_counts_through_the_seam_are_what_pr58_froze():
    _cell, conf, _traffic, mods = _seam._resolve(PR58_CELL)
    rows = [r for r in PR58_FROZEN["rows"] if r["cell"] == PR58_CELL]
    assert len(rows) == 18
    for r in rows:
        assert getattr(mods["counts"], r["fn"])(conf["model"], **r["args"]) == r["value"], r


def test_the_solar_cell_gets_the_modules_it_names_and_the_lists_it_was_appended_to():
    cell, conf, traffic_path, mods = _seam._resolve(PR58_CELL)
    assert {k: os.path.relpath(m.__file__, BENCH) for k, m in mods.items()} == conf["modules"] == {
        "reference": "solar_open2/reference.py", "weights": "solar_open2/weights.py", "counts": "solar_open2/counts.py"}
    assert {"none", "shift", "droplayer", "dropblock", "scalardecay", "nonegeig", "nogate", "misroute", "noshared",
            "state16", "bf16router"} == set(mods["reference"].CONTROLS)
    counts = mods["counts"]
    assert counts.kernel_counts(conf["model"], "gated_delta_step", rows=16)["calls_per_program"] == 6
    assert counts.kernel_counts(conf["model"], "expert_gemv", rows=16)["layers"] == 8
    assert counts.kernel_counts(conf["model"], "paged_ragged_attention", rows=16)["calls_per_program"] == 2
    assert (cell["chips"], cell["traffic"], len(cell["why"]) <= 200) == (1, "long-doc-solar-open2", True)
    assert os.path.basename(traffic_path) == "long-doc-solar-open2.json"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    assert len(manifest["workloads"]) >= 13 and len(manifest["configs"]) >= 10 and manifest["workloads"][12]["name"] == PR58_CELL
    assert manifest["configs"][9]["reduced"] == ["n_routed_experts", "vocab_size", "num_hidden_layers", "gqa_layers",
                                                 "max_position_embeddings"]
    # it brought no metric of its own: every list it stands in had a cell before it, and it stands LAST in each
    lists = {m["name"]: m["workloads"] for s in ("end_to_end", "per_layer") for m in manifest[s] if "workloads" in m}
    # (as PR 58 left them: PR 60's cell was appended behind it since, and PR 61 brought the chunk kernel's share of
    # ``forward``, a metric of this cell alone)
    lists = {n: [c for c in w if c != PR60_CELL] for n, w in lists.items() if n != "gated_delta_chunk_prefill_share"}
    assert manifest["per_layer"][-1]["name"] == "gated_delta_chunk_prefill_share" and manifest["per_layer"][-1]["workloads"] == [PR58_CELL]
    mine = {n for n, w in lists.items() if PR58_CELL in w}
    assert mine and all(lists[n][-1] == PR58_CELL and len(lists[n]) > 1 for n in mine)
    # behind granite's cell wherever that stands, but for the kernels this step does not run (an SSD mixer; the run
    # form at 16 rows: 128 pairs <= 320 keep the pair form); and in the lists of the kernels it does run
    beside_pr54 = {n for n, w in lists.items() if PR54_CELL in w} - {
        "ssd_step_share", "ssd_step_hbm_share", "expert_chunk_step_share", "expert_chunk_step_hbm_share",
        "itl_p88_ms", "itl_p90_ms", "itl_p95_ms"}
    assert beside_pr54 <= mine
    assert {"gated_delta_step_share", "gated_delta_step_hbm_share", "expert_gemv_share", "expert_gemv_hbm_share",
            "paged_attn_step_share", "paged_attn_step_hbm_share", "prefill_xla_share", "expert_chunk_prefill_share",
            "expert_chunk_prefill_hbm_share", "moe_held_pair_share", "moe_expert_load_max_over_mean",
            "moe_pairs_per_plane", "moe_planes_fetched_share"} <= mine
    assert sum(PR58_CELL in lists[n] for n in ("itl_p88_ms", "itl_p90_ms", "itl_p95_ms")) == 1      # ONE judged tail
    assert not {"ssd_step_share", "mla_step_share", "expert_chunk_step_share", "window_blocks_returned_share",
                "chunk_with_rows_share"} & mine


# -- PR 61's two metrics: data files on the reader that is there ----------------------


@pytest.mark.parametrize("name,program,cell", [
    ("gated_delta_chunk_tick_share", "forward_and_step", "olmo-hybrid-7b.long-prompt"),
    ("gated_delta_chunk_prefill_share", "forward", "solar-open2-250b.long-doc")])
def test_the_chunk_kernels_share_reads_its_program_and_nothing_from_a_parent(name, program, cell):
    """``kernel_roofline`` with ``share: "time"`` over the metric's own file:
    the ``gated_delta_chunk`` ops' time under the program over the program's
    runs, in percent; ``None`` where the trace holds no such op (the parent
    commit: its chunk form was XLA fusions) and where there is no trace; and
    the metric stands at the end of ``per_layer`` with its one cell."""
    spec = importlib.util.spec_from_file_location("kernel_roofline", os.path.join(BENCH, "readers", "kernel_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
        metric = json.load(f)
    assert metric == {"reader": "kernel_roofline", "args": {"kernel": "gated_delta_chunk", "program": program, "share": "time"}}
    ops = [[f"{program}/gated_delta_chunk.9 custom-call", 3.0], [f"{program}/quant_matmul.1 custom-call", 20.0],
           [f"{program}/fusion.7 fusion", 2.0], ["paged_sampled_step_guarded/gated_delta_step.9 custom-call", 1.0]]
    modules = {f"jit_{program}(7)": [10.0, 15.0], "jit_paged_sampled_step_guarded(3)": [30.0]}
    assert reader.read({"trace": {"device_ops": ops, "modules": modules}}, **metric["args"]) == 100.0 * 3.0 / 25.0
    assert reader.read({"trace": {"device_ops": ops[1:], "modules": modules}}, **metric["args"]) is None
    assert reader.read({"trace": None}, **metric["args"]) is None
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), encoding="utf-8") as f:
        entry = next(m for m in json.load(f)["per_layer"][-2:] if m["name"] == name)
    assert (entry["workloads"], entry["better"], entry["unit"], entry["moves"], entry["source"]) == (
        [cell], "lower", "%", "itl_mean_ms", "device_trace")
