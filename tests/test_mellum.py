"""The decoder whose period is sliding layers CLOSED by a full one, q and k
normed a head, every layer routed (``ArchType.MELLUM``, ``models/mellum.py``
over ``models/laguna.py``'s one walk; two block pools a sequence and a matched
prefix that brings its window, ``runtime/kvblocks.py`` / ``runtime/serving.py``)
against its plain reference (``benchmark/mellum/reference.py``, imported from
where it lies, no copy), at a tiny size on the CPU: hidden 64, 8 query heads of
32 on 2 K/V heads, 8 layers = two periods of [sliding x 3, full], window 32, 16
experts of which a token takes 4, all held, vocabulary 256, float32, seeded
weights from the benchmark's own maker (``benchmark/mellum/weights.py``), so
program and reference read the same Q40 planes.

``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference compute
the same float32 function with their sums in another order (a grouped matmul
over sorted pairs against every expert weighted, a paged walk against a dense
mask), and the router's rows carry a common direction of gain 800
(``benchmark/mellum/weights.py``) that turns a rounding of its input into 1e-4
of a weight; the worst seen is 1.0e-3. Every control of the reference moves a
logit by 0.02 and more (``test_every_control_moves_the_logits``). A request
admitted behind a match reads ``MATCH_TOL`` 1.5e-3 from the same request
admitted cold by another generator: the matched rows were computed by ANOTHER
admission in other chunks, which is the same function in another order of
sums. Seen: 2.6e-4 and 3.6e-4 behind a match, 4.5e-4 to 8.5e-4 between two
COLD admissions of one prompt by generators of two slots and of one (whose step
programs sum in another order): the match adds nothing to it. A stale or
missing window block reads 0.02 and more (``dropwindowblock``)."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
MELLUM = os.path.join(BENCH, "mellum")
TINY = os.path.join(MELLUM, "selftest", "configs", "tiny-mellum.json")
LOGIT_TOL, MATCH_TOL, CONTROL_MOVES = 2e-3, 1.5e-3, 0.02
BS, WINDOW = 16, 32


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)           # as run.py puts it, and as benchmark/selftest/test_*.py do
import reference as dense_reference  # noqa: E402
import run as bench_run  # noqa: E402


@pytest.fixture(autouse=True)
def _engine_loader_put_back():
    """The weights module's seam replaces the engine's tensor-reading call
    for the process: every test here hands it back as it found it."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.models.llama import load_params_from_mfile

    yield
    engine_mod.load_params_from_mfile = load_params_from_mfile


@pytest.fixture(scope="module")
def bench():
    with open(TINY, encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    return {"weights": _import("mellum_weights", os.path.join(MELLUM, "weights.py")),
            "reference": _import("mellum_reference", os.path.join(MELLUM, "reference.py")),
            "counts": _import("mellum_counts", os.path.join(MELLUM, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-mellum.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", BS)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("mellum"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens, control="none", boundary=None, n_prompt=None):
    ref, dense, model = bench["reference"], dense_reference, bench["model"]
    T = len(tokens)
    padded = -(-T // dense.BLOCK_Q) * dense.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:T] = tokens
    x = ref.stack_output(model, params, ids, T if n_prompt is None else n_prompt, control, boundary)
    h = dense._rms_norm(x, params.final_norm, float(model["norm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ dense._dequant(dense._planes(params.logits)))[:T]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _decode(gen, slots, n_steps):
    """Greedy decode of ``slots`` by hand over the generator's own pools, one
    step program a token, keeping the logits: what ``PagedGenerator.step``
    dispatches, less the sampler."""
    from dllama_tpu.models import llama

    step = jax.jit(lambda params, *args: llama.paged_forward(params, gen.cfg, *args))
    rows = {s: [] for s in slots}
    for _ in range(n_steps):
        for s in slots:
            gen._ensure_blocks(s, int(gen.pos[s]))
        logits, (gen.pkv, gen.wkv, gen.moe_stats) = step(
            gen.eng.params, jnp.asarray(gen.next_token[:, None], jnp.int32), jnp.asarray(gen.pos, jnp.int32),
            (gen.pkv, gen.wkv, gen.moe_stats), jnp.asarray(np.stack([gen.tables, gen.wtables])))
        for s in slots:
            rows[s].append(np.asarray(logits[s, 0]))
            gen.next_token[s] = int(rows[s][-1].argmax())
            gen.pos[s] += 1
    return {s: np.stack(r) for s, r in rows.items()}


def _serve(gen, slot, prompt, n_steps, rid=1):
    """Admit ``prompt`` into ``slot``, decode ``n_steps`` tokens greedily and
    retire: ``(logits [n_steps, V], matched tokens)``."""
    from dllama_tpu.runtime.serving import Request

    before = gen.prefix_totals()[0]
    gen.admit(Request(rid=rid, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), slot)
    matched = gen.prefix_totals()[0] - before
    got = _decode(gen, [slot], n_steps)[slot]
    gen._retire(slot)
    return got, matched


# -- the family is data over laguna's walk ------------------------------------------


def test_the_family_is_lagunas_programs_over_the_headers_data(engine):
    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.models import laguna, mellum
    from dllama_tpu.models.family import family_of

    cfg = engine.cfg
    assert cfg.arch == ArchType.MELLUM and family_of(cfg) is mellum.FAMILY
    assert (mellum.FAMILY.forward, mellum.FAMILY.paged_forward, mellum.FAMILY.tick) == \
        (laguna.forward, laguna.paged_forward, laguna.forward_and_step)
    # no second period scan: the module defines no walk of its own
    with open(mellum.__file__, encoding="utf-8") as f:
        text = f.read()
    assert "lax.scan" not in text and "fori_loop" not in text
    assert (cfg.layer_period, cfg.full_layer_at, cfg.n_periods) == (4, 3, 2)
    assert cfg.uses_qk_norm and not cfg.has_attention_gate and cfg.prefix_reuse_skipped is None
    assert (cfg.n_kv_layers, cfg.n_window_layers, cfg.n_moe_layers, cfg.n_dense_layers) == (2, 6, 8, 0)
    assert (cfg.n_experts, cfg.moe_router_width, cfg.n_heads_sliding, cfg.rope_dim) == (16, 16, 8, 32)
    lp = engine.params.layers
    assert lp.full.wg is None and lp.w1 is None and lp.ws1 is None and lp.slide.norm_q.shape == (6, 32)
    # the sliding part of an admission's column: the window and the widest chunk, not the slot's length
    assert cfg.window_column_rows == 384
    col = laguna.LagunaColumn.zeros(cfg, jnp.float32)
    assert col.k.shape == (2, 1, 2, 512, 32) and col.wk.shape == (6, 1, 2, 384, 32)


def test_header_round_trip_walk_and_the_converters_mapping(bench, tmp_path):
    from dllama_tpu.convert.hf import hf_tensor_plan, load_hf_config
    from dllama_tpu.formats.mfile import ArchType, ModelFile, RopeType
    from dllama_tpu.formats.quants import Q40

    path = str(tmp_path / "tiny.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path, max_seq_len=512) as mf:
        h, t = mf.header, mf.tensors
        assert (h.arch_type, h.rope_type) == (ArchType.MELLUM, RopeType.YARN)
        assert (h.layer_period, h.full_layer_at, h.sliding_window, h.n_heads, h.n_heads_sliding) == (4, 3, 32, 8, 8)
        assert (h.n_experts, h.moe_router_width, h.n_active_experts, h.shared_expert_dim, h.n_dense_layers) == \
            (16, 16, 4, 0, 0)
        assert t["block_norm_q.0"].shape == (32,) and t["block_norm_k.7"].shape == (32,)
        assert "block_attn_gate.0" not in t and "block_matmul_w1.0" not in t and "block_shared_w1.0" not in t
        assert t["block_moe_gate.0"].shape == (16, 64) and "block_expert_w1.7.15" in t
    # the published config's keys give the same header; the tensor names are not guessed
    folder = tmp_path / "hf"
    folder.mkdir()
    with open(TINY, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    (folder / "config.json").write_text(json.dumps(published))
    params = load_hf_config(folder, Q40)
    assert ArchType(params["arch_type"]) == ArchType.MELLUM
    assert (params["layer_period"], params["full_layer_at"], params["sliding_window"], params["moe_router_width"],
            params["hidden_dim"], params["rope_theta_sliding"], params["rope_scaling_factor"]) == \
        (4, 3, 32, 16, 32, 10000, 4)
    with pytest.raises(NotImplementedError, match="_walk_laguna_layer"):
        hf_tensor_plan(params)
    (folder / "config.json").write_text(json.dumps({**published, "layer_types": published["layer_types"][::-1]}))
    with pytest.raises(ValueError, match="closed by a full one"):
        load_hf_config(folder, Q40)


def test_a_header_that_is_not_the_archs_is_refused(bench, tmp_path):
    from dllama_tpu.formats.mfile import ModelFile

    weights = bench["weights"]
    fields = weights.header_fields(bench["model"])
    for key, value in ((weights.FULL_LAYER_AT, 0), (weights.SHARED_EXPERT_DIM, 32), (weights.N_HEADS_SLIDING, 4)):
        path = str(tmp_path / f"bad-{key}.m")
        weights.dense.write_sparse(path, {**fields, key: value}, lambda n: weights.walk_size(bench["model"], n))
        with pytest.raises(ValueError, match="mellum model"):
            ModelFile.open(path, max_seq_len=512)


# -- against the reference ------------------------------------------------------------


@pytest.mark.parametrize("T", [40, 300])
def test_whole_forward_logits(bench, engine, T):
    """One chunk over a column: 40 is under two windows, 300 past the window
    and a 256-token chunk; the routed layers run their chunk form."""
    from dllama_tpu.models import laguna, llama

    tokens = _tokens(T)
    col = laguna.LagunaColumn.zeros(engine.cfg, jnp.float32)
    logits, col = llama.forward(engine.params, engine.cfg, jnp.asarray([tokens], jnp.int32), jnp.int32(0), col)
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
    assert int(col.base) == 0


# 20: under the window, one padded chunk; 70: two chunks, two windows deep; 300: past the window AND a 256-token
# chunk; 420: the sliding buffer (384 rows) slides under the chunks. 40 decode steps cross the window and two block
# boundaries, so blocks go back (and park: the commit registered them) while the row decodes.
@pytest.mark.parametrize("n_prompt", [20, 70, 300, 420])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt):
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt, n_steps = _tokens(n_prompt, seed=n_prompt), 40
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL
    first = (n_prompt - 1 + n_steps - WINDOW + 1) // BS
    assert sorted(gen._wbids[1]) == list(range(first, (n_prompt - 1 + n_steps - 1) // BS + 1))
    assert not gen.wtables[1, :first].any() and gen.wtables[1, first] != 0
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // BS)          # the full pool keeps every block


@pytest.mark.parametrize("control", ["shift", "droplayer", "nowindow", "ropeswap", "noqknorm", "rawtopk", "bf16router",
                                     "dropwindowblock"])
def test_every_control_moves_the_logits(bench, engine, control):
    """Each control is another function: over a prompt three windows deep the
    reference's own logits behind the prompt move by far more than the
    tolerance the program is held to."""
    tokens = _tokens(150, seed=5)
    honest = _reference_logits(bench, engine.params, tokens)
    broken = _reference_logits(bench, engine.params, tokens, control=control, n_prompt=120,
                               boundary=96 if control == "dropwindowblock" else None)
    # shift and dropwindowblock act on the rows behind the prompt (120 tokens) / the boundary: read those
    assert float(np.abs(honest[-20:] - broken[-20:]).max()) > CONTROL_MOVES


# -- a matched prefix brings its window -----------------------------------------------


def test_a_request_behind_a_match_gives_the_logits_it_gives_cold(bench, engine):
    """Both kinds of boundary. (1) The previous prompt's end: a session's
    second turn (its first prompt, the answer, new tokens) matches the first
    prompt's whole blocks, whose last window the commit registered and the
    retirement parked. (2) A shared prefix's end INSIDE longer prompts, at no
    prompt's end: the second request that shares it finds the full pool's
    blocks and no window (``window_miss``), prefills from 0 and leaves the
    boundary's window behind; the third matches it. Each served request's
    logits are the reference's, and within ``MATCH_TOL`` of the same request
    served cold by a generator that has seen nothing."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import PagedGenerator

    missed = telemetry.registry().counter(telemetry.PREFIX_REUSE_SKIPPED)
    miss0 = missed.total(reason="window_miss")
    gen, n_steps = PagedGenerator(engine, n_slots=2), 12

    def check(prompt, slot, want_matched, rid):
        got, matched = _serve(gen, slot, prompt, n_steps, rid)
        assert matched == want_matched
        emitted = got.argmax(axis=1).tolist()
        want = _reference_logits(bench, engine.params, prompt + emitted)[len(prompt) - 1:len(prompt) - 1 + n_steps]
        assert float(np.abs(got - want).max()) < LOGIT_TOL
        cold, none = _serve(PagedGenerator(engine, n_slots=1), 0, prompt, n_steps)
        assert none == 0 and float(np.abs(got - cold).max()) < MATCH_TOL
        return emitted

    # (1) a session: turn 1 is 150 tokens (nine whole blocks of its 149 prefill positions), turn 2 the session so far
    turn1 = _tokens(150, seed=31)
    answer = check(turn1, 0, 0, rid=1)
    turn2 = turn1 + answer + _tokens(60, seed=32)
    check(turn2, 1, 144, rid=2)
    assert gen.window_totals()[:2] == (144, 1) and missed.total(reason="window_miss") == miss0
    # (2) a shared prefix of 128 tokens (eight blocks) under three different prompts
    system = _tokens(128, seed=33)
    check(system + _tokens(70, seed=34), 0, 0, rid=3)
    check(system + _tokens(90, seed=35), 1, 0, rid=4)                # the full pool matched 128, the window was gone
    assert missed.total(reason="window_miss") == miss0 + 1
    check(system + _tokens(50, seed=36), 0, 128, rid=5)              # ... and was left behind by the one that missed
    assert gen.window_totals()[:2] == (144 + 128 + 128, 2)
    assert gen.pool.used_blocks() == 0 and gen.wpool.used_blocks() == 0


def test_after_the_parked_window_is_evicted_the_match_is_shorter_or_none_never_other_rows(bench, engine):
    """The window pool under pressure takes parked blocks back: the next turn
    then matches a shorter boundary or none, prefills the rest, and gives the
    reference's logits all the same."""
    from dllama_tpu.runtime.serving import PagedGenerator

    gen, n_steps = PagedGenerator(engine, n_slots=2), 8
    turn1 = _tokens(150, seed=41)
    got, _ = _serve(gen, 0, turn1, n_steps)
    parked = gen.wpool.cached_blocks()
    assert parked >= 2
    # every free block taken and given back: each allocation past the free list evicts the oldest parked block
    taken = [gen.wpool.alloc() for _ in range(gen.wpool.free_blocks())]
    for bid in taken:
        gen.wpool.release(bid)
    assert gen.wpool.cached_blocks() == 0
    turn2 = turn1 + got.argmax(axis=1).tolist() + _tokens(40, seed=42)
    got2, matched = _serve(gen, 1, turn2, n_steps, rid=2)
    assert matched == 0
    want = _reference_logits(bench, engine.params, turn2 + got2.argmax(axis=1).tolist())
    assert float(np.abs(got2 - want[len(turn2) - 1:len(turn2) - 1 + n_steps]).max()) < LOGIT_TOL
    # the turn that missed left the boundary's window behind: the same turn again matches it
    _got3, matched = _serve(gen, 0, turn2, n_steps, rid=3)
    assert matched == (len(turn2) - 1) // BS * BS


def test_a_stale_window_block_is_what_dropwindowblock_reads(bench, engine):
    """The control is the fault it stands for: a parked block of the matched
    boundary's window overwritten on the device before the next turn matches
    it moves that turn's logits as the reference's ``dropwindowblock`` at the
    same boundary does, far past ``MATCH_TOL``."""
    from dllama_tpu.runtime.serving import PagedGenerator

    gen, n_steps = PagedGenerator(engine, n_slots=2), 8
    turn1 = _tokens(150, seed=51)
    got, _ = _serve(gen, 0, turn1, n_steps)
    turn2 = turn1 + got.argmax(axis=1).tolist() + _tokens(20, seed=52)
    cold, _ = _serve(PagedGenerator(engine, n_slots=1), 0, turn2, n_steps)
    stale = next(iter(gen.wpool._cached))                  # a parked block of the boundary's window
    gen.wkv = jax.tree.map(lambda a: a.at[:, stale].set(0), gen.wkv)
    got2, matched = _serve(gen, 1, turn2, n_steps, rid=2)
    assert matched == 144 and float(np.abs(got2 - cold).max()) > CONTROL_MOVES
    honest = _reference_logits(bench, engine.params, turn2 + cold.argmax(axis=1).tolist())
    broken = _reference_logits(bench, engine.params, turn2 + cold.argmax(axis=1).tolist(), "dropwindowblock", 144)
    assert float(np.abs(honest[len(turn2) - 1:] - broken[len(turn2) - 1:]).max()) > CONTROL_MOVES


def test_scheduler_serves_sessions_and_counts(bench, engine):
    """Through ``BatchScheduler``: a second turn's tokens are the reference's
    argmax, its prefix is counted as matched in both pools, and the routing
    counters add up over prefilled and decoded positions."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    pairs = reg.counter(telemetry.MOE_PAIRS)
    held0, absent0 = pairs.total(where="held"), pairs.total(where="absent")      # the worker's registry: other files' models count there
    sched = BatchScheduler(engine, n_slots=3)
    try:
        first = _tokens(100, seed=61)
        r1 = sched.submit(first, 10, stop_on_eos=False)
        assert r1.done.wait(300) and not r1.error
        second = first + list(r1.tokens) + _tokens(30, seed=62)
        r2 = sched.submit(second, 10, stop_on_eos=False)
        assert r2.done.wait(300) and not r2.error
        assert sched.gen.prefix_totals() == (96, 99 + 139) and sched.gen.window_totals()[:2] == (96, 1)
        want = _reference_logits(bench, engine.params, second + list(r2.tokens))
        assert [int(r.argmax()) for r in want[len(second) - 1:-1]] == list(r2.tokens)
        assert pairs.total(where="held") - held0 == (99 + 10 + 139 - 96 + 10) * 4 * 8 and pairs.total(where="absent") == absent0
        assert reg.gauge(telemetry.KV_WINDOW_BLOCKS_TOTAL).value() == 2 * 3 * (WINDOW // BS + 2)
        assert reg.gauge(telemetry.KV_WINDOW_BLOCKS_PARKED).value() == sched.gen.wpool.cached_blocks() > 0
    finally:
        sched.close()
    assert sched.gen.wpool.used_blocks() == 0


@pytest.mark.parametrize("kwargs, named", [
    ({"spec_lookup": 2}, "--spec-lookup"),
    ({"kv_host_blocks": 8}, "--kv-host-blocks"),
    ({"kv_block_size": 0}, "--kv-block-size"),
])
def test_refused_at_construction_with_the_flag_named(bench, tmp_path, kwargs, named):
    with pytest.raises(ValueError, match=named):
        _engine(bench, tmp_path, **kwargs)


def test_generator_refuses_what_has_no_construction_flag(engine):
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=1)
    with pytest.raises(ValueError, match="two pools"):
        gen.export_prefix(_tokens(40))
    with pytest.raises(ValueError, match="window layers"):
        gen.begin_admit(Request(rid=1, prompt_ids=_tokens(40), max_tokens=4, score=True), 0)


# -- the configuration, the counts, the readers ---------------------------------------


def test_the_cell_configuration_is_the_issues_reckoning(bench):
    """The published widths uncut, the depth the one cut, and the bytes the
    issue reckoned: 443.7 MB a layer, 8.0 GB held, a column of 127 MB."""
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json"), encoding="utf-8") as f:
        conf = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl", encoding="utf-8") as f:
        published = next(json.loads(line) for line in f if line.startswith('{"name": "Mellum2'))["config"]
    for key, value in published.items():
        if key in conf["reduced"]:
            continue
        assert conf[key] == value, key
    assert sorted(conf["reduced"]) == ["layer_types", "max_position_embeddings", "mlp_layer_types", "num_hidden_layers"]
    assert conf["num_hidden_layers"] == 16 and conf["layer_types"] == published["layer_types"][:16]
    assert conf["mlp_layer_types"] == published["mlp_layer_types"][:16]
    model = bench_run.model_view(conf)
    fields = bench["weights"].header_fields(model)
    assert (fields[bench["weights"].LAYER_PERIOD], fields[bench["weights"].FULL_LAYER_AT]) == (4, 3)
    counts = bench["counts"]
    layer = (counts.always_read_weights(model) + 16 * 64 * counts._dims(model)["expert"]) / 16
    assert round(layer * 1.0625 / 1e6, 1) == 443.7
    held = 16 * layer * 1.0625 + 2 * 98304 * 2304 * 2
    assert 7.9e9 < held < 8.1e9
    from dllama_tpu.runtime.kvblocks import window_column_rows

    rows = window_column_rows(1024, 16, (256, 128, 64, 32), 11776)
    assert rows == 1280
    column = 2 * 4 * 128 * 2 * (4 * 11776 + 12 * rows)
    assert round(column / 1e6) == 128           # 96.5 MB the four full layers dense, 31.5 MB the twelve buffers


def test_the_new_readers_read_what_the_program_counts_and_nothing_from_a_parent():
    slice_counters = _import("slice_counters", os.path.join(BENCH, "readers", "slice_counters.py"))
    span_stat = _import("span_stat", os.path.join(BENCH, "readers", "span_stat.py"))
    with open(os.path.join(BENCH, "layer_metrics", "window_prefix_hit_share.json"), encoding="utf-8") as f:
        hit = json.load(f)["args"]
    with open(os.path.join(BENCH, "layer_metrics", "window_blocks_parked_peak_share.json"), encoding="utf-8") as f:
        parked = json.load(f)["args"]
    with open(os.path.join(BENCH, "layer_metrics", "admit_column_mb_p50.json"), encoding="utf-8") as f:
        column = json.load(f)["args"]
    step = lambda **st: ("step_wait", 0.0, 1.0, st)
    ticks = [{"children": [("admit_begin", 0.0, 0.1, {"admitted": 1, "column_bytes": 127 * 2 ** 20}),
                           step(prefix_tokens=100, full_matched_tokens=100, wblocks_parked=10, wblocks_total=200)]},
             {"children": [("admit_begin", 0.0, 0.1, {"admitted": 1, "column_bytes": 0}),
                           step(prefix_tokens=900, full_matched_tokens=1100, wblocks_parked=50, wblocks_total=200)]},
             {"children": [step(prefix_tokens=900, full_matched_tokens=1100, wblocks_parked=30, wblocks_total=200)]}]
    ctx = {"trace": {}, "program_spans": {"ticks": ticks}}
    assert slice_counters.read(ctx, **hit) == 80.0
    assert span_stat.read(ctx, **parked) == 25.0
    assert span_stat.read(ctx, **column) == 127.0
    parent = {"trace": {}, "program_spans": {"ticks": [{"children": [("admit_begin", 0.0, 0.1, {"admitted": 1}),
                                                                      step(prefix_tokens=1), step(prefix_tokens=2)]}]}}
    assert slice_counters.read(parent, **hit) is None and span_stat.read(parent, **parked) is None
    assert span_stat.read(parent, **column) is None and span_stat.read({"trace": None}, **column) is None
