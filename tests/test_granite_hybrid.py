"""``ArchType.GRANITE_HYBRID`` (``models/granite_hybrid.py``: Granite 4.0-H Small's
layer equation, an SSD mixer or attention without positions and THEN gated
routed experts beside a gated shared one, under four scalar multipliers and a
tied head) at a tiny size on the CPU, against the benchmark's plain reference
(``benchmark/granite_hybrid/reference.py``): whole-forward logits, padded chunked
prefill then paged decode through both of the step's routed forms, every
control another function, each multiplier's control caught, the tie one
buffer, the router against top-k-then-softmax, the step-form rule's choice for
every routed family, ``ssm_groups = 1`` through both SSD forms, the published 40
``layer_types`` walked whole, the header, the converter, the scheduler with the
new span field, and the cell's configuration against the issue's reckoning."""

import dataclasses
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
GH = os.path.join(BENCH, "granite_hybrid")
TINY = os.path.join(GH, "selftest", "configs", "tiny-granite-hybrid.json")
REAL = os.path.join(BENCH, "configs", "granite-4.0-h-small.json")
PUBLISHED_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
# a float32 program against the float32 reference, in units of the logits' spread (0.002 at 64 lanes under the tie and
# 1 / 16): what the nearest precisions below the stated ones fail (bf16router reads 0.025, bf16state 0.2)
LOGIT_TOL = 2e-3


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)           # as run.py puts it, and as benchmark/selftest/test_*.py do
import reference as dense_reference  # noqa: E402,F401
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
    return {"weights": _import("granite_hybrid_weights", os.path.join(GH, "weights.py")),
            "reference": _import("granite_hybrid_reference", os.path.join(GH, "reference.py")),
            "counts": _import("granite_hybrid_counts", os.path.join(GH, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-granite-hybrid.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("granite_hybrid"))
    yield eng
    eng.close()


def _spread(bench, params, tokens, model=None, variant="none"):
    """The reference's logits over ``tokens`` and their spread."""
    want = bench["reference"].reference_logits(model or bench["model"], params, tokens, variant=variant)
    return want, float(want.std())


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _column(cfg, seq_len=512, dtype=jnp.float32):
    from dllama_tpu.runtime.kvblocks import StateColumn

    k = jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq_len, cfg.cache_width), dtype)
    return StateColumn.zeros(cfg, k, k, dtype)


def _forward(engine, tokens, start=0, col=None):
    from dllama_tpu.models import llama

    cfg = engine.cfg
    return jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(start), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg) if col is None else col)


# -- the configuration as the program sees it ----------------------------------------


def test_the_blocks_the_pools_the_multipliers_and_the_tie_are_the_architectures(engine):
    """Two periods of (mamba mamba attention), two blocks a layer: 4 mixer, 2
    attention and 6 routed blocks; K/V of the attention layers alone, a state
    and a tail of the mixer layers alone; the four scalars as the header holds
    them; the head and the embedding ONE buffer."""
    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.models import granite_hybrid, nemotron_h
    from dllama_tpu.models.family import family_of
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    cfg = engine.cfg
    assert cfg.arch == ArchType.GRANITE_HYBRID and family_of(cfg) is granite_hybrid.FAMILY
    assert "".join(cfg.layer_pattern) == "MEME*EMEME*E" == granite_hybrid.layer_pattern(["mamba", "mamba", "attention"] * 2)
    assert (cfg.n_layers, cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers, cfg.n_dense_layers) == (12, 4, 2, 6, 0)
    assert cfg.has_state and cfg.has_ssm and cfg.has_expert_share and cfg.paged_only and cfg.ssm_groups == 1
    assert cfg.state_shape(5) == (4, 5, 4, 32, 16) and cfg.conv_shape(5) == (4, 5, 3, 128 + 2 * 16)
    assert (cfg.hidden_dim, cfg.expert_width_held, cfg.shared_expert_dim, cfg.moe_latent_dim) == (32, 32, 64, 0)
    assert (cfg.moe_score, cfg.moe_norm_topk, cfg.moe_select_bias, cfg.moe_norm_eps, cfg.moe_routed_scale) == (
        "softmax", True, False, 0.0, 1.0)
    m = cfg.mult
    assert (m.embedding, m.lm_head, np.float32(m.residual)) == (12.0, 0.0625, np.float32(0.22))
    assert (cfg.attn_score_scale, cfg.attn_scale, cfg.score_dim) == (0.0625, 0.0625, 256.0)   # 1 / 16 a score: 256 ** -0.5
    assert dataclasses.replace(cfg, attn_score_scale=0.0).score_dim == cfg.head_dim == 16
    assert family_of(cfg).forward is nemotron_h.forward and family_of(cfg).tick is None
    p = engine.params
    assert cfg.tied_embeddings and p.logits is p.embedding and p.embedding.shape == (256, 64)
    assert p.logits.unsafe_buffer_pointer() == p.embedding.unsafe_buffer_pointer()
    lp = p.layers
    assert lp.we1.codes.shape == (6, 8, 64, 32) == lp.we3.codes.shape and lp.we2.codes.shape == (6, 8, 32, 64)
    assert lp.ws3.codes.shape == (6, 64, 64) and lp.w_lat_in is None and lp.moe_bias is None
    assert StatePool.create(cfg, 4, jnp.float32).s.shape == (4, 5, 4, 32, 16)
    assert PagedKVCache.create(cfg, 9, 16).k.shape == (2, 9, 2, 16, 16)
    assert nemotron_h.fold_runs(nemotron_h.pattern_runs(cfg.layer_pattern))[1] == 1


def test_the_published_forty_layers_as_blocks_and_the_held_period_as_four_runs():
    from dllama_tpu.models import granite_hybrid, nemotron_h

    whole = granite_hybrid.layer_pattern(PUBLISHED_TYPES)
    held = granite_hybrid.layer_pattern(PUBLISHED_TYPES[:10])
    assert held == "MEMEMEMEME*EMEMEMEME" and whole == held * 4 and len(whole) == 80
    assert nemotron_h.pattern_runs(held) == [("ME", 5), ("*", 1), ("EM", 4), ("E", 1)]
    # the whole 80 blocks: the greedy cut joins a period's tail to the next one's head, 13 runs that fold no further
    # (five traced pair bodies; the cut the cell serves is ONE period, four runs)
    runs = nemotron_h.pattern_runs(whole)
    assert runs == [("ME", 5)] + [("*", 1), ("EM", 9), ("E", 1)] * 3 + [("*", 1), ("EM", 4), ("E", 1)]
    assert "".join(unit * n for unit, n in runs) == whole and nemotron_h.fold_runs(runs) == (runs, 1)
    assert [whole.count(k) for k in "M*E"] == [36, 4, 40]
    with pytest.raises(KeyError):
        granite_hybrid.layer_pattern(["mamba", "full_attention"])


def test_the_walk_takes_the_published_forty_layers_at_tiny_widths(bench, tmp_path):
    """The published ``layer_types`` at tiny widths through the program and the
    reference: 36 / 4 / 40 blocks in the three stacks and the pools, ONE scan of
    four periods."""
    from dllama_tpu.runtime.kvblocks import StatePool

    model = dict(bench["model"], layer_types=PUBLISHED_TYPES, num_hidden_layers=40, num_local_experts=4,
                 num_experts_per_tok=2)
    eng = _engine(bench, tmp_path, model=model, seq_len=64)
    try:
        cfg = eng.cfg
        assert (cfg.n_layers, cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers) == (80, 36, 4, 40)
        assert StatePool.create(cfg, 2, jnp.float32).s.shape[0] == 36
        lp = eng.params.layers
        assert (lp.mixer.w_in.codes.shape[0], lp.attn.wq.codes.shape[0], lp.we3.codes.shape[:2]) == (36, 4, (40, 4))
        tokens = _tokens(24, seed=40)
        from dllama_tpu.models import llama
        logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
            eng.params, jnp.asarray([tokens], jnp.int32), _column(cfg, 64))
        want, spread = _spread(bench, eng.params, tokens, model=model)
        assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL * spread
        stats = np.asarray(col.stats)
        assert stats[0] == 24 * 2 * 40 and stats[1] == 0            # every expert held
    finally:
        eng.close()


def test_route_at_ten_of_72_is_top_k_then_softmax(bench):
    """``share.route`` (a softmax over all 72, the ten largest, renormalised
    over the chosen) against the published order in float64 (the ten largest
    LOGITS, then a softmax over those), and against the reference's own
    ``route``."""
    from dllama_tpu.formats.mfile import ArchType, RopeType
    from dllama_tpu.models import share
    from dllama_tpu.models.config import ModelConfig

    cfg = ModelConfig(arch=ArchType.GRANITE_HYBRID, dim=64, hidden_dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
                      head_dim=16, vocab_size=256, seq_len=64, norm_epsilon=1e-5, rope_theta=1e4,
                      rope_type=RopeType.LLAMA, n_experts=72, n_active_experts=10, moe_router_width=72,
                      layer_pattern=("M", "E"))
    rng = np.random.default_rng(72)
    h = rng.standard_normal((40, 64)).astype(np.float32)
    gate = (rng.standard_normal((72, 64)) * 0.5).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        w, idx = share.route(cfg, jnp.asarray(h), jnp.asarray(gate))
        w_ref, idx_ref = bench["reference"].route({"num_experts_per_tok": 10}, jnp.asarray(h), jnp.asarray(gate))
    logits = h.astype(np.float64) @ gate.T.astype(np.float64)
    want = np.argsort(-logits, axis=1, kind="stable")[:, :10]
    assert (np.asarray(idx) == want).all() and (np.asarray(idx_ref) == want).all()
    chosen = np.take_along_axis(logits, want, axis=1)
    soft = np.exp(chosen - chosen.max(axis=1, keepdims=True))
    soft /= soft.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), soft, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(w_ref), soft, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 1.0, rtol=1e-6)


def _routed_cfg(arch, k, width, held, **kw):
    from dllama_tpu.formats.mfile import RopeType
    from dllama_tpu.models.config import ModelConfig

    return ModelConfig(arch=arch, dim=64, hidden_dim=32, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256,
                       seq_len=64, norm_epsilon=1e-5, rope_theta=1e4, rope_type=RopeType.LLAMA, n_experts=held,
                       n_active_experts=k, moe_router_width=width, **kw)


# (arch, a token's experts, the router's width, the experts held) at the published sizes of the routed cells, and the
# rows up to which the step takes the pair form (``expert_gemv``): laguna's, A.X-K1's, lfm2's and nemotron_h's stay where
# they were at every row count up to 16; this family crosses at 8 rows
@pytest.mark.parametrize("name,k,width,held,pair_form_up_to", [
    ("LAGUNA", 10, 256, 32, 16), ("AXK1", 8, 192, 12, 16), ("LFM2", 4, 64, 64, 16), ("NEMOTRON_H", 22, 512, 128, 16),
    ("GRANITE_HYBRID", 10, 72, 72, 7)])
def test_the_step_form_rule_by_family(name, k, width, held, pair_form_up_to):
    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.models import share

    cfg = _routed_cfg(ArchType[name], k, width, held)
    took = [rows for rows in range(1, 40) if share.step_form(cfg, rows)]
    assert took == list(range(1, pair_form_up_to + 1))
    assert not share.step_form(cfg, share.STEP_FORM_MAX_ROWS + 1)          # a chunk is never the pair form


@pytest.mark.parametrize("rows,form", [(2, "step"), (4, "chunk")])
def test_routed_ffn_takes_the_form_the_rule_says(engine, rows, form, monkeypatch):
    """At 3 of 8, two rows are 6 pairs over 8 planes (the pair form), four are
    12 (the run form, and the rows it fed are counted)."""
    from dllama_tpu.models import share

    took = []
    for name in ("_experts_step", "_experts_chunk"):
        fn = getattr(share, name)
        monkeypatch.setattr(share, name, lambda *a, _fn=fn, _n=name, **kw: took.append(_n) or _fn(*a, **kw))
    cfg = engine.cfg
    h = jnp.asarray(np.random.default_rng(rows).standard_normal((rows, 1, 64)), jnp.float32)
    y, stats = share.routed_ffn(cfg, h, engine.params.layers, jnp.int32(1), jnp.ones(rows, bool))
    assert took == ["_experts_" + form] and y.shape == h.shape
    assert int(stats[0]) == rows * 3 and (int(stats[2]) > 0) == (form == "chunk")


# -- the program against the reference ---------------------------------------------------


@pytest.mark.parametrize("T", [20, 70, 300])
def test_whole_forward_logits(bench, engine, T):
    tokens = _tokens(T, seed=T)
    logits, col = _forward(engine, tokens)
    want, spread = _spread(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL * spread
    stats = np.asarray(col.stats)          # every pair counted once and held: 3 a token in 6 routed blocks
    assert stats[0] == T * 3 * 6 and stats[1] == 0 and stats[4:].sum() == stats[0]
    # the column's state and tail carry on: a chunk behind them agrees too
    more = _tokens(9, seed=T + 1)
    logits2, _ = _forward(engine, more, start=T, col=col)
    want2 = bench["reference"].reference_logits(bench["model"], engine.params, tokens + more)[T:]
    assert float(np.abs(np.asarray(logits2[0]) - want2).max()) < LOGIT_TOL * spread


# each control moves the reference's own logits by more than the float32 tolerance, in units of their spread: a
# program that computed it would fail ``test_whole_forward_logits``. The four multipliers' first; the nearest
# precisions below the stated ones (``bf16state``, ``bf16router``) move them least
@pytest.mark.parametrize("variant,least", [
    ("noresmult", 1.0), ("noembmult", 1.0), ("sqrtscale", 0.5), ("nologitscale", 10.0), ("rope", 0.5),
    ("bf16state", 0.03), ("bf16router", 6e-3), ("softmaxall", 0.1), ("misroute", 1.0), ("noshared", 1.0),
    ("dropstate", 0.05), ("secondhalf", 1.0)])
def test_the_references_variants_are_another_function(bench, engine, variant, least):
    tokens = _tokens(300, seed=70)
    honest, spread = _spread(bench, engine.params, tokens)
    moved = float(np.abs(_spread(bench, engine.params, tokens, variant=variant)[0] - honest).max()) / spread
    assert moved > least > LOGIT_TOL, moved


def test_a_multiplier_of_one_is_not_traced_and_each_is_where_the_equation_puts_it(engine):
    """The program with each of the four scalars put back to what another
    family's header would say is the reference's control of the same name; and
    a config whose multipliers are all 1 traces no multiply for them."""
    from dllama_tpu.models import llama

    cfg, tokens = engine.cfg, _tokens(40, seed=4)
    run = lambda c: np.asarray(jax.jit(lambda params, ids, col: llama.forward(params, c, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg))[0][0])
    honest = run(cfg)
    spread = float(honest.std())
    for field, least in (("residual", 1.0), ("embedding", 1.0), ("lm_head", 10.0)):
        moved = run(dataclasses.replace(cfg, mult=cfg.mult._replace(**{field: 1.0})))
        assert float(np.abs(moved - honest).max()) / spread > least, field
    assert float(np.abs(run(dataclasses.replace(cfg, attn_score_scale=0.0)) - honest).max()) / spread > 0.3
    plain = dataclasses.replace(cfg, mult=type(cfg.mult)(), attn_score_scale=0.0)
    text = lambda c: jax.jit(lambda params, ids, col: llama.forward(params, c, ids, jnp.int32(0), col)).lower(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg)).as_text()
    # the residual multiplier alone: one multiply a block (4 traced block bodies and the attention block) and no more
    with_r = dataclasses.replace(plain, mult=plain.mult._replace(residual=0.5))
    assert text(with_r).count("stablehlo.multiply") > text(plain).count("stablehlo.multiply")
    assert "2.200000e-01" not in text(plain) and "1.200000e+01" not in text(plain)


def _decode(gen, slots, n_steps):
    """Greedy decode of ``slots`` by hand over the generator's own pools, one
    step program a token, keeping the logits: what ``PagedGenerator.step``
    dispatches, less the sampler, handed the cache as ``_cache_parts`` says."""
    from dllama_tpu.models import llama

    step = jax.jit(lambda params, *args: llama.paged_forward(params, gen.cfg, *args))   # its own trace cache
    assert gen._cache_parts == ("pkv", "spool", "moe_stats")
    rows = {s: [] for s in slots}
    for _ in range(n_steps):
        for s in slots:
            gen._ensure_blocks(s, int(gen.pos[s]))
        logits, (gen.pkv, gen.spool, gen.moe_stats) = step(
            gen.eng.params, jnp.asarray(gen.next_token[:, None], jnp.int32), jnp.asarray(gen.pos, jnp.int32),
            tuple(getattr(gen, name) for name in gen._cache_parts), jnp.asarray(gen.tables))
        for s in slots:
            rows[s].append(np.asarray(logits[s, 0]))
            gen.next_token[s] = int(rows[s][-1].argmax())
            gen.pos[s] += 1
    return {s: np.stack(r) for s, r in rows.items()}


# prompt lengths on and around the edges (a block's edge, a bucket's, a padded tail, exactly the widest chunk and one
# past it); ``n_slots`` 2: six pairs over eight planes, the PAIR form; 4: twelve, the RUN form (its every-row oracle
# off a TPU). kernel "fused": the steps' attention through paged_ragged_attention, the routed feed-forward through
# expert_gemv and the mixer through ssd_step at ONE group, all in interpret mode, a dead slot with a stale depth beside
@pytest.mark.parametrize("n_prompt,n_slots,kernel", [(17, 2, None), (33, 2, None), (70, 2, None), (257, 2, None),
                                                     (258, 4, None), (300, 4, None), (70, 4, None), (70, 2, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, n_slots, kernel, monkeypatch):
    from dllama_tpu.models import share
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops import ssd
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"experts": 0, "ssd": 0, "runs": 0}
    gemv, step, chunk = eg.expert_gemv, ssd.ssd_step, share._experts_chunk
    monkeypatch.setattr(eg, "expert_gemv",
                        lambda *a, **kw: calls.__setitem__("experts", calls["experts"] + 1) or gemv(*a, **kw))
    monkeypatch.setattr(ssd, "ssd_step", lambda *a, **kw: calls.__setitem__("ssd", calls["ssd"] + 1) or step(*a, **kw))
    monkeypatch.setattr(share, "_experts_chunk",
                        lambda *a, **kw: calls.__setitem__("runs", calls["runs"] + 1) or chunk(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=n_slots)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 20
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    calls["runs"] = 0                     # the admission's chunks are the run form by their width
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # traced bodies of the step: (ME) in a loop, *, (EM) in a loop, E, *, E: four routed bodies of three projections,
    # two mixer bodies
    assert (calls["experts"], calls["ssd"]) == ((12, 2) if kernel else (0, 0))
    assert calls["runs"] == (4 if n_slots == 4 else 0)
    want, spread = _spread(bench, engine.params, prompt + emitted)
    want = want[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL * spread
    totals = np.asarray(gen.moe_stats)
    assert totals[0, 0] == n_steps * 3 * 6 and totals[1, 0] == (n_prompt - 1) * 3 * 6 and not totals[:, 1].any()
    assert totals[0, 3] == totals[0, 0]                   # one live row: every pair its own plane
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)


def test_one_group_of_b_and_c_through_both_ssd_forms():
    """``ssm_groups = 1``: the chunk form, the step's XLA form and the Pallas
    step kernel (interpret mode) against the token-by-token recurrence, every
    head on the ONE group's B and C."""
    from dllama_tpu.ops import ssd

    rng = np.random.default_rng(1)
    B, T, H, P, G, N = 2, 64, 16, 8, 1, 16
    f = lambda *shape, scale=1.0: jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    x, Bm, Cm = f(B, T, H, P), f(B, T, G, N), f(B, T, G, N)
    dt = jax.nn.softplus(f(B, T, H))
    A = -jnp.exp(f(H, scale=0.5))
    S0 = f(B, H, P, N, scale=0.1)
    with jax.default_matmul_precision("highest"):
        y_ref, S_ref = ssd.ssd_recurrent(x, dt, A, Bm, Cm, S0)
        y_chunk, S_chunk = ssd.ssd_chunk(x, dt, A, Bm, Cm, S0, 16)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref), atol=2e-4)
    np.testing.assert_allclose(np.asarray(S_chunk), np.asarray(S_ref), atol=2e-4)
    pool = jnp.concatenate([jnp.zeros((1, H, P, N)), S0])[None]            # [1 layer, rows (null first), H, P, N]
    rows = jnp.asarray([1, 2], jnp.int32)
    args = (jnp.int32(0), rows, x[:, 0], dt[:, 0], jnp.exp(dt[:, 0] * A), Bm[:, 0], Cm[:, 0])
    y_one, S_one = ssd.ssd_recurrent(x[:, :1], dt[:, :1], A, Bm[:, :1], Cm[:, :1], S0)
    y_xla, pool_xla = ssd.ssd_step_xla(pool, *args)
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_one[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool_xla[0, 1:]), np.asarray(S_one), atol=1e-5)
    y_k, pool_k = ssd.ssd_step(pool, *args, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_one[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool_k[0, 1:]), np.asarray(S_one), atol=1e-5)


def test_the_chunk_form_carries_the_scope_a_trace_reads():
    """``ops/ssd.ssd_chunk`` runs under ``jax.named_scope("ssd_chunk")``: the
    scope is in the lowered text's locations (what a device trace's op names
    are made from) and not in the text a digest is taken of."""
    from dllama_tpu.ops import ssd

    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    lowered = jax.jit(lambda *a: ssd.ssd_chunk(*a, 16)).lower(
        S((1, 32, 4, 8), f32), S((1, 32, 4), f32), S((4,), f32), S((1, 32, 1, 16), f32), S((1, 32, 1, 16), f32),
        S((1, 4, 8, 16), f32))
    assert "ssd_chunk" not in lowered.as_text()
    assert "ssd_chunk" in lowered.as_text(debug_info=True)


def test_a_bfloat16_program_stays_within_its_stated_tolerance(bench, tmp_path):
    """A bfloat16 engine against the float32 reference, in units of the
    logits' spread: over the float32 tolerance (so the tolerance does part
    them) and under a stated one, the tie still one buffer in bfloat16."""
    eng = _engine(bench, tmp_path, dtype="bfloat16", seed=11)
    try:
        assert eng.params.logits is eng.params.embedding and eng.params.embedding.dtype == jnp.bfloat16
        tokens = _tokens(120, seed=12)
        logits, _ = _forward(eng, tokens, col=_column(eng.cfg, dtype=jnp.bfloat16))
        want, spread = _spread(bench, eng.params, tokens)
        apart = np.abs(np.asarray(logits[0]) - want) / spread
        assert LOGIT_TOL < float(apart.mean()) < 0.1, apart.mean()
        assert float(np.median(apart.max(axis=1))) < 0.4
    finally:
        eng.close()


def test_scheduler_serves_state_and_counters_and_the_chunks_planes_ride_the_span(bench, engine, tmp_path):
    """Through ``BatchScheduler``: interleaved requests finish and are the
    reference's tokens, the prefix is NOT reused, the layer kinds read right,
    and while a profiler listens the steps' spans carry ``moe_chunk_planes``
    (the totals' chunk row, what ``expert_chunk_prefill_hbm_share`` multiplies)
    beside ``moe_chunk_held`` / ``moe_chunk_fed``; the new reader reads them."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    skipped = reg.counter(telemetry.PREFIX_REUSE_SKIPPED)
    skip0 = skipped.total(reason="recurrent_state")
    sched = BatchScheduler(engine, n_slots=3)
    try:
        prompts = [_tokens(n, seed=n) for n in (90, 33, 150)]
        reqs = [sched.submit(p, 12, stop_on_eos=False) for p in prompts]
        for r in reqs:
            assert r.done.wait(300) and not r.error
        again = sched.submit(prompts[0], 12, stop_on_eos=False)
        assert again.done.wait(300) and list(again.tokens) == list(reqs[0].tokens)
        assert skipped.total(reason="recurrent_state") == skip0 + 1
        kinds = reg.gauge(telemetry.LAYER_KINDS)
        assert [kinds.value(kind=k) for k in ("mamba", "attention", "moe", "full", "conv")] == [4, 2, 6, 0, 0]
        want = bench["reference"].reference_logits(bench["model"], engine.params, prompts[1] + list(reqs[1].tokens))
        assert [int(r.argmax()) for r in want[len(prompts[1]) - 1:-1]] == list(reqs[1].tokens)
        import program_spans        # benchmark/program_spans.py
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            first = sched.submit(_tokens(50, seed=50), 40, stop_on_eos=False)
            deadline = time.monotonic() + 300
            while len(first.tokens) < 4 and time.monotonic() < deadline:      # a second admission BETWEEN its steps
                time.sleep(0.01)
            second = sched.submit(_tokens(280, seed=51), 8, stop_on_eos=False)
            assert first.done.wait(300) and second.done.wait(300) and not first.error and not second.error
        spans = program_spans.load(program_spans.newest_trace(trace_dir))
        steps = [st for t in spans["ticks"] for name, _s, _e, st in t["children"]
                 if name == "step_wait" and "moe_chunk_planes" in st]
        assert len(steps) >= 24 and all("moe_chunk_held" in st and "moe_planes" in st for st in steps)
        planes, held = [int(st["moe_chunk_planes"]) for st in steps], [int(st["moe_chunk_held"]) for st in steps]
        assert planes == sorted(planes) and planes[-1] > planes[0] and held[-1] > held[0]
        # a chunk's rows choose at most every held expert a routed block, and no more planes than pairs
        assert planes[-1] - planes[0] <= min(held[-1] - held[0], 6 * 8 * 4)
        reader = _import("expert_planes_span_roofline", os.path.join(BENCH, "readers", "expert_planes_span_roofline.py"))
        with open(os.path.join(BENCH, "layer_metrics", "expert_chunk_prefill_hbm_share.json"), encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["reader"] == "expert_planes_span_roofline" and spec["args"]["field"] == "moe_chunk_planes"
        ctx = {"trace": {"device_ops": [("forward/expert_chunk.1", 0.5), ("paged_sampled_step_guarded/expert_chunk", 9.0),
                                        ("forward/fusion.3", 2.0)]},
               "program_spans": spans, "counts": bench["counts"], "model": bench["model"],
               "peaks": {"hbm_bytes_per_s": 1e9}}
        one = bench["counts"].kernel_counts(bench["model"], "expert_chunk", rows=1)["bytes"]
        assert reader.read(ctx, **spec["args"]) == pytest.approx(100.0 * one * (planes[-1] - planes[0]) / 1e9 / 0.5)
        assert reader.read({**ctx, "program_spans": {"ticks": []}}, **spec["args"]) is None     # a parent's spans
        assert reader.read({**ctx, "trace": None}, **spec["args"]) is None
    finally:
        sched.close()


# -- what is refused, the header, the converter --------------------------------------


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_block_size": 0}, "--kv-block-size"),
    ({"spec_lookup": 3}, "--spec-lookup"),
    ({"kv_host_blocks": 32}, "--kv-host-blocks"),
    ({"tp": 2}, "--tp > 1"),
    ({"pp": 2}, "--pp > 1"),
    ({"weight_mode": "offload"}, "--weight-mode offload"),
])
def test_refused_at_construction_with_the_flag_named(bench, tmp_path, kwargs, named):
    with pytest.raises(ValueError, match="a mixer then routed experts a layer") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_header_round_trip_and_walk(bench, tmp_path):
    from dllama_tpu.formats.mfile import ArchType, HiddenAct, ModelFile, parse_header, write_header

    path = str(tmp_path / "walk.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path) as mf:
        h = mf.header
        assert (h.arch_type, h.hidden_act, h.layer_pattern, h.moe_latent_dim, h.n_layers) == (
            ArchType.GRANITE_HYBRID, HiddenAct.SILU, "MEME*EMEME*E", 0, 12)
        assert (h.embedding_mult, h.lm_head_mult, h.attn_scale, h.tied_embeddings) == (12.0, 0.0625, 0.0625, 1)
        assert np.float32(h.residual_mult) == np.float32(0.22)
        assert (h.ssm_n_heads, h.ssm_head_dim, h.ssm_n_groups, h.ssm_state_dim, h.ssm_conv_kernel) == (4, 32, 1, 16, 4)
        assert (h.moe_router_width, h.moe_first_expert, h.n_experts, h.n_active_experts, h.shared_expert_dim) == (
            8, 0, 8, 3, 64)
        assert (h.pattern_layers("M"), h.pattern_layers("*")) == ([0, 2, 6, 8], [4, 10])
        assert mf.tensors["block_ssm_in.0"].shape == (288, 64) and mf.tensors["block_ssm_dt.0"].shape == (4, 64)
        assert mf.tensors["block_matmul_q.4"].shape == (64, 64) and "block_matmul_q.0" not in mf.tensors
        for plane, shape in (("w1", (32, 64)), ("w2", (64, 32)), ("w3", (32, 64))):
            assert mf.tensors[f"block_expert_{plane}.1.7"].shape == shape
        assert mf.tensors["block_shared_w3.1"].shape == (64, 64) and "block_latent_in.1" not in mf.tensors
        assert "block_moe_bias.1" not in mf.tensors and "block_norm_1.0" not in mf.tensors
        assert mf.tensors["final_matmul_logits"].shape == (256, 64)        # carried, as the reference format does
        last = max(mf.tensors.values(), key=lambda r: r.offset)
        assert last.offset + last.n_bytes == os.path.getsize(path)             # the walk ends where the file does
    # the writer's own header round-trips the three new keys, and a latent is refused
    import io
    fields = {"version": 1, "arch_type": int(ArchType.GRANITE_HYBRID), "dim": 64, "hidden_dim": 32, "n_layers": 2,
              "n_heads": 4, "n_kv_heads": 2, "n_experts": 8, "n_active_experts": 3, "vocab_size": 256, "seq_len": 64,
              "weight_float_type": 2, "head_dim": 16, "norm_epsilon": 5, "ssm_n_heads": 4, "ssm_head_dim": 32,
              "ssm_n_groups": 1, "ssm_state_dim": 16, "ssm_conv_kernel": 4, "ssm_chunk_size": 32,
              "residual_mult": 0.22, "attn_scale": 0.0078125, "tied_embeddings": 1, "layer_pattern": "ME"}
    buf = io.BytesIO()
    write_header(buf, fields)
    h = parse_header(buf.getvalue(), 0)
    assert (np.float32(h.residual_mult), h.attn_scale, h.tied_embeddings, h.layer_pattern) == (
        np.float32(0.22), 0.0078125, 1, "ME")
    buf = io.BytesIO()
    write_header(buf, dict(fields, moe_latent_dim=16))
    with pytest.raises(ValueError, match="granite_hybrid model: a latent of 16"):
        parse_header(buf.getvalue(), 0)


def _synthetic_checkpoint(folder, cfg: dict, rng):
    """A checkpoint under ``model_type: granitemoehybrid``'s tensor names, the
    head tied (no ``lm_head.weight``)."""
    from safetensors.numpy import save_file

    d, H = cfg["hidden_size"], cfg["mamba_n_heads"]
    d_ssm = H * cfg["mamba_d_head"]
    conv = d_ssm + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hid, wide, E = cfg["intermediate_size"], cfg["shared_intermediate_size"], cfg["num_local_experts"]
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n = lambda *shape, scale=0.1: (rng.standard_normal(shape) * scale).astype(np.float32)
    t = {"model.embed_tokens.weight": n(cfg["vocab_size"], d, scale=0.01), "model.norm.weight": np.ones(d, np.float32)}
    for l, kind in enumerate(cfg["layer_types"]):
        pre = f"model.layers.{l}."
        t[pre + "input_layernorm.weight"] = np.ones(d, np.float32)
        t[pre + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        if kind == "mamba":
            mx = pre + "mamba."
            t.update({mx + "in_proj.weight": n(d_ssm + conv + H, d), mx + "conv1d.weight": n(conv, 1, cfg["mamba_d_conv"], scale=0.5),
                      mx + "conv1d.bias": n(conv), mx + "A_log": n(H, scale=1.0), mx + "D": np.ones(H, np.float32),
                      mx + "dt_bias": n(H, scale=1.0), mx + "norm.weight": np.ones(d_ssm, np.float32),
                      mx + "out_proj.weight": n(d, d_ssm)})
        else:
            at = pre + "self_attn."
            t.update({at + "q_proj.weight": n(q, d, scale=0.5), at + "k_proj.weight": n(kv, d), at + "v_proj.weight": n(kv, d),
                      at + "o_proj.weight": n(d, q)})
        t.update({pre + "block_sparse_moe.router.layer.weight": n(E, d, scale=0.5),
                  pre + "block_sparse_moe.input_linear.weight": n(E, 2 * hid, d),
                  pre + "block_sparse_moe.output_linear.weight": n(E, d, hid),
                  pre + "shared_mlp.input_linear.weight": n(2 * wide, d),
                  pre + "shared_mlp.output_linear.weight": n(d, wide)})
    save_file(t, str(folder / "model.safetensors"))
    return t


def test_the_converter_maps_a_synthetic_checkpoint_and_the_file_is_served(bench, tmp_path):
    """``convert/hf.py`` on a checkpoint under the family's tensor names: the
    fused ``input_linear`` split into the ``we1`` / ``we3`` (``ws1`` / ``ws3``) planes,
    the FIRST half the one under ``silu``; the mixer's ``in_proj`` split into the
    packed plane and the float32 ``dt`` rows; the tied head written from the
    embedding and kept as ONE array by the streaming loader (no seam); the file
    served through ``BatchScheduler`` to the reference's tokens."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.convert import hf
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.ops.linear import dequantize_weight
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    with open(TINY, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    src = tmp_path / "hf"
    src.mkdir()
    (src / "config.json").write_text(json.dumps(published))
    tensors = _synthetic_checkpoint(src, published, np.random.default_rng(3))
    out = str(tmp_path / "converted.m")
    hf.convert_hf(src, "q40", out)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(out, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        cfg, p = eng.cfg, eng.params
        lp = p.layers
        assert "".join(cfg.layer_pattern) == "MEME*EMEME*E" and cfg.tied_embeddings and p.logits is p.embedding
        np.testing.assert_array_equal(np.asarray(p.embedding), tensors["model.embed_tokens.weight"])
        assert (cfg.mult.embedding, cfg.mult.lm_head, cfg.attn_score_scale) == (12.0, 0.0625, 0.0625)
        in_proj = tensors["model.layers.3.mamba.in_proj.weight"]
        np.testing.assert_array_equal(np.asarray(lp.mixer.w_dt[2]), in_proj[-4:])
        plane = lambda stack, *at: np.asarray(dequantize_weight(jax.tree.map(lambda a: a[at], stack)))
        np.testing.assert_allclose(plane(lp.mixer.w_in, 2), in_proj[:-4].T, atol=0.05)
        fused = tensors["model.layers.4.block_sparse_moe.input_linear.weight"]
        np.testing.assert_allclose(plane(lp.we1, 4, 5), fused[5, :32].T, atol=0.05)       # the half under silu
        np.testing.assert_allclose(plane(lp.we3, 4, 5), fused[5, 32:].T, atol=0.05)
        np.testing.assert_allclose(plane(lp.we2, 4, 5),
                                   tensors["model.layers.4.block_sparse_moe.output_linear.weight"][5].T, atol=0.05)
        shared = tensors["model.layers.2.shared_mlp.input_linear.weight"]
        np.testing.assert_allclose(plane(lp.ws1, 2), shared[:64].T, atol=0.05)
        np.testing.assert_allclose(plane(lp.ws3, 2), shared[64:].T, atol=0.05)
        np.testing.assert_array_equal(np.asarray(lp.moe_gate[1]), tensors["model.layers.1.block_sparse_moe.router.layer.weight"])
        np.testing.assert_allclose(plane(lp.attn.wo, 1), tensors["model.layers.5.self_attn.o_proj.weight"].T, atol=0.05)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            req = sched.submit(prompt, 6, stop_on_eos=False)
            assert req.done.wait(300) and not req.error
            want = bench["reference"].reference_logits(bench["model"], eng.params, prompt + list(req.tokens))
            assert [int(r.argmax()) for r in want[len(prompt) - 1:-1]] == list(req.tokens)
        finally:
            sched.close()
    finally:
        eng.close()
    (src / "config.json").write_text(json.dumps(dict(published, layer_types=["mamba", "conv"] * 3)))
    with pytest.raises(ValueError, match="entries of mamba / attention"):
        hf.load_hf_config(src, 2)


@pytest.mark.parametrize("model_type,extra", [("falcon_h1", {}), ("axk1", {})])
def test_the_refusals_of_a_tie_name_the_families_that_carry_one(tmp_path, model_type, extra):
    from dllama_tpu.convert import hf

    fn = {"falcon_h1": hf._falcon_h1_header, "axk1": hf._axk1_header}[model_type]
    with pytest.raises(ValueError, match="granitemoehybrid, whose head and embedding are ONE array"):
        fn({"tie_word_embeddings": True, **extra})


def test_the_budget_counts_a_tied_head_once(engine):
    from dllama_tpu.models.family import family_of
    from dllama_tpu.runtime import hbm

    cfg = dataclasses.replace(engine.cfg, compute_dtype="bfloat16")
    tied = hbm.estimate_device_bytes(cfg, weight_repr="q40", kv_dtype_bytes=2)["weights_bytes"]
    untied = dataclasses.replace(cfg, tied_embeddings=False)
    twice = hbm.estimate_device_bytes(untied, weight_repr="q40", kv_dtype_bytes=2)["weights_bytes"]
    head = cfg.vocab_size * cfg.dim
    assert family_of(cfg).matmul_weight_count(untied) - family_of(cfg).matmul_weight_count(cfg) == head
    assert twice - tied == 2 * head                     # a second dense bfloat16 array of the vocabulary
    planes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(engine.params.layers))
    held = sum(a.size * a.dtype.itemsize for a in {id(a): a for a in jax.tree.leaves(engine.params)}.values())
    assert held - planes == (head + cfg.dim) * 4        # float32 here: ONE embedding and the final norm


def test_the_cell_configuration_is_the_issues_reckoning(bench):
    """Every published width unchanged (the catalog's row, copied here);
    ``reduced`` exactly what was cut; the floors hold; the counts module's
    bytes are the issue's."""
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {"attention_bias": False, "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
                 "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768, "logits_scaling": 16,
                 "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
                 "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
                 "mamba_proj_bias": False, "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
                 "num_attention_heads": 32, "num_experts_per_tok": 10, "num_key_value_heads": 8, "num_local_experts": 72,
                 "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
                 "rope_scaling": None, "rope_theta": 10000, "shared_intermediate_size": 1536,
                 "tie_word_embeddings": True, "vocab_size": 100352, "max_position_embeddings": 131072}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["num_hidden_layers"] == 10 and conf["layer_types"] == PUBLISHED_TYPES[:10]
    assert conf["reduced_from"] == {"num_hidden_layers": 40, "layer_types": PUBLISHED_TYPES,
                                    "max_position_embeddings": 131072}
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["deployment"] and conf["memory"]
    assert set(bench["weights"].ASSUMED) <= set(conf["program"]) and set(bench["weights"].ASSUMED) <= set(conf["assumed"])
    assert conf["program"]["head_dim"] == 128 == conf["hidden_size"] // conf["num_attention_heads"]
    assert (conf["engine"]["slots"], conf["engine"]["max_seq_len"], conf["engine"]["kv_block_size"]) == (16, 8704, 16)
    model, c = bench_run.model_view(conf), bench["counts"]
    assert model["norm_epsilon"] == 1e-5 and bench["weights"].pattern(model) == "MEMEMEMEME*EMEMEMEME"
    planes = (c.always_read_weights(model) + 10 * 72 * 3 * 4096 * 768) * 1.0625
    assert 8.40e9 < planes < 8.48e9                                    # + 0.82 GB of ONE embedding, float32 rows: 9.3 GB
    one = c.kernel_counts(model, "expert_chunk", rows=13)
    assert abs(one["bytes"] - 10.03e6) < 0.01e6 and one["layers"] == 10 and one["calls_per_program"] == 30
    assert one["pairs_per_layer"] == 130 and 61 < one["planes_per_layer"] < 62.5
    assert 8.4e9 < c.prefill_chunk_bytes(model, chunk=256, context_before=2048) < 8.7e9
    assert 1.05e12 < c.prefill_chunk_flops(model, chunk=256, context_before=2048) < 1.15e12
    assert c.kernel_counts(model, "ssd_step", rows=16)["calls_per_program"] == 9
    assert c.kernel_counts(model, "paged_ragged_attention", rows=1)["bytes"] == 4096.0
