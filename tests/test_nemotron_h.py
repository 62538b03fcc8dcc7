"""A decoder whose every layer is ONE block of a pattern: an SSD mixer,
attention without positions, or a routed feed-forward of ungated experts in a
latent space (``ArchType.NEMOTRON_H``, ``models/nemotron_h.py``), against the
benchmark's plain reference (``benchmark/nemotron_h/reference.py``, imported
from where it lies, no copy) on seeded weights from the benchmark's own
weight-maker, at tiny widths on the CPU.

What is held: logits of prefill then decode through the paged generator and
``BatchScheduler`` against the reference's full forward pass (float32 tight;
bfloat16 at a tolerance a bfloat16 state or router in the reference fails);
the chunk form against the step form across a chunk boundary with padding;
THE SHARE TEST (four shares of a layer, the shared expert and the residual
counted once, add up to the uncut reference's whole layer, the latent
up-projection applied a share); the walk over the PUBLISHED 88-character
pattern; ``route`` at 22 of 512 with a bias; the converter's name map on a
synthetic checkpoint; the refusals by flag; the cell's configuration.
"""

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
NH = os.path.join(BENCH, "nemotron_h")
TINY = os.path.join(NH, "selftest", "configs", "tiny-nemotron-h.json")
MANIFEST = os.path.join(NH, "selftest", "manifest.json")
REAL = os.path.join(BENCH, "configs", "nemotron-3-super-120b-a12b.json")
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
LOGIT_TOL = 2e-3            # float32 program against the float32 reference (logits of spread 1)
BF16_MEAN_TOL, BF16_MEDIAN_TOL = 0.08, 0.15    # a bfloat16 program: the mean difference of a logit, and the median over
                            # positions of a position's worst (two seeds read 0.029-0.044 and 0.07-0.09); the WORST position reads
                            # 1-2: 4 of 16 experts at width 64 flip at a near-tie, another function. The float32 tolerance is the one
                            # a bfloat16 state or router in the reference fails (3e-3 and more)


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
    return {"weights": _import("nemotron_h_weights", os.path.join(NH, "weights.py")),
            "reference": _import("nemotron_h_reference", os.path.join(NH, "reference.py")),
            "counts": _import("nemotron_h_counts", os.path.join(NH, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-nemotron-h.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("nemotron_h"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens, model=None, variant="none"):
    ref, dense, model = bench["reference"], dense_reference, model or bench["model"]
    T = len(tokens)
    padded = -(-T // dense.BLOCK_Q) * dense.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:T] = tokens
    fn = ref._layers_fn(json.dumps(model, sort_keys=True), variant, False, 0.0)
    x = fn(jnp.asarray(ids), params.embedding, ref.layer_tree(params),
           *dense.control_handles(model["num_hidden_layers"], T, padded, "none"))
    h = dense._rms_norm(x.astype(jnp.float32), params.final_norm, float(model["norm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ dense._dequant(dense._planes(params.logits)))[:T]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _column(cfg, seq_len=512, dtype=jnp.float32):
    from dllama_tpu.runtime.kvblocks import StateColumn

    k = jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq_len, cfg.cache_width), dtype)
    return StateColumn.zeros(cfg, k, k, dtype)


# -- the configuration as the program sees it ----------------------------------------


def test_the_pattern_the_pools_and_the_state_are_the_architectures(engine):
    """Two periods of ``EMEM*``: 4 mixer, 2 attention and 4 routed layers; K/V of
    the attention layers alone, a state and a tail of the mixer layers alone;
    an expert's 288 lanes held in 512 with zeros behind."""
    from dllama_tpu.models import nemotron_h
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool, state_pool_bytes

    cfg = engine.cfg
    assert "".join(cfg.layer_pattern) == "EMEM*EMEM*" and cfg.n_layers == 10
    assert (cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers, cfg.n_dense_layers) == (4, 2, 4, 0)
    assert cfg.has_state and cfg.has_ssm and cfg.has_expert_share and cfg.paged_only and not cfg.has_short_conv
    assert cfg.state_shape(5) == (4, 5, 4, 32, 16) and cfg.conv_shape(5) == (4, 5, 3, 128 + 2 * 2 * 16)
    assert (cfg.moe_latent_dim, cfg.moe_select_bias, cfg.moe_norm_eps, cfg.moe_score) == (32, True, 1e-20, "sigmoid")
    assert (cfg.hidden_dim, cfg.expert_width_held, cfg.shared_expert_dim, cfg.moe_routed_scale) == (288, 512, 64, 5.0)
    assert all(getattr(cfg.mult, f) == 1.0 for f in cfg.mult._fields)
    pool = StatePool.create(cfg, 4, jnp.float32)
    assert pool.s.shape == (4, 5, 4, 32, 16) and pool.conv.shape == (4, 5, 3, 192)
    assert state_pool_bytes(cfg, 4, 4) == pool.s.nbytes + pool.conv.nbytes
    assert PagedKVCache.create(cfg, 9, 16).k.shape == (2, 9, 2, 16, 16)
    lp = engine.params.layers
    assert lp.we1.codes.shape == (4, 8, 32, 512) and lp.we2.codes.shape == (4, 8, 512, 32) and lp.we3 is None
    assert not np.asarray(lp.we1.codes[..., 288:]).any() and not np.asarray(lp.we2.codes[..., 288:, :]).any()
    assert np.asarray(lp.we1.codes[..., :288]).any() and lp.ws3 is None and lp.w_lat_in.codes.shape == (4, 64, 32)
    assert nemotron_h.fold_runs(nemotron_h.pattern_runs(cfg.layer_pattern)) == ([("EM", 2), ("*", 1)], 2)
    assert nemotron_h.stack_indices(cfg.layer_pattern).tolist() == [0, 0, 1, 1, 0, 2, 2, 3, 3, 1]


def test_the_published_pattern_is_19_runs_over_three_stacks_of_40_8_40():
    from dllama_tpu.models import nemotron_h

    runs = nemotron_h.pattern_runs(PUBLISHED)
    assert "".join(unit * n for unit, n in runs) == PUBLISHED and len(PUBLISHED) == 88
    assert [PUBLISHED.count(k) for k in "M*E"] == [40, 8, 40]
    assert [l for l, k in enumerate(PUBLISHED) if k == "*"] == [7, 16, 25, 36, 47, 58, 69, 78]
    assert len(runs) == 19 and nemotron_h.fold_runs(runs) == (runs, 1)
    assert runs[:5] == [("ME", 3), ("M", 1), ("*", 1), ("EM", 4), ("*", 1)] and runs[-1] == ("E", 1)
    held = PUBLISHED[26:48]
    assert held == "EMEMEMEMEM*EMEMEMEMEM*"
    assert nemotron_h.fold_runs(nemotron_h.pattern_runs(held)) == ([("EM", 5), ("*", 1)], 2)
    index = nemotron_h.stack_indices(PUBLISHED)
    assert index[7] == 0 and index[78] == 7 and index[87] == 39 and index[86] == 39


def test_the_walk_takes_the_published_88_layers_at_tiny_widths(bench, tmp_path):
    """The published string at tiny widths through the program and the
    reference: 40 / 8 / 40 layers in the three stacks and the pools."""
    from dllama_tpu.models import llama
    from dllama_tpu.runtime.kvblocks import StatePool

    model = dict(bench["model"], hybrid_override_pattern=PUBLISHED, num_hidden_layers=88, moe_intermediate_size=32,
                 intermediate_size=32, n_routed_experts=4, router_width=8, first_expert=2, num_experts_per_tok=2)
    eng = _engine(bench, tmp_path, model=model, seq_len=64)
    try:
        cfg = eng.cfg
        assert (cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers) == (40, 8, 40)
        assert StatePool.create(cfg, 2, jnp.float32).s.shape[0] == 40
        lp = eng.params.layers
        assert (lp.mixer.w_in.codes.shape[0], lp.attn.wq.codes.shape[0], lp.we1.codes.shape[:2]) == (40, 8, (40, 4))
        tokens = _tokens(24, seed=88)
        logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
            eng.params, jnp.asarray([tokens], jnp.int32), _column(cfg, 64))
        want = _reference_logits(bench, eng.params, tokens, model=model)
        assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
        stats = np.asarray(col.stats)
        assert stats[0] + stats[1] == 24 * 2 * 40 and stats[0] > 0 and stats[1] > 0
    finally:
        eng.close()


def test_route_at_22_of_512_with_a_bias_against_a_plain_top_k(bench):
    """The router at the published width and count: a sigmoid over 512 in
    float32, the 22 experts by ``s + b``, their weights ``s`` alone over their
    sum (+1e-20) times 5."""
    import dataclasses

    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.models import share
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.formats.mfile import RopeType

    cfg = ModelConfig(arch=ArchType.NEMOTRON_H, dim=64, hidden_dim=32, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=16,
                      vocab_size=256, seq_len=64, norm_epsilon=1e-5, rope_theta=1e4, rope_type=RopeType.LLAMA,
                      n_experts=128, n_active_experts=22, moe_router_width=512, moe_score="sigmoid",
                      moe_select_bias=True, moe_norm_eps=1e-20, moe_routed_scale=5.0, layer_pattern=("E",))
    rng = np.random.default_rng(22)
    h = rng.standard_normal((40, 64)).astype(np.float32)
    gate = (rng.standard_normal((512, 64)) * 0.5).astype(np.float32)
    bias = (rng.standard_normal(512) * 0.05).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        w, idx = share.route(cfg, jnp.asarray(h), jnp.asarray(gate), jnp.asarray(bias))
    s = 1.0 / (1.0 + np.exp(-(h.astype(np.float64) @ gate.T.astype(np.float64))))
    want = np.argsort(-(s + bias), axis=1, kind="stable")[:, :22]
    assert (np.sort(np.asarray(idx), axis=1) == np.sort(want, axis=1)).all()
    plain = np.argsort(-s, axis=1, kind="stable")[:, :22]
    assert (np.sort(plain, axis=1) != np.sort(want, axis=1)).any(axis=1).mean() > 0.5     # the bias is in the choice
    chosen = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.asarray(w), 5.0 * chosen / chosen.sum(axis=1, keepdims=True), rtol=2e-5)
    local, stats = share.routed_pairs(dataclasses.replace(cfg, moe_first_expert=128), idx, jnp.ones(40, bool))
    held = ((np.asarray(idx) >= 128) & (np.asarray(idx) < 256)).sum()
    assert int(stats[0]) == held and int(stats[0] + stats[1]) == 40 * 22 and int((np.asarray(local) < 128).sum()) == held


def test_an_unknown_activation_raises_and_relu2_is_the_square_of_a_relu(engine):
    import dataclasses

    from dllama_tpu.formats.mfile import HiddenAct
    from dllama_tpu.models.llama import _hidden_act

    x = jnp.asarray([-2.0, 0.0, 0.5, 3.0])
    assert engine.cfg.hidden_act == HiddenAct.RELU2
    assert np.asarray(_hidden_act(engine.cfg, x)).tolist() == [0.0, 0.0, 0.25, 9.0]
    with pytest.raises(ValueError, match="unknown hidden activation"):
        _hidden_act(dataclasses.replace(engine.cfg, hidden_act=7), x)


# -- the program against the reference ---------------------------------------------------


@pytest.mark.parametrize("T", [20, 70, 300])
def test_whole_forward_logits(bench, engine, T):
    from dllama_tpu.models import llama

    cfg, tokens = engine.cfg, _tokens(T, seed=T)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg))
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)          # every pair counted once, held or absent: 4 a token in 4 routed layers
    assert stats[0] + stats[1] == T * 4 * 4 and stats[4:].sum() == stats[0] and 0 < stats[1]
    # the column's state and tail carry on: a chunk behind them agrees too
    more = _tokens(9, seed=T + 1)
    logits2, _ = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(T), col))(
        engine.params, jnp.asarray([more], jnp.int32), col)
    want2 = _reference_logits(bench, engine.params, tokens + more)[T:]
    assert float(np.abs(np.asarray(logits2[0]) - want2).max()) < LOGIT_TOL


@pytest.mark.parametrize("variant,least", [("dropstate", 0.5), ("nodecay", 0.5), ("bf16state", 3e-3), ("misroute", 0.5),
                                           ("noshared", 0.5), ("bf16router", 3e-3), ("nolatent", 0.3), ("gated", 0.5),
                                           ("nobias", 3e-3), ("rope", 0.3)])
def test_the_references_variants_are_another_function(bench, engine, variant, least):
    """Every control moves the reference's own logits by more than the
    float32 tolerance: a program that computed it would fail
    ``test_whole_forward_logits``. The nearest precisions below the stated
    ones (``bf16state``, ``bf16router``) and the bias move them least."""
    tokens = _tokens(300, seed=70)
    honest = _reference_logits(bench, engine.params, tokens)
    moved = float(np.abs(_reference_logits(bench, engine.params, tokens, variant=variant) - honest).max())
    assert moved > least > LOGIT_TOL, moved


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


# prompt lengths on and around the edges: 17 / 16 prefilled positions (a block's edge; a bucket's), 33 (a padded
# 32-bucket behind a whole one), 70 (64 + a padded tail: state and tail lie BEHIND padding), 257 / 258 (exactly the
# widest chunk; one past it: a second chunk of one position), 300 (256, 32, 11 padded to 16). kernel "fused": the
# steps' attention through paged_ragged_attention, the routed feed-forward through expert_gemv and the mixer through
# ssd_step, all in interpret mode, a dead slot with a stale depth beside the live one.
@pytest.mark.parametrize("n_prompt,kernel", [(17, None), (18, None), (33, None), (70, None), (257, None), (258, None),
                                             (300, None), (70, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops import ssd
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"experts": 0, "ssd": 0}
    gemv, step = eg.expert_gemv, ssd.ssd_step
    monkeypatch.setattr(eg, "expert_gemv",
                        lambda *a, **kw: calls.__setitem__("experts", calls["experts"] + 1) or gemv(*a, **kw))
    monkeypatch.setattr(ssd, "ssd_step", lambda *a, **kw: calls.__setitem__("ssd", calls["ssd"] + 1) or step(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 20
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # traced once each: ONE pair body (a routed block of two GEMVs, a mixer) in the loop of the scanned period
    assert (calls["experts"], calls["ssd"]) == ((2, 1) if kernel else (0, 0))
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL
    totals = np.asarray(gen.moe_stats)
    assert totals[0, 0] + totals[0, 1] == n_steps * 4 * 4 and totals[1, 0] + totals[1, 1] == (n_prompt - 1) * 4 * 4
    assert totals[0, 3] == totals[0, 0]                   # one live row: every held pair its own plane
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)


def test_the_chunk_form_is_the_step_form_across_a_chunk_boundary_with_padding(bench, engine):
    """70 positions as one padded chunk of 128 (58 of padding behind them) and
    as 64 by the chunk form then six single steps of the chunk form at ``T =
    1``: the same logits, the same state, the same tail."""
    from dllama_tpu.models import llama

    cfg, tokens = engine.cfg, _tokens(70, seed=5)
    fwd = jax.jit(lambda params, ids, start, col, n: llama.forward(params, cfg, ids, start, col, n))
    padded = np.zeros(128, np.int32)
    padded[:70] = tokens
    whole, col_a = fwd(engine.params, jnp.asarray([padded]), jnp.int32(0), _column(cfg), jnp.int32(70))
    first, col_b = fwd(engine.params, jnp.asarray([tokens[:64]]), jnp.int32(0), _column(cfg), jnp.int32(64))
    rows = [np.asarray(first[0])]
    for t in range(64, 70):
        one, col_b = fwd(engine.params, jnp.asarray([[tokens[t]]]), jnp.int32(t), col_b, jnp.int32(1))
        rows.append(np.asarray(one[0]))
    assert float(np.abs(np.asarray(whole[0, :70]) - np.concatenate(rows)).max()) < 2e-4
    np.testing.assert_allclose(np.asarray(col_a.s), np.asarray(col_b.s), atol=2e-5)
    np.testing.assert_allclose(np.asarray(col_a.conv), np.asarray(col_b.conv), atol=2e-5)
    assert np.asarray(col_a.stats)[0] == np.asarray(col_b.stats)[0]       # padding is not routed


def test_a_bfloat16_program_stays_within_its_stated_tolerance(bench, tmp_path):
    from dllama_tpu.models import llama

    eng = _engine(bench, tmp_path, dtype="bfloat16", seed=11)
    try:
        cfg, tokens = eng.cfg, _tokens(120, seed=12)
        logits, _ = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
            eng.params, jnp.asarray([tokens], jnp.int32), _column(cfg, dtype=jnp.bfloat16))
        apart = np.abs(np.asarray(logits[0]) - _reference_logits(bench, eng.params, tokens))
        assert LOGIT_TOL < float(apart.mean()) < BF16_MEAN_TOL, apart.mean()
        assert float(np.median(apart.max(axis=1))) < BF16_MEDIAN_TOL
    finally:
        eng.close()


# -- the share --------------------------------------------------------------------------


def test_four_shares_of_a_layer_add_up_to_the_uncut_references_whole_layer(bench):
    """THE SHARE TEST. One routed layer, 16 experts, 4 a token, cut into four
    shares of 4 experts: each share's ``routed_ffn`` (the router over all 16,
    its own experts' partial latent sum, ``W_lat_out`` applied to THAT, the
    shared expert every chip computes alike) less the shared expert counted
    once too often adds up to the uncut reference's whole layer."""
    import dataclasses

    import weights as dense_weights

    from dllama_tpu.formats.mfile import ArchType, HiddenAct, RopeType
    from dllama_tpu.models import share
    from dllama_tpu.models.config import ModelConfig
    from dllama_tpu.models.nemotron_h import NemotronHLayers

    d, lat, hid, wide, W, k = 64, 32, 32, 64, 16, 4
    cfg = ModelConfig(arch=ArchType.NEMOTRON_H, dim=d, hidden_dim=hid, n_layers=1, n_heads=4, n_kv_heads=2, head_dim=16,
                      vocab_size=256, seq_len=64, norm_epsilon=1e-5, rope_theta=1e4, rope_type=RopeType.LLAMA,
                      hidden_act=HiddenAct.RELU2, n_experts=W, n_active_experts=k, moe_router_width=W,
                      moe_score="sigmoid", moe_select_bias=True, moe_norm_eps=1e-20, moe_routed_scale=5.0,
                      moe_latent_dim=lat, shared_expert_dim=wide, layer_pattern=("E",))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 12))
    q = lambda pre, o, i: dense_weights.qw(next(keys), pre, o, i, scale_dtype=jnp.float32)
    full = NemotronHLayers(
        mixer=None, attn=None, norm_moe=jnp.ones((1, d)),
        moe_gate=jax.random.normal(next(keys), (1, W, d)) * 0.5, moe_bias=jax.random.normal(next(keys), (1, W)) * 0.05,
        w_lat_in=q((1,), lat, d), w_lat_out=q((1,), d, lat), we1=q((1, W), hid, lat), we2=q((1, W), lat, hid),
        ws1=q((1,), wide, d), ws2=q((1,), d, wide))
    h = jax.random.normal(next(keys), (1, 24, d), jnp.float32)
    live = jnp.ones(24, bool)
    with jax.default_matmul_precision("highest"):
        whole, stats = share.routed_ffn(cfg, h, full, jnp.int32(0), live)
        parts, held = [], 0
        for r in range(4):
            cut = dataclasses.replace(cfg, n_experts=4, moe_first_expert=4 * r)
            mine = full._replace(we1=jax.tree.map(lambda a: a[:, 4 * r:4 * r + 4], full.we1),
                                 we2=jax.tree.map(lambda a: a[:, 4 * r:4 * r + 4], full.we2))
            y, st = share.routed_ffn(cut, h, mine, jnp.int32(0), live)
            parts.append(np.asarray(y[0], np.float64))
            held += int(st[0])
            assert int(st[0] + st[1]) == 24 * k
        shared = np.asarray(share.swiglu(cfg, h, share._plane(full.ws1, 0), share._plane(full.ws2, 0), None)[0],
                            np.float64)
    assert held == 24 * k == int(stats[0])                                    # every pair on exactly one share
    summed = sum(parts) - 3 * shared                                          # the shared expert counted once
    model = {"num_experts_per_tok": k, "first_expert": 0, "n_routed_experts": W, "norm_topk_prob": True,
             "norm_topk_eps": 1e-20, "routed_scaling_factor": 5, "moe_latent_size": lat}
    ref = bench["reference"]
    tree = {n: jax.tree.map(lambda a: a[0], dense_reference._planes(getattr(full, n)))
            for n in ("moe_gate", "moe_bias", "w_lat_in", "w_lat_out", "we1", "we2", "ws1", "ws2")}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_block(model, h[0], tree, "none", False, 0.0))
    assert float(np.abs(summed - want).max()) < 1e-4 and float(np.abs(np.asarray(whole[0]) - want).max()) < 1e-4
    assert float(np.abs(parts[0] - want).max()) > 0.05                        # one share alone is not the layer


def test_a_chunk_past_the_grouped_kernels_budget_goes_through_it_in_pieces(bench, engine, monkeypatch):
    """22 of 128 held makes the fed layout's bound outgrow the kernel's VMEM
    budget at 128 rows; such a chunk goes through the kernel in halves that
    fit (a scan over one traced piece) and gives what the every-row form
    gives. Here: the budget lowered until 64 rows are refused and 32 pass."""
    from dllama_tpu.models import share
    from dllama_tpu.ops import expert_chunk as ec

    cfg, lp = engine.cfg, engine.params.layers
    rng = np.random.default_rng(64)
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.choice(16, 4, replace=False) for _ in range(64)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (64, 4)), jnp.float32)
    local, _stats = share.routed_pairs(cfg, idx, jnp.ones(64, bool))
    want, _every = share._experts_chunk_xla(cfg, x, local, weights, jnp.int32(2), lp)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    assert share._chunk_pieces(cfg, x, 64, 4, lp)[0] == 64
    budget = ec._VMEM_BUDGET
    while share._chunk_pieces(cfg, x, 64, 4, lp)[0] == 64:               # lower it until 64 rows are refused
        budget = budget * 7 // 8
        monkeypatch.setattr(ec, "_VMEM_BUDGET", budget)
    rows, kw = share._chunk_pieces(cfg, x, 64, 4, lp)
    assert rows == 32 and kw["interpret"]
    got, fed = jax.jit(lambda x, local, weights: share._experts_chunk(cfg, x, local, weights, jnp.int32(2), lp))(
        x, local, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    halves = [np.bincount(np.asarray(local[:128]), minlength=9)[:8], np.bincount(np.asarray(local[128:]), minlength=9)[:8]]
    assert int(fed) == sum(int((-(-c // 32)).sum()) * 32 for c in halves) and float(jnp.abs(want).max()) > 0.05
    monkeypatch.setattr(ec, "_VMEM_BUDGET", 1024)
    assert share._chunk_pieces(cfg, x, 64, 4, lp) is None          # nothing passes: the every-row form


# -- through the scheduler ----------------------------------------------------------------


def test_scheduler_serves_state_and_counters_in_one_step(bench, engine, tmp_path):
    """Through ``BatchScheduler``: interleaved requests finish and are the
    reference's tokens, the prefix is NOT reused (a state is a function of the
    whole prefix), the routing counters reach the registry, and while a
    profiler listens the steps' spans carry ``moe_planes`` and the new
    ``moe_plane_slots`` for ``moe_planes_fetched_share``."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    pairs, skipped = reg.counter(telemetry.MOE_PAIRS), reg.counter(telemetry.PREFIX_REUSE_SKIPPED)
    seen0 = pairs.total(where="held") + pairs.total(where="absent")
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
        tokens = sum(len(p) - 1 + 12 for p in prompts + [prompts[0]])         # prefilled + decoded positions
        assert pairs.total(where="held") + pairs.total(where="absent") - seen0 == tokens * 4 * 4
        kinds = reg.gauge(telemetry.LAYER_KINDS)
        assert [kinds.value(kind=k) for k in ("mamba", "attention", "moe", "full", "conv")] == [4, 2, 4, 0, 0]
        assert reg.gauge(telemetry.STATE_POOL_BYTES).value() == 4 * 4 * (4 * 32 * 16 * 4 + 3 * 192 * 4)
        want = _reference_logits(bench, engine.params, prompts[1] + list(reqs[1].tokens))
        assert [int(r.argmax()) for r in want[len(prompts[1]) - 1:-1]] == list(reqs[1].tokens)
        import program_spans        # benchmark/program_spans.py
        counters = _import("slice_counters", os.path.join(BENCH, "readers", "slice_counters.py"))
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            traced = sched.submit(_tokens(50, seed=50), 40, stop_on_eos=False)
            assert traced.done.wait(300) and not traced.error
        spans = program_spans.load(program_spans.newest_trace(trace_dir))
        children = [c for t in spans["ticks"] for c in t["children"]]
        steps = [st for name, _s, _e, st in children if name == "step_wait" and "moe_plane_slots" in st]
        assert len(steps) == 40 and all("moe_planes" in st and "kv_walk_blocks" in st for st in steps)
        slots = [int(st["moe_plane_slots"]) for st in steps]
        assert {b - a for a, b in zip(slots, slots[1:])} == {4 * 8}             # routed layers x held experts a step
        with open(os.path.join(BENCH, "layer_metrics", "moe_planes_fetched_share.json"), encoding="utf-8") as f:
            spec = json.load(f)
        ctx = {"trace": {}, "program_spans": spans}
        share_read = counters.read(ctx, **spec["args"])
        planes = [int(st["moe_planes"]) for st in steps]
        assert spec["reader"] == "slice_counters" and share_read == pytest.approx(
            100.0 * (planes[-1] - planes[0]) / (slots[-1] - slots[0]))
        assert 0.0 < share_read <= 100.0 * 4 / 8                                # one row takes 4 of 16, at most 4 held
        assert counters.read({"trace": {}, "program_spans": {"ticks": []}}, **spec["args"]) is None   # a parent's spans
    finally:
        sched.close()


# -- what is refused, the header, the converter --------------------------------------


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_block_size": 0}, "--kv-block-size"),
    ({"spec_lookup": 3}, "--spec-lookup"),
    ({"kv_host_blocks": 32}, "--kv-host-blocks"),
    ({"tp": 2}, "--tp > 1"),
    ({"sp": 2}, "--sp > 1"),
    ({"pp": 2}, "--pp > 1"),
    ({"dp": 2}, "--dp > 1"),
    ({"weight_mode": "offload"}, "--weight-mode offload"),
    ({"numerics_taps": True}, "--numerics-taps"),
    ({"sync_type": 3}, "q80"),
])
def test_refused_at_construction_with_the_flag_named(bench, tmp_path, kwargs, named):
    with pytest.raises(ValueError, match="one block a layer in a pattern") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_generator_refuses_what_has_no_construction_flag(engine):
    from dllama_tpu.runtime.serving import PagedGenerator

    gen = PagedGenerator(engine, n_slots=1)
    with pytest.raises(ValueError, match="recurrent state"):
        gen.export_prefix([1, 2, 3])
    with pytest.raises(RuntimeError, match="BatchScheduler"):
        engine.prefill([1, 2, 3])


def test_header_round_trip_and_walk(bench, tmp_path):
    from dllama_tpu.formats.mfile import (ArchType, HiddenAct, ModelFile, pattern_from_words, pattern_words)

    path = str(tmp_path / "walk.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path) as mf:
        h = mf.header
        assert (h.arch_type, h.hidden_act, h.layer_pattern, h.moe_latent_dim) == (
            ArchType.NEMOTRON_H, HiddenAct.RELU2, "EMEM*EMEM*", 32)
        assert (h.ssm_n_heads, h.ssm_head_dim, h.ssm_n_groups, h.ssm_state_dim, h.ssm_conv_kernel) == (4, 32, 2, 16, 4)
        assert (h.moe_router_width, h.moe_first_expert, h.n_experts, h.n_active_experts, h.shared_expert_dim) == (
            16, 4, 8, 4, 64)
        assert (h.pattern_layers("M"), h.pattern_layers("*")) == ([1, 3, 6, 8], [4, 9])
        assert mf.tensors["block_ssm_in.1"].shape == (320, 64) and mf.tensors["block_ssm_dt.1"].shape == (4, 64)
        assert mf.tensors["block_matmul_q.4"].shape == (64, 64) and "block_matmul_q.0" not in mf.tensors
        assert mf.tensors["block_expert_w1.0.7"].shape == (288, 32) and mf.tensors["block_expert_w2.0.7"].shape == (32, 288)
        assert mf.tensors["block_latent_in.0"].shape == (32, 64) and mf.tensors["block_shared_w2.0"].shape == (64, 64)
        assert mf.tensors["block_moe_bias.2"].shape == (16,) and "block_norm_1.0" not in mf.tensors
        last = max(mf.tensors.values(), key=lambda r: r.offset)
        assert last.offset + last.n_bytes == os.path.getsize(path)             # the walk ends where the file does
    assert len(pattern_words(PUBLISHED)) == 6 and pattern_from_words(pattern_words(PUBLISHED), 88) == PUBLISHED


def _synthetic_checkpoint(folder, cfg: dict, rng):
    """A checkpoint under ``model_type: nemotron_h``'s tensor names, with an
    ``mtp.*`` head the converter must skip."""
    from safetensors.numpy import save_file

    d, H = cfg["hidden_size"], cfg["mamba_num_heads"]
    d_ssm = H * cfg["mamba_head_dim"]
    conv = d_ssm + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    lat, hid, wide = cfg["moe_latent_size"], cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    q, kv = cfg["num_attention_heads"] * cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"]
    n = lambda *shape, scale=0.1: (rng.standard_normal(shape) * scale).astype(np.float32)
    t = {"backbone.embeddings.weight": n(cfg["vocab_size"], d, scale=1.0), "backbone.norm_f.weight": np.ones(d, np.float32),
         "lm_head.weight": n(cfg["vocab_size"], d), "mtp.layers.0.mixer.q_proj.weight": n(q, d),
         "mtp.layers.1.mixer.gate.weight": n(cfg["n_routed_experts"], d)}
    for l, kind in enumerate(cfg["hybrid_override_pattern"]):
        mx = f"backbone.layers.{l}.mixer."
        t[f"backbone.layers.{l}.norm.weight"] = np.ones(d, np.float32)
        if kind == "M":
            t.update({mx + "in_proj.weight": n(d_ssm + conv + H, d), mx + "conv1d.weight": n(conv, 1, cfg["conv_kernel"], scale=0.5),
                      mx + "conv1d.bias": n(conv), mx + "A_log": n(H, scale=1.0), mx + "D": np.ones(H, np.float32),
                      mx + "dt_bias": n(H, scale=1.0), mx + "norm.weight": np.ones(d_ssm, np.float32),
                      mx + "out_proj.weight": n(d, d_ssm)})
        elif kind == "*":
            t.update({mx + "q_proj.weight": n(q, d), mx + "k_proj.weight": n(kv, d), mx + "v_proj.weight": n(kv, d),
                      mx + "o_proj.weight": n(d, q)})
        else:
            t.update({mx + "gate.weight": n(cfg["n_routed_experts"], d, scale=0.5),
                      mx + "gate.e_score_correction_bias": n(cfg["n_routed_experts"], scale=0.01),
                      mx + "fc1_latent_proj.weight": n(lat, d), mx + "fc2_latent_proj.weight": n(d, lat),
                      mx + "shared_experts.up_proj.weight": n(wide, d), mx + "shared_experts.down_proj.weight": n(d, wide)})
            for e in range(cfg["n_routed_experts"]):
                t[mx + f"experts.{e}.up_proj.weight"] = n(hid, lat)
                t[mx + f"experts.{e}.down_proj.weight"] = n(lat, hid)
    save_file(t, str(folder / "model.safetensors"))
    return t


def test_the_converter_maps_a_synthetic_checkpoint_and_the_file_is_served(bench, tmp_path, capsys):
    """``convert/hf.py`` on a checkpoint under the family's tensor names:
    the ``mtp.*`` head skipped with a line, the mixer's ``in_proj`` split into
    the packed plane and the float32 ``dt`` rows, ``conv1d`` as taps; the file
    loads through the streaming loader (no seam), the experts' planes padded to
    the held width, and is served through ``BatchScheduler`` to the
    reference's tokens."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.convert import hf
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.ops.linear import dequantize_weight
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    with open(TINY, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    published.update(n_routed_experts=8, num_nextn_predict_layers=1, mtp_hybrid_override_pattern="*E")
    src = tmp_path / "hf"
    src.mkdir()
    (src / "config.json").write_text(json.dumps(published))
    tensors = _synthetic_checkpoint(src, published, np.random.default_rng(3))
    out = str(tmp_path / "converted.m")
    hf.convert_hf(src, "q40", out)
    printed = capsys.readouterr().out
    assert "skipping 2 mtp.* tensors" in printed and "mtp.layers" not in printed.split("skipping")[1]
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(out, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        cfg, lp = eng.cfg, eng.params.layers
        assert "".join(cfg.layer_pattern) == "EMEM*EMEM*" and (cfg.n_experts, cfg.moe_router_width, cfg.moe_first_expert) == (8, 8, 0)
        in_proj = tensors["backbone.layers.3.mixer.in_proj.weight"]
        np.testing.assert_array_equal(np.asarray(lp.mixer.w_dt[1]), in_proj[-4:])
        np.testing.assert_allclose(np.asarray(dequantize_weight(jax.tree.map(lambda a: a[1], lp.mixer.w_in))),
                                   in_proj[:-4].T, atol=0.05)
        np.testing.assert_array_equal(np.asarray(lp.mixer.conv_w[0]), tensors["backbone.layers.1.mixer.conv1d.weight"][:, 0, :].T)
        np.testing.assert_array_equal(np.asarray(lp.mixer.a_log[3]), tensors["backbone.layers.8.mixer.A_log"])
        np.testing.assert_array_equal(np.asarray(lp.moe_bias[2]), tensors["backbone.layers.5.mixer.gate.e_score_correction_bias"])
        up = np.asarray(dequantize_weight(jax.tree.map(lambda a: a[3, 5], lp.we1)))         # [latent, held width]
        np.testing.assert_allclose(up[:, :288], tensors["backbone.layers.7.mixer.experts.5.up_proj.weight"].T, atol=0.05)
        assert up.shape == (32, 512) and not up[:, 288:].any()
        down = np.asarray(dequantize_weight(jax.tree.map(lambda a: a[0, 2], lp.we2)))
        np.testing.assert_allclose(down[:288], tensors["backbone.layers.0.mixer.experts.2.down_proj.weight"].T, atol=0.05)
        assert down.shape == (512, 32) and not down[288:].any()
        np.testing.assert_allclose(np.asarray(dequantize_weight(jax.tree.map(lambda a: a[1], lp.attn.wo))),
                                   tensors["backbone.layers.9.mixer.o_proj.weight"].T, atol=0.05)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            req = sched.submit(prompt, 6, stop_on_eos=False)
            assert req.done.wait(300) and not req.error
            model = dict(bench["model"], n_routed_experts=8, router_width=8, first_expert=0)
            want = _reference_logits(bench, eng.params, prompt + list(req.tokens), model=model)
            assert [int(r.argmax()) for r in want[len(prompt) - 1:-1]] == list(req.tokens)
        finally:
            sched.close()
    finally:
        eng.close()
    (src / "config.json").write_text(json.dumps(dict(published, hybrid_override_pattern="EMEMX")))
    with pytest.raises(ValueError, match="characters over M"):
        hf.load_hf_config(src, 2)


def test_the_cell_configuration_is_the_issues_reckoning(bench):
    """Every published width unchanged (the catalog's row, copied here);
    ``reduced`` exactly what was cut; the floors hold; the counts module's
    bytes are the issue's."""
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {"hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                 "conv_kernel": 4, "chunk_size": 128, "expand": 2, "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "num_experts_per_tok": 22, "routed_scaling_factor": 5, "moe_intermediate_size": 2688,
                 "intermediate_size": 2688, "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
                 "n_shared_experts": 1, "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "rope_theta": 10000,
                 "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05, "max_position_embeddings": 262144,
                 "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "num_nextn_predict_layers": 1,
                 "mtp_hybrid_override_pattern": "*E", "tie_word_embeddings": False, "use_conv_bias": True}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["n_routed_experts", "vocab_size", "num_hidden_layers", "hybrid_override_pattern",
                               "max_position_embeddings"]
    assert (conf["n_routed_experts"], conf["vocab_size"], conf["num_hidden_layers"]) == (128, 32768, 22)
    assert conf["hybrid_override_pattern"] == PUBLISHED[26:48] == conf["reduced_from"]["hybrid_override_pattern"][26:48]
    assert conf["reduced_from"] == {"n_routed_experts": 512, "vocab_size": 131072, "num_hidden_layers": 88,
                                    "hybrid_override_pattern": PUBLISHED, "max_position_embeddings": 262144}
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["deployment"] and conf["memory"] and conf["not_served"]
    assert (conf["program"]["router_width"], conf["program"]["first_expert"]) == (512, 0)
    assert set(bench["weights"].ASSUMED) <= set(conf["program"]) and set(bench["weights"].ASSUMED) <= set(conf["assumed"])
    assert (conf["engine"]["slots"], conf["engine"]["max_seq_len"], conf["engine"]["kv_block_size"]) == (32, 2048, 16)
    # the floors: two whole periods, 128 experts (8 asked), a quarter of the vocabulary (an eighth asked)
    assert conf["hybrid_override_pattern"] == "EMEMEMEMEM*" * 2 and conf["n_routed_experts"] >= 8 and conf["vocab_size"] * 8 >= 131072
    model, c = bench_run.model_view(conf), bench["counts"]
    assert model["norm_epsilon"] == 1e-5 and bench["weights"].pattern(model) == conf["hybrid_override_pattern"]
    planes = (c.always_read_weights(model) + 10 * 128 * 2 * 1024 * 2688) * 1.0625
    assert 9.2e9 < planes < 9.4e9                                      # + 84 MB of router rows, 537 MB of embedding and head: 9.9 GB
    step = c.decode_step_bytes(model, rows=32, context_tokens=32 * 1000)
    assert 10.4e9 < step < 10.8e9 and 96 < c.experts_touched(model, 32) < 97.5 and c.pairs_held(model, 32) == 176
    one = c.kernel_counts(model, "expert_chunk", rows=32)
    assert abs(one["bytes"] - 5.85e6) < 0.01e6 and one["layers"] == 10 and one["calls_per_program"] == 20
    assert c.kernel_counts(model, "expert_gemv", rows=16)["pairs_per_layer"] == 88
    ssd = c.kernel_counts(model, "ssd_step", rows=32)
    assert ssd["calls_per_program"] == 10 and 2.6e9 < ssd["bytes"] * 10 < 2.8e9
    walk = c.kernel_counts(model, "paged_ragged_attention", rows=32)
    assert (walk["bytes"], walk["layers"], walk["flops"]) == (1024.0, 2, 4.0 * 4096)
    assert c.kernel_counts(model, "gated_delta_step", rows=32) is None
    with open(os.path.join(BENCH, "traffic", "reasoning-nemotron-3-super.json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sizes_seed"], mix["engine"]) == ("closed", 32, 5101, {"slots": 32, "max_seq_len": 2048})
    assert [(m["prompt_tokens"], m["output_tokens"]) for m in mix["mix"]] == [
        ({"dist": "uniform", "low": 64, "high": 256}, {"dist": "uniform", "low": 768, "high": 1536})]
    assert mix["sampling"]["temperature"] == 0.0 and "sessions" not in mix and "shared_prefix" not in mix and "rate_per_s" not in mix
    import traffic
    plans = [traffic.plan(mix, seed=seed, seconds=45.0, vocab_size=model["vocab_size"]) for seed in (3, 2 ** 31 + 5)]
    sizes = [[(len(r.new_tokens), r.max_tokens) for r in p.requests] for p in plans]
    assert sizes[0] == sizes[1] and plans[0].max_context <= 1792 <= conf["engine"]["max_seq_len"]     # the seed moves no size
    assert all(64 <= n <= 256 and 768 <= m <= 1536 for n, m in sizes[0]) and plans[0].clients == 32
    assert 30000 < max(t for r in plans[1].requests for t in r.new_tokens) < 32768                    # ids from the held slice
