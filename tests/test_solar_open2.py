"""``ArchType.SOLAR_OPEN2`` (``models/solar_open2.py``: Solar-Open2-250B's layer
equation, a delta rule whose decay is a VECTOR a head in three layers of four,
a gated full layer without positions in the fourth, which stands FIRST in its
period, and behind every mixer a sigmoid router over experts of which a share
is held, beside a shared one) at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/solar_open2/reference.py``: the
per-token recurrence): the rule's three forms with a decay a key channel,
whole-forward logits, padded chunked prefill then paged decode, every control
another function, the shares of a layer adding up to the whole, the header,
the converter, the scheduler, the refusals and the cell's configuration."""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import delta_chunk_form

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SO = os.path.join(BENCH, "solar_open2")
TINY = os.path.join(SO, "selftest", "configs", "tiny-solar-open2.json")
REAL = os.path.join(BENCH, "configs", "solar-open2-250b.json")
# a float32 program against the float32 reference, in units of the logits' spread. The program's prefill is the CHUNK
# form of the rule (a triangular solve and matmuls at full float32 precision), the reference the per-token recurrence:
# they differ by rounding alone, 1e-5 of a spread at 8 layers; the nearest precision below the stated one (``state16``)
# moves the logits by 1e-2
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
    return {"weights": _import("solar_open2_weights", os.path.join(SO, "weights.py")),
            "reference": _import("solar_open2_reference", os.path.join(SO, "reference.py")),
            "counts": _import("solar_open2_counts", os.path.join(SO, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-solar-open2.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("solar_open2"))
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


def _forward(engine, tokens, start=0, col=None, n_valid=None):
    from dllama_tpu.models import llama

    cfg = engine.cfg
    n_valid = len(tokens) if n_valid is None else n_valid
    return jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(start), col, jnp.int32(n_valid)))(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg) if col is None else col)


# -- the rule with a decay a key channel ---------------------------------------------------


def _rule_inputs(rng, B, T, H, dk, dv, *, strong=False):
    from dllama_tpu.ops import gated_delta as gd

    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = gd.l2norm(f(B, T, H, dk)) * dk ** -0.5
    k = gd.l2norm(f(B, T, H, dk))
    v = f(B, T, H, dv)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (B, T, H)), jnp.float32)
    g = -jnp.asarray(rng.uniform(0.001, 0.7, (B, T, H, dk)), jnp.float32)      # decays of 0.999 to 0.5 a token
    if strong:
        g = g.at[..., ::3].set(-20.0)                                          # every third channel: e^-20 a token
    return q, k, v, g, beta, f(B, H, dk, dv)


# the kernel at every bucket's sub-chunk count, with the overflow case over one sub-chunk and over three, and at solar's
# head shape cut in H only (dk = dv = 128: whole lane tiles)
@pytest.mark.parametrize("T,strong,form,heads", [
    (16, False, "xla", (3, 16, 8)), (64, False, "xla", (3, 16, 8)), (192, False, "xla", (3, 16, 8)),
    (64, True, "xla", (3, 16, 8)), (192, True, "xla", (3, 16, 8)),
    (32, False, "kernel", (3, 16, 8)), (64, False, "kernel", (3, 16, 8)), (128, False, "kernel", (3, 16, 8)),
    (256, False, "kernel", (3, 16, 8)), (64, True, "kernel", (3, 16, 8)), (192, True, "kernel", (3, 16, 8)),
    (128, False, "kernel", (2, 128, 128)), (64, True, "kernel", (2, 128, 128))])
def test_the_chunk_form_with_a_vector_decay_is_the_recurrence(T, strong, form, heads):
    """``gated_delta_chunk`` (the XLA twin and the kernel) with ``g [B, T, H,
    dk]`` against the per-token scan, a state of noise in and the state out;
    with channels that decay by e^-20 a token (e^-1280 over a sub-chunk, where
    ``(k * G) . (k / G)`` would overflow float32 by the fifth token) everything
    stays finite and equal."""
    from dllama_tpu.ops import gated_delta as gd

    q, k, v, g, beta, S0 = _rule_inputs(np.random.default_rng(T), 2, T, *heads, strong=strong)
    o_ref, S_ref = gd.gated_delta_recurrent(q, k, v, g, beta, S0)
    o, S = delta_chunk_form(form)(q, k, v, g, beta, S0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=5e-6)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=2e-5)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_no_exponent_of_the_vector_chunk_form_is_positive(form):
    """Every ``exp`` the vector case traces takes an argument <= 0, whatever
    the decay: read off the ``exp`` operands on a worst case (e^-60 a token in
    every channel: e^-3840 over a sub-chunk), in the XLA twin and in the
    kernel's body (``_chunk_head``, one head's sub-chunk as plain values)."""
    from dllama_tpu.ops import gated_delta as gd

    q, k, v, g, beta, S0 = _rule_inputs(np.random.default_rng(0), 1, 64, 2, 16, 8)
    g = jnp.full_like(g, -60.0)
    seen = []
    real = jnp.exp
    try:
        gd.jnp.exp = lambda a: seen.append(float(jnp.max(a))) or real(a)
        with jax.disable_jit():              # the scan over sub-chunks as a Python loop: its exps are read too
            if form == "xla":
                o, S = gd.gated_delta_chunk_xla(q, k, v, g, beta, S0)
            else:
                stages = gd._chunk_head(q[0, :, 0], k[0, :, 0], v[0, :, 0], g[0, :, 0], beta[0, None, :, 0], S0[0, 0],
                                        per_channel=True)
                try:
                    while True:
                        next(stages)
                except StopIteration as done:
                    o, S = done.value
    finally:
        gd.jnp.exp = real
    assert len(seen) >= 5 and max(seen) <= 0.0, seen
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    if form == "kernel":
        o_ref, S_ref = gd.gated_delta_recurrent(q, k, v, g, beta, S0)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref[0, :, 0]), atol=5e-6)
        np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref[0, 0]), atol=2e-5)


@pytest.mark.parametrize("strong", [False, True])
def test_the_step_forms_with_a_vector_decay_are_the_recurrence(strong):
    """The XLA step and the Pallas kernel (interpret mode) over a pool in
    place, ``alpha [B, H, dk]``: four tokens of two rows against the scan; a row
    no step names is not touched."""
    from dllama_tpu.ops import gated_delta as gd

    B, H, dk, dv = 2, 4, 16, 8
    q, k, v, g, beta, S0 = _rule_inputs(np.random.default_rng(5), B, 4, H, dk, dv, strong=strong)
    o_ref, S_ref = gd.gated_delta_recurrent(q, k, v, g, beta, S0)
    pool0 = jnp.zeros((2, 4, H, dk, dv), jnp.float32).at[1, 1:3].set(S0).at[1, 3].set(7.0)
    rows, layer = jnp.asarray([1, 2], jnp.int32), jnp.int32(1)
    for step in (gd.gated_delta_step_xla, lambda *a: gd.gated_delta_step(*a, interpret=True)):
        pool = pool0
        for t in range(4):
            o, pool = step(pool, layer, rows, q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t])
            np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref[:, t]), atol=2e-6)
        np.testing.assert_allclose(np.asarray(pool[1, 1:3]), np.asarray(S_ref), atol=2e-6)
        assert float(jnp.abs(pool[1, 3] - 7.0).max()) == 0.0 and float(jnp.abs(pool[0]).max()) == 0.0


def test_a_scalar_decay_is_the_vector_whose_channels_agree_and_keeps_its_operands():
    """One rule: ``g [.., H]`` gives what ``g [.., H, dk]`` with every channel
    equal gives, in all three forms; and the step kernel's operands for a
    ``[B, H]`` decay are today's (q and k as TWO columns, the decay a row of
    ``vab``): nothing of it is broadcast to ``dk`` in memory. A ``[B, H, dk]``
    decay rides with q and k as ROWS of one ``[B, H, 8, dk]`` operand."""
    from dllama_tpu.ops import gated_delta as gd

    B, T, H, dk, dv = 1, 64, 2, 16, 8
    q, k, v, g, beta, S0 = _rule_inputs(np.random.default_rng(9), B, T, H, dk, dv)
    gs = g[..., 0]
    wide = jnp.broadcast_to(gs[..., None], g.shape)
    for rule in (gd.gated_delta_recurrent, gd.gated_delta_chunk_xla, delta_chunk_form("kernel")):
        (o1, S1), (o2, S2) = rule(q, k, v, gs, beta, S0), rule(q, k, v, wide, beta, S0)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-6)
        np.testing.assert_allclose(np.asarray(S1), np.asarray(S2), atol=5e-6)
    pool = jnp.zeros((1, 2, H, dk, dv), jnp.float32)
    args = (pool, jnp.int32(0), jnp.asarray([1], jnp.int32), q[:, 0], k[:, 0], v[:, 0])
    text = lambda alpha: jax.jit(lambda *a: gd.gated_delta_step(*a, interpret=True)).lower(
        *args, alpha, beta[:, 0]).as_text()
    assert f"tensor<1x{H}x{dk}x2xf32>" in text(jnp.exp(gs[:, 0])) and f"tensor<1x{H}x8x{dk}xf32>" not in text(jnp.exp(gs[:, 0]))
    assert f"tensor<1x{H}x8x{dk}xf32>" in text(jnp.exp(g[:, 0])) and f"x{dk}x2xf32>" not in text(jnp.exp(g[:, 0]))


# -- the configuration as the program sees it ----------------------------------------


def test_the_stacks_the_pools_and_the_share_are_the_architectures(engine):
    """Two periods of (full, kda, kda, kda): 6 delta-rule and 2 full layers and
    8 routed halves; K/V of the full layers alone, a state ``[H, dk, dv]`` and
    a tail of q~ k~ v~ side by side for the others; 4 of 8 experts held from 2."""
    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.models import hybrid, solar_open2
    from dllama_tpu.models.family import family_of
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    cfg = engine.cfg
    assert cfg.arch == ArchType.SOLAR_OPEN2 and family_of(cfg) is solar_open2.FAMILY
    assert (cfg.n_layers, cfg.layer_period, cfg.full_layer_at, cfg.n_periods) == (8, 4, 0, 2)
    assert (cfg.n_linear_layers, cfg.n_state_layers, cfg.n_kv_layers, cfg.n_moe_layers, cfg.n_dense_layers) == (6, 6, 2, 8, 0)
    assert cfg.is_hybrid and cfg.has_state and cfg.has_expert_share and cfg.paged_only and not cfg.has_ssm
    assert (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim, cfg.lin_decay_dim, cfg.lin_gate_rank) == (4, 16, 16, 16, 16)
    assert cfg.lin_neg_eigval and cfg.lin_conv_kernel == 4
    assert cfg.state_shape(5) == (6, 5, 4, 16, 16) and cfg.conv_shape(5) == (6, 5, 3, 3 * 4 * 16)
    assert (cfg.n_experts, cfg.moe_router_width, cfg.moe_first_expert, cfg.n_active_experts) == (4, 8, 2, 3)
    assert (cfg.hidden_dim, cfg.shared_expert_dim, cfg.moe_score, cfg.moe_select_bias, cfg.moe_norm_topk) == (
        32, 32, "sigmoid", True, True)
    fam = family_of(cfg)
    assert fam.tick is None and fam.layer_kinds(cfg)["linear"] == 6 and fam.layer_kinds(cfg)["full"] == 2
    assert fam.layer_kinds(cfg)["moe"] == 8 and "a decay a key channel (16 a head)" in fam.describe(cfg, None)
    kda, full, moe = engine.params.layers
    assert kda.wq.codes.shape == (6, 64, 64) and kda.w_f_up.shape == (6, 64, 16) and kda.dt_bias.shape == (6, 64)
    assert kda.conv_w.shape == (6, 4, 192) and kda.a_log.shape == (6, 4) and kda.w_b.shape == (6, 4, 64)
    assert full.wg.codes.shape == (2, 64, 64) and moe.we1.codes.shape == (8, 4, 64, 32) and moe.moe_bias.shape == (8, 8)
    assert StatePool.create(cfg, 4, jnp.float32).s.shape == (6, 5, 4, 16, 16)
    assert PagedKVCache.create(cfg, 9, 16).k.shape == (2, 9, 2, 16, 16)
    # ONE period scan: this family's programs are the hybrid's over its own walk
    walk = solar_open2._walk(engine.params, cfg)
    assert isinstance(walk, hybrid.Walk) and walk.full_at == 0 and hybrid._olmo_walk.__name__ == "_olmo_walk"


def test_the_decays_of_one_head_really_differ(engine):
    """What ``scalardecay`` needs to show anything: at a zero gate input the
    channels of ONE head decay from about 0.999 to 0.5 a token."""
    kda = engine.params.layers.kda
    H, dk = engine.cfg.lin_heads, engine.cfg.lin_key_dim
    alpha = np.exp(-np.exp(np.asarray(kda.a_log))[:, :, None]
                   * np.logaddexp(0.0, np.asarray(kda.dt_bias).reshape(-1, H, dk)))
    assert alpha.min() < 0.6 and alpha.max() > 0.998
    assert (alpha.max(axis=-1) - alpha.min(axis=-1)).min() > 0.1        # within EVERY head of every layer (16 channels a head here)


# -- the program against the reference ---------------------------------------------------


@pytest.mark.parametrize("T", [20, 70, 300])
def test_whole_forward_logits(bench, engine, T):
    tokens = _tokens(T, seed=T)
    logits, col = _forward(engine, tokens)
    want, spread = _spread(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL * spread
    stats = np.asarray(col.stats)          # every pair counted once, held or absent: 3 a token in 8 layers
    assert stats[0] + stats[1] == T * 3 * 8 and stats[0] > 0 and stats[1] > 0 and stats[4:].sum() == stats[0]
    # the column's state and tail carry on: a chunk behind them agrees too
    more = _tokens(9, seed=T + 1)
    logits2, _ = _forward(engine, more, start=T, col=col)
    want2 = bench["reference"].reference_logits(bench["model"], engine.params, tokens + more)[T:]
    assert float(np.abs(np.asarray(logits2[0]) - want2).max()) < LOGIT_TOL * spread


def test_padding_leaves_the_state_the_tail_and_the_counters_alone(engine):
    """``n_valid`` masks a chunk's padding: ``beta = 0`` and ``g = 0`` in every
    channel, nothing into the tail, not routed."""
    tokens = _tokens(40, seed=3)
    _, exact = _forward(engine, tokens)
    _, padded = _forward(engine, tokens + [5] * 24, n_valid=40)
    np.testing.assert_allclose(np.asarray(padded.s), np.asarray(exact.s), atol=5e-5)   # sub-chunks of 8 against 64: rounding
    np.testing.assert_allclose(np.asarray(padded.conv), np.asarray(exact.conv), atol=5e-5)     # a later layer's inputs
    np.testing.assert_array_equal(np.asarray(padded.stats[:2]), np.asarray(exact.stats[:2]))


# each control moves the reference's own logits by more than the float32 tolerance, in units of their spread: a
# program that computed it would fail ``test_whole_forward_logits``. ``state16``, the nearest precision below the
# stated one, moves them least
@pytest.mark.parametrize("variant,least", [
    ("scalardecay", 0.1), ("nonegeig", 0.1), ("nogate", 0.1), ("misroute", 0.1), ("noshared", 0.5), ("state16", 5e-3),
    ("bf16router", 0.05)])
def test_the_references_variants_are_another_function(bench, engine, variant, least):
    tokens = _tokens(300, seed=70)
    honest, spread = _spread(bench, engine.params, tokens)
    moved = float(np.abs(_spread(bench, engine.params, tokens, variant=variant)[0] - honest).max()) / spread
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


# prompt lengths on and around the edges (a block's edge, a bucket's, a padded tail, exactly the widest chunk and one
# past it). kernel "fused": the steps' attention through paged_ragged_attention, the routed feed-forward through
# expert_gemv and the rule through gated_delta_step with its decays a channel, all in interpret mode, a dead slot with a
# stale depth beside
@pytest.mark.parametrize("n_prompt,kernel", [(17, None), (33, None), (70, None), (257, None), (258, None), (300, None),
                                             (70, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import gated_delta as gd
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"rule": []}
    step = gd.gated_delta_step
    monkeypatch.setattr(gd, "gated_delta_step",
                        lambda *a, **kw: calls["rule"].append(a[6].shape) or step(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 20
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # ONE traced delta-rule layer body (a period's loop), its decay [rows, H, dk]
    assert calls["rule"] == ([(2, 4, 16)] if kernel else [])
    want, spread = _spread(bench, engine.params, prompt + emitted)
    want = want[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL * spread
    totals = np.asarray(gen.moe_stats)
    assert totals[0, 0] + totals[0, 1] == n_steps * 3 * 8 and totals[1, 0] + totals[1, 1] == (n_prompt - 1) * 3 * 8
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)


def test_the_shares_of_a_layer_add_up_to_the_whole(bench, engine):
    """The guide's share test: one routed layer's output as the two shares of
    four experts give it (experts 0-3 and 4-7 of the router's 8; the shared
    expert, which every chip computes alike, counted ONCE) is the uncut
    layer's, in the program (``share.routed_ffn`` over planes cut from ONE
    stack of eight) and in the reference."""
    from dllama_tpu.models import share
    from dllama_tpu.ops.linear import QuantizedWeight

    whole_cfg = dataclasses.replace(engine.cfg, n_experts=8, moe_first_expert=0)
    rng = np.random.default_rng(8)
    moe = engine.params.layers.moe
    key = jax.random.PRNGKey(3)
    from weights import qw           # benchmark/weights.py
    stacks = {n: qw(jax.random.fold_in(key, i), (8, 8), o, i_, scale_dtype=jnp.float32)
              for i, (n, o, i_) in enumerate((("we1", 32, 64), ("we2", 64, 32), ("we3", 32, 64)))}
    cut = lambda w, lo: QuantizedWeight(scales=w.scales[:, lo:lo + 4], codes=w.codes[:, lo:lo + 4])
    h = jnp.asarray(rng.standard_normal((1, 24, 64)), jnp.float32)
    live = jnp.ones(24, bool)
    m = jnp.int32(1)

    def layer(cfg, first, shared):
        lp = moe._replace(**{n: (w if first is None else cut(w, first)) for n, w in stacks.items()})
        if not shared:
            lp = lp._replace(ws1=None, ws2=None, ws3=None)
        with jax.default_matmul_precision("highest"):
            return share.routed_ffn(cfg, h, lp, m, live)

    whole, stats = layer(whole_cfg, None, True)
    assert int(stats[0]) == 24 * 3 and int(stats[1]) == 0
    parts = [layer(dataclasses.replace(engine.cfg, moe_first_expert=lo), lo, lo == 0) for lo in (0, 4)]
    assert sum(int(s[0]) for _y, s in parts) == 24 * 3 and all(int(s[1]) > 0 for _y, s in parts)
    np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]), np.asarray(whole), atol=2e-5)
    # the reference, given the same shares of the same stack
    ref = bench["reference"]
    tree = lambda lp: {n: dense_reference._planes(getattr(lp, n)) for n in ref.MOE_LEAVES}
    x = h[0]

    def ref_layer(first, held, variant):
        lp = moe._replace(**{n: (w if first is None else cut(w, first)) for n, w in stacks.items()})
        model = dict(bench["model"], n_routed_experts=held, first_expert=first or 0)
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref.routed_block(model, x, tree(lp), 1, variant, False, 0.0))

    want = ref_layer(None, 8, "none")
    got = ref_layer(0, 4, "none") + ref_layer(4, 4, "noshared")
    np.testing.assert_allclose(got, want, atol=2e-5)
    eps = engine.cfg.norm_epsilon
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)    # norm_ffn is ones
    with jax.default_matmul_precision("highest"):
        prog = share.routed_ffn(whole_cfg, normed[None], moe._replace(**stacks), m, live)[0][0]
    np.testing.assert_allclose(np.asarray(prog), want, atol=5e-5)


def test_scheduler_serves_state_and_counters(bench, engine):
    """Through ``BatchScheduler``: interleaved requests finish and are the
    reference's tokens, the prefix is NOT reused, the layer kinds read right."""
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
        assert [kinds.value(kind=k) for k in ("linear", "full", "moe", "mamba", "conv")] == [6, 2, 8, 0, 0]
        want = bench["reference"].reference_logits(bench["model"], engine.params, prompts[1] + list(reqs[1].tokens))
        assert [int(r.argmax()) for r in want[len(prompts[1]) - 1:-1]] == list(reqs[1].tokens)
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
    with pytest.raises(ValueError, match="a decay a key channel beside gated full attention") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_header_round_trip_and_walk(bench, tmp_path):
    from dllama_tpu.formats.mfile import ArchType, ModelFile, parse_header, write_header

    path = str(tmp_path / "walk.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path) as mf:
        h = mf.header
        assert (h.arch_type, h.n_layers, h.layer_period, h.full_layer_at) == (ArchType.SOLAR_OPEN2, 8, 4, 0)
        assert (h.linear_n_key_heads, h.linear_n_value_heads, h.linear_key_head_dim, h.linear_value_head_dim) == (4, 4, 16, 16)
        assert (h.linear_decay_dim, h.linear_gate_rank, h.linear_conv_kernel, h.linear_neg_eigval) == (16, 16, 4, 1)
        assert (h.moe_router_width, h.moe_first_expert, h.n_experts, h.n_active_experts, h.shared_expert_dim) == (
            8, 2, 4, 3, 32)
        assert (h.moe_score_func, h.moe_select_bias, h.moe_norm_topk, h.moe_routed_scale_milli) == (1, 1, 1, 1000)
        assert mf.tensors["block_matmul_wg.0"].shape == (64, 64) and "block_matmul_wg.1" not in mf.tensors
        assert mf.tensors["block_kda_q.1"].shape == (64, 64) and "block_kda_q.4" not in mf.tensors
        assert mf.tensors["block_kda_conv_v.3"].shape == (4, 64) and mf.tensors["block_kda_f_up.5"].shape == (64, 16)
        assert mf.tensors["block_kda_dt_bias.7"].shape == (64,) and mf.tensors["block_kda_a_log.7"].shape == (4,)
        assert mf.tensors["block_moe_gate.0"].shape == (8, 64) and mf.tensors["block_moe_bias.6"].shape == (8,)
        assert mf.tensors["block_expert_w2.4.3"].shape == (64, 32) and "block_expert_w2.4.4" not in mf.tensors
        assert mf.tensors["block_shared_w3.2"].shape == (32, 64)
        last = max(mf.tensors.values(), key=lambda r: r.offset)
        assert last.offset + last.n_bytes == os.path.getsize(path)             # the walk ends where the file does
    import io
    fields = {"version": 1, "arch_type": int(ArchType.SOLAR_OPEN2), "dim": 64, "hidden_dim": 32, "n_layers": 4,
              "n_heads": 4, "n_kv_heads": 2, "n_experts": 4, "n_active_experts": 3, "vocab_size": 256, "seq_len": 64,
              "weight_float_type": 2, "head_dim": 16, "norm_epsilon": 5, "layer_period": 4, "linear_n_key_heads": 4,
              "linear_n_value_heads": 4, "linear_key_head_dim": 16, "linear_value_head_dim": 16,
              "linear_conv_kernel": 4, "linear_decay_dim": 16, "linear_gate_rank": 16, "full_layer_at": 0,
              "moe_router_width": 8, "moe_first_expert": 4, "moe_score_func": 1, "moe_select_bias": 1}
    buf = io.BytesIO()
    write_header(buf, fields)
    h = parse_header(buf.getvalue(), 0)
    assert (h.linear_decay_dim, h.linear_gate_rank, h.full_layer_at, h.moe_first_expert) == (16, 16, 0, 4)
    # the decays a head and the full layer's place are STATED, not chosen: one number a head is OLMO_HYBRID's rule
    for bad, named in (({"linear_decay_dim": 8}, "one number a key channel"), ({"linear_decay_dim": 1}, "1 decays a head"),
                       ({"linear_decay_dim": 0}, "0 decays a head"), ({"full_layer_at": 4}, "whole periods"),
                       ({"full_layer_at": 3}, "led by its full layer"), ({"moe_first_expert": 6}, "held of a router over 8")):
        buf = io.BytesIO()
        write_header(buf, dict(fields, **bad))
        with pytest.raises(ValueError, match="solar_open2 model: .*" + named):
            parse_header(buf.getvalue(), 0)


def _synthetic_checkpoint(folder, cfg: dict, rng):
    """A checkpoint under ``model_type: solar_open2``'s tensor names as
    ``convert/hf.py`` takes them."""
    from safetensors.numpy import save_file

    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, ld, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    wide, E = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    n = lambda *shape, scale=0.1: (rng.standard_normal(shape) * scale).astype(np.float32)
    t = {"model.embed_tokens.weight": n(cfg["vocab_size"], d, scale=1.0), "model.norm.weight": np.ones(d, np.float32),
         "lm_head.weight": n(cfg["vocab_size"], d)}
    for l in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{l}."
        sa = pre + "self_attn."
        t[pre + "input_layernorm.weight"] = np.ones(d, np.float32)
        t[pre + "post_attention_layernorm.weight"] = np.ones(d, np.float32)
        if l in cfg["gqa_layers"]:
            t.update({sa + "q_proj.weight": n(q, d, scale=0.3), sa + "k_proj.weight": n(kv, d), sa + "v_proj.weight": n(kv, d),
                      sa + "o_proj.weight": n(d, q), sa + "g_proj.weight": n(q, d)})
        else:
            t.update({sa + f"{p}_proj.weight": n(H * ld, d) for p in "qkv"})
            t.update({sa + f"{p}_conv1d.weight": n(H * ld, 1, K, scale=0.5) for p in "qkv"})
            t.update({sa + "A_log": n(1, 1, H, 1, scale=0.3), sa + "f_a_proj.weight": n(ld, d),
                      sa + "f_b_proj.weight": n(H * ld, ld), sa + "dt_bias": n(H * ld, scale=1.0) - 2.0,
                      sa + "b_proj.weight": n(H, d), sa + "g_a_proj.weight": n(ld, d),
                      sa + "g_b_proj.weight": n(H * ld, ld), sa + "o_norm.weight": np.ones(ld, np.float32),
                      sa + "o_proj.weight": n(d, H * ld)})
        t.update({pre + "mlp.gate.weight": n(E, d, scale=0.5),
                  pre + "mlp.gate.e_score_correction_bias": n(E, scale=0.01)})
        for e in range(E):
            t.update({pre + f"mlp.experts.{e}.gate_proj.weight": n(wide, d), pre + f"mlp.experts.{e}.up_proj.weight": n(wide, d),
                      pre + f"mlp.experts.{e}.down_proj.weight": n(d, wide)})
        t.update({pre + "mlp.shared_experts.gate_proj.weight": n(wide, d), pre + "mlp.shared_experts.up_proj.weight": n(wide, d),
                  pre + "mlp.shared_experts.down_proj.weight": n(d, wide)})
    save_file(t, str(folder / "model.safetensors"))
    return t


def test_the_converter_maps_a_synthetic_checkpoint_and_the_cli_serves_the_file(bench, tmp_path, capsys):
    """``convert/hf.py`` on a checkpoint under the names it assumes (no
    published checkpoint was at hand): three conv weights ``[C, 1, K]`` as taps
    ``[K, C]`` side by side, ``A_log`` flattened, the experts in the walk's order;
    the file read by the STREAMING loader (no seam), served through
    ``BatchScheduler`` to the reference's tokens and through ``python -m
    dllama_tpu inference`` (the paged generator) without error."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.convert import hf
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.ops.linear import dequantize_weight
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    with open(TINY, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    published["n_routed_experts"] = 8                       # a whole checkpoint holds every expert
    src = tmp_path / "hf"
    src.mkdir()
    (src / "config.json").write_text(json.dumps(published))
    tensors = _synthetic_checkpoint(src, published, np.random.default_rng(3))
    out = str(tmp_path / "converted.m")
    hf.convert_hf(src, "q40", out)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(out, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        cfg = eng.cfg
        kda, full, moe = eng.params.layers
        assert (cfg.n_experts, cfg.moe_router_width, cfg.moe_first_expert, cfg.lin_decay_dim) == (8, 8, 0, 16)
        plane = lambda stack, *at: np.asarray(dequantize_weight(jax.tree.map(lambda a: a[at], stack)))
        sa = "model.layers.2.self_attn."
        taps = np.concatenate([tensors[sa + f"{p}_conv1d.weight"][:, 0, :].T for p in "qkv"], axis=1)
        np.testing.assert_array_equal(np.asarray(kda.conv_w[1]), taps)
        np.testing.assert_array_equal(np.asarray(kda.a_log[1]), tensors[sa + "A_log"].reshape(-1))
        np.testing.assert_array_equal(np.asarray(kda.w_f_up[1]), tensors[sa + "f_b_proj.weight"])
        np.testing.assert_array_equal(np.asarray(kda.w_g_down[1]), tensors[sa + "g_a_proj.weight"])
        np.testing.assert_allclose(plane(kda.wv, 1), tensors[sa + "v_proj.weight"].T, atol=0.05)
        np.testing.assert_allclose(plane(full.wg, 1), tensors["model.layers.4.self_attn.g_proj.weight"].T, atol=0.05)
        np.testing.assert_allclose(plane(moe.we1, 5, 6), tensors["model.layers.5.mlp.experts.6.gate_proj.weight"].T, atol=0.05)
        np.testing.assert_allclose(plane(moe.we3, 5, 6), tensors["model.layers.5.mlp.experts.6.up_proj.weight"].T, atol=0.05)
        np.testing.assert_allclose(plane(moe.ws2, 3), tensors["model.layers.3.mlp.shared_experts.down_proj.weight"].T, atol=0.05)
        np.testing.assert_array_equal(np.asarray(moe.moe_bias[7]), tensors["model.layers.7.mlp.gate.e_score_correction_bias"])
        model = dict(bench["model"], n_routed_experts=8, first_expert=0)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            req = sched.submit(prompt, 6, stop_on_eos=False)
            assert req.done.wait(300) and not req.error
            want = bench["reference"].reference_logits(model, eng.params, prompt + list(req.tokens))
            assert [int(r.argmax()) for r in want[len(prompt) - 1:-1]] == list(req.tokens)
        finally:
            sched.close()
    finally:
        eng.close()
    (src / "config.json").write_text(json.dumps(dict(published, gqa_layers=[3, 7])))
    with pytest.raises(ValueError, match="the first of every gqa_interval"):
        hf.load_hf_config(src, 2)


def test_the_cell_configuration_is_the_issues_reckoning(bench):
    """Every published key at its published value but the cut ones (the
    catalog's row, copied here); ``reduced`` exactly what was cut; the floors
    hold; the counts module's bytes are the issue's."""
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {"model_type": "solar_open2", "partial_rotary_factor": 1,
                 "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
                 "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
                 "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "tie_word_embeddings": False, "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
                 "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True, "kda_use_full_proj": False,
                 "kda_allow_neg_eigval": True, "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
                 "num_experts_per_tok": 8}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["n_routed_experts", "vocab_size", "num_hidden_layers", "gqa_layers",
                               "max_position_embeddings"]
    assert (conf["n_routed_experts"], conf["vocab_size"], conf["num_hidden_layers"], conf["gqa_layers"]) == (
        40, 24576, 8, [0, 4])
    assert conf["reduced_from"] == {"n_routed_experts": 320, "vocab_size": 196608, "num_hidden_layers": 48,
                                    "gqa_layers": list(range(0, 48, 4)), "max_position_embeddings": 1048576}
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["deployment"] and conf["memory"]
    assert set(bench["weights"].ASSUMED) <= set(conf["program"]) and set(bench["weights"].ASSUMED) <= set(conf["assumed"])
    assert (conf["program"]["router_width"], conf["program"]["first_expert"]) == (320, 0)
    # the floors: whole periods and at least four layers, at least 8 experts, at least an eighth of the vocabulary
    assert conf["num_hidden_layers"] % 4 == 0 and conf["n_routed_experts"] >= 8 and conf["vocab_size"] * 8 >= 196608
    assert (conf["engine"]["slots"], conf["engine"]["max_seq_len"], conf["engine"]["kv_block_size"]) == (16, 9472, 16)
    model, c = bench_run.model_view(conf), bench["counts"]
    assert model["norm_epsilon"] == 1e-5 and bench["weights"].period(model) == 4
    planes = (c.always_read_weights(model) + 8 * 40 * 3 * 4096 * 1280) * 1.0625
    assert 6.55e9 < planes < 6.75e9                                    # + 0.4 GB of embedding and head: 7.07 GB
    one = c.kernel_counts(model, "expert_gemv", rows=16)
    assert abs(one["bytes"] - 16.71e6) < 0.01e6 and one["layers"] == 8 and one["pairs_per_layer"] == 16
    assert 13 < one["planes_per_layer"] < 13.5
    step = c.kernel_counts(model, "gated_delta_step", rows=16)
    assert step["calls_per_program"] == 6 and 134e6 < step["bytes"] < 137e6          # 0.80 GB of state a step
    assert c.kernel_counts(model, "paged_ragged_attention", rows=1)["bytes"] == 4096.0
    assert c.kernel_counts(model, "ssd_step", rows=1) is None


def test_a_rounded_state_is_erased_as_fast_as_it_is_written(bench, engine):
    """Why ``state16`` reads an honest run in the cell (``gap_tolerance.json``, ``not_caught``): ``(I - beta k k^T)``
    forgets a rounding of the state within about ``dk`` tokens whatever the decay is, so what a state rounded to
    bfloat16 after every token adds to the mixer's output stops growing with the context: the error over positions
    768-1,024 is the error over 64-256 (at the cell's ``dk`` 128 on this CPU: 1.1% from position 256 to 4,096 alike)."""
    ref, m = bench["reference"], bench["model"]
    lp = jax.tree.map(lambda a: a[0], ref.layer_tree(engine.params)["kda"])
    u = jnp.asarray(np.random.default_rng(11).standard_normal((1024, m["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        exact, rounded = ref.mixer(m, u, lp), ref.mixer(m, u, lp, "state16")
    err = np.asarray(jnp.linalg.norm(rounded - exact, axis=-1) / jnp.linalg.norm(exact, axis=-1))
    early, late = float(err[64:256].mean()), float(err[768:].mean())
    assert 1e-3 < early < 0.05 and late < 1.25 * early, (early, late)
