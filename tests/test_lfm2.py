"""The decoder of gated short-convolution layers beside grouped-query
attention layers with routed experts behind leading dense layers
(``ArchType.LFM2``, ``models/lfm2.py``, ``ops/causal_conv.py``,
``models/share.py``; a K/V pool, a pool of convolution tails AND routing
counters a step, ``runtime/serving.py``) against its plain reference
(``benchmark/lfm2/reference.py``, imported from where it lies, no copy), at a
tiny size on the CPU: hidden 64, 4:2 heads of 16 lanes (cached in 128), 9 layers
(two leading conv layers with a dense feed-forward, one whole period of an
attention layer and three conv ones, a period cut short), 8 routed experts of
which a token takes 2 under a selection bias, 3 taps, vocabulary 256, float32,
seeded weights from the benchmark's own maker (``benchmark/lfm2/weights.py``),
so program and reference read the same planes.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference
  compute the same float32 function in another order (a tail carried over
  chunks and steps against one convolution over the whole sequence, a running
  softmax over blocks against a dense mask, a grouped matmul over sorted pairs
  against every expert weighted); the worst seen is 4e-6. Each of the
  reference's variants that the tokens can show reads 0.2 and more.
* ``FORM_TOL`` 2e-5 on attention outputs of spread 0.3: the paged kernel at
  padded heads against the oracle at the heads' own width, reduction order
  alone.
"""

import hashlib
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
LFM2 = os.path.join(BENCH, "lfm2")
TINY = os.path.join(LFM2, "selftest", "configs", "tiny-lfm2.json")
MANIFEST = os.path.join(LFM2, "selftest", "manifest.json")
REAL = os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")
LOGIT_TOL, FORM_TOL = 2e-3, 2e-5


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
    return {"weights": _import("lfm2_weights", os.path.join(LFM2, "weights.py")),
            "reference": _import("lfm2_reference", os.path.join(LFM2, "reference.py")),
            "counts": _import("lfm2_counts", os.path.join(LFM2, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-lfm2.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("lfm2"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens, model=None, variant="none"):
    ref, dense, model = bench["reference"], dense_reference, model or bench["model"]
    T = len(tokens)
    padded = -(-T // dense.BLOCK_Q) * dense.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:T] = tokens
    fn = ref._layers_fn(json.dumps(model, sort_keys=True), variant)
    tree = ref.layer_tree(params)
    x = fn(jnp.asarray(ids), params.embedding, tree,
           *dense.control_handles(model["num_hidden_layers"], T, padded, "none"))
    h = dense._rms_norm(x, params.final_norm, float(model["norm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ dense._dequant(dense._planes(params.logits)))[:T]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def _column(cfg, seq_len=512):
    from dllama_tpu.runtime.kvblocks import StateColumn

    k = jnp.zeros((cfg.n_kv_layers, 1, cfg.n_kv_heads, seq_len, cfg.cache_width), jnp.float32)
    return StateColumn.zeros(cfg, k, k, jnp.float32)


# -- the configuration as the program sees it ----------------------------------------


def test_the_pattern_the_pools_and_the_state_are_the_architectures(engine):
    """Two leading conv layers, a whole period, a period cut short: 7 conv
    layers and 2 attention layers; K/V of the attention layers alone, a
    16-lane head cached in 128; the state a tail of ``K - 1`` rows and NOTHING
    else (no float32 state to allocate, commit or count)."""
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool, state_bytes, state_pool_bytes

    cfg = engine.cfg
    assert (cfg.n_layers, cfg.n_conv_layers, cfg.n_attn_layers, cfg.n_kv_layers, cfg.n_state_layers) == (9, 7, 2, 2, 7)
    assert (cfg.head_dim, cfg.cache_width, cfg.cache_row_elems) == (16, 128, 2 * 2 * 128)
    assert cfg.has_state and cfg.has_short_conv and cfg.has_expert_share and cfg.paged_only
    assert cfg.state_shape(5) is None and cfg.conv_shape(5) == (7, 5, 2, 64)
    assert (cfg.moe_select_bias, cfg.moe_norm_eps, cfg.moe_score, cfg.n_moe_layers) == (True, 1e-6, "sigmoid", 7)
    pool = StatePool.create(cfg, 4, jnp.float32)
    assert pool.s is None and pool.conv.shape == (7, 5, 2, 64)
    assert pool.n_bytes == state_bytes(pool) == state_pool_bytes(cfg, 4, 4) == 7 * 5 * 2 * 64 * 4
    pkv = PagedKVCache.create(cfg, 9, 16, dtype=jnp.float32)
    assert pkv.k.shape == pkv.v.shape == (2, 9, 2, 16, 128)
    col = _column(cfg)
    assert col.s is None and col.conv.shape == (7, 1, 2, 64) and col.stats.shape == (4 + 8,)


def test_causal_conv_takes_its_activation_and_lives_in_a_module_of_its_own():
    """``activation=None`` is the plain convolution; the default is the SiLU
    the two standing clients always had."""
    from dllama_tpu.ops.causal_conv import causal_conv

    rng = np.random.default_rng(0)
    x, tail, w = (jnp.asarray(rng.normal(size=s).astype(np.float32)) for s in ((2, 5, 6), (2, 2, 6), (3, 6)))
    plain, new_tail = causal_conv(x, tail, w, jnp.int32(3), activation=None)
    seq = np.concatenate([np.asarray(tail), np.asarray(x)], axis=1)
    want = sum(np.asarray(w)[j] * seq[:, j:j + 5] for j in range(3))
    assert np.abs(np.asarray(plain) - want).max() < 1e-6
    assert np.array_equal(np.asarray(new_tail), seq[:, 3:5])          # the two inputs in front of position 3
    silu, _ = causal_conv(x, tail, w, jnp.int32(3))
    assert np.abs(np.asarray(silu) - np.asarray(jax.nn.silu(plain))).max() < 1e-6


# -- the router: a selection bias, an epsilon, and the two standing clients untouched -


def _parents_route(cfg, h, gate):
    """``share.route`` as the parent commit had it, letter for letter."""
    logits = jnp.einsum("nd,ed->ne", h.astype(jnp.float32), gate.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.moe_score == "sigmoid" else jax.nn.softmax(logits, axis=-1))
    k, G = cfg.n_active_experts, cfg.moe_n_group
    if G > 1:
        N, W = scores.shape
        per_group = jax.lax.top_k(scores.reshape(N, G, W // G), k // cfg.moe_topk_group)[0].sum(axis=-1)
        _, best = jax.lax.top_k(per_group, cfg.moe_topk_group)
        allowed = jnp.zeros((N, G), bool).at[jnp.arange(N)[:, None], best].set(True)
        limited = jnp.where(jnp.repeat(allowed, W // G, axis=1), scores, -jnp.inf)
        top, idx = jax.lax.top_k(limited, k)
    else:
        top, idx = jax.lax.top_k(scores, k)
    if cfg.moe_norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top * cfg.moe_routed_scale, idx


@pytest.mark.parametrize("client", ["laguna", "a.x-k1"])
def test_the_standing_clients_routes_are_bit_identical_to_the_parents(engine, client):
    """laguna's softmax router and A.X-K1's group-limited sigmoid one, at
    their cells' own widths and counts: the same bits out of ``share.route`` as
    out of the parent's function, and the same lowered program."""
    from dataclasses import replace

    from dllama_tpu.models import share

    cfg = (replace(engine.cfg, moe_score="softmax", moe_router_width=256, n_experts=32, n_active_experts=10,
                   moe_routed_scale=2.5, moe_norm_eps=0.0, moe_select_bias=False)
           if client == "laguna" else
           replace(engine.cfg, moe_score="sigmoid", moe_router_width=192, n_experts=12, n_active_experts=8,
                   moe_n_group=8, moe_topk_group=4, moe_routed_scale=2.5, moe_norm_eps=0.0, moe_select_bias=False))
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(48, 64)).astype(np.float32))
    gate = jnp.asarray((rng.normal(size=(cfg.moe_router_width, 64)) * 0.5).astype(np.float32))
    got, want = share.route(cfg, h, gate), _parents_route(cfg, h, gate)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(got, want))
    lowered = lambda fn: jax.jit(lambda h, g: fn(cfg, h, g)).lower(h, gate).as_text()
    strip = lambda text: "\n".join(line.split(" loc(")[0] for line in text.splitlines() if not line.startswith("#loc"))
    assert strip(lowered(share.route)) == strip(lowered(_parents_route))


def test_the_bias_enters_the_selection_only_and_changes_it_in_a_good_share_of_rows(bench, engine):
    """Against a plain ``top_k``: the experts are the top of ``s + b``, their
    weights the chosen ``s`` over ``(their sum + 1e-6)``; without a bias the
    plain top of ``s``. The benchmark's seeded bias changes the chosen set."""
    from dllama_tpu.models import share

    cfg, lp = engine.cfg, engine.params.layers
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(400, 64)).astype(np.float32))
    gate, bias = lp.moe_gate[2], lp.moe_bias[2]
    assert float(jnp.abs(bias).max()) > 0
    w, idx = share.route(cfg, h, gate, bias)
    s = np.asarray(jax.nn.sigmoid(jnp.einsum("nd,ed->ne", h, gate, precision=jax.lax.Precision.HIGHEST)))
    want_idx = np.argsort(-(s + np.asarray(bias)), axis=1, kind="stable")[:, :2]
    assert np.array_equal(np.sort(np.asarray(idx), axis=1), np.sort(want_idx, axis=1))
    chosen = np.take_along_axis(s, np.asarray(idx), axis=1)
    assert np.abs(np.asarray(w) - chosen / (chosen.sum(axis=1, keepdims=True) + 1e-6)).max() < 1e-6
    w0, idx0 = share.route(cfg, h, gate)
    plain = np.argsort(-s, axis=1, kind="stable")[:, :2]
    assert np.array_equal(np.sort(np.asarray(idx0), axis=1), np.sort(plain, axis=1))
    moved = np.mean(np.any(np.sort(np.asarray(idx), axis=1) != np.sort(np.asarray(idx0), axis=1), axis=1))
    assert 0.05 < moved < 0.95, moved
    # the reference's own router gives the same weights, and its controls move them
    ref = bench["reference"]
    held = np.asarray(ref.route(bench["model"], h, gate, bias, "none"))
    assert np.abs(np.take_along_axis(held, np.asarray(idx), axis=1) - np.asarray(w)).max() < 1e-6
    assert np.abs(np.asarray(ref.route(bench["model"], h, gate, bias, "nobias")) - held).max() > 0.1


# -- logits against the reference: the chunk form, then chunks and steps through the pools --


@pytest.mark.parametrize("T", [20, 70, 300])
def test_whole_forward_logits(bench, engine, T):
    from dllama_tpu.models import llama

    cfg, tokens = engine.cfg, _tokens(T, seed=T)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), _column(cfg))
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)          # every pair counted once, every expert held; planes: the distinct a layer
    assert stats[0] == T * 2 * 7 and stats[1] == 0 and stats[4:].sum() == stats[0] and 7 <= stats[3] <= 7 * 8
    # the column's tails are the last two gated inputs of every conv layer: a chunk behind them agrees too
    more = _tokens(9, seed=T + 1)
    logits2, _ = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(T), col))(
        engine.params, jnp.asarray([more], jnp.int32), col)
    want2 = _reference_logits(bench, engine.params, tokens + more)[T:]
    assert float(np.abs(np.asarray(logits2[0]) - want2).max()) < LOGIT_TOL


@pytest.mark.parametrize("variant,least", [("nobias", 0.1), ("convsilu", 0.5), ("notail", 0.5), ("noqknorm", 0.5),
                                           ("bf16router", 0.1)])
def test_the_references_variants_are_another_function(bench, engine, variant, least):
    """The routing variants move a logit less than the mixers' do: an expert's
    down-projection is drawn at a quarter of the other planes' gain
    (``benchmark/lfm2/weights.py`` says why)."""
    tokens = _tokens(70, seed=70)
    honest = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(_reference_logits(bench, engine.params, tokens, variant=variant) - honest).max()) > least


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


# prompt lengths on and around the edges: 17 / 16 / 15 prefilled positions (a block's edge; a bucket's), 33 (a padded
# 32-bucket behind a whole one), 70 (64 + a padded tail: the tail of the conv state lies BEHIND padding), 257 / 258
# (exactly the widest chunk; one past it: a second chunk of one position, whose tail is one old row and one new),
# 300 (256, 32, 11 padded to 16). kernel "fused": the steps' attention through paged_ragged_attention at the padded
# heads and the routed feed-forward through expert_gemv, both in interpret mode, a dead slot with a stale depth
# beside the live one.
@pytest.mark.parametrize("n_prompt,kernel", [(16, None), (17, None), (18, None), (33, None), (70, None), (257, None),
                                             (258, None), (300, None), (70, "fused"), (258, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"attention": [], "experts": 0}
    entry, gemv = pa.paged_ragged_attention, eg.expert_gemv
    monkeypatch.setattr(pa, "paged_ragged_attention",
                        lambda q, *a, **kw: calls["attention"].append(q.shape[-1]) or entry(q, *a, **kw))
    monkeypatch.setattr(eg, "expert_gemv",
                        lambda *a, **kw: calls.__setitem__("experts", calls["experts"] + 1) or gemv(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 20
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # traced once each: the attention layer's body at 128 lanes (the scan's and the cut period's) in the step AND
    # (PR 53) in the tick program of each of the prompt's two buckets, whose dead rows walk the null block; two
    # routed bodies of three GEMVs in the step alone (a tick's joined rows take the chunk form)
    assert (calls["attention"], calls["experts"]) == (([128] * 6, 12) if kernel else ([], 0))
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL
    totals = np.asarray(gen.moe_stats)
    assert totals[0, 0] == n_steps * 2 * 7 and totals[1, 0] == (n_prompt - 1) * 2 * 7      # the steps', the chunks'
    assert totals[0, 3] == n_steps * 2 * 7                # one live row: every pair its own plane
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)


def test_a_slot_retired_and_taken_again_leaves_no_tail_behind(bench, engine):
    """The second occupant of a slot decodes what it decodes alone: the
    first one's tails (and its K/V) are gone with its commit's overwrite."""
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    first, second = _tokens(90, seed=1), _tokens(41, seed=2)
    gen = PagedGenerator(engine, n_slots=1)
    gen.admit(Request(rid=1, prompt_ids=first, max_tokens=8, stop_on_eos=False), 0)
    _decode(gen, [0], 8)
    left = np.asarray(gen.spool.conv[:, 1])
    assert np.abs(left).max() > 0
    gen._retire(0, "done")
    gen.admit(Request(rid=2, prompt_ids=second, max_tokens=8, stop_on_eos=False), 0)
    assert not np.array_equal(np.asarray(gen.spool.conv[:, 1]), left)
    got = _decode(gen, [0], 8)[0]
    emitted = got.argmax(axis=1).tolist()
    want = _reference_logits(bench, engine.params, second + emitted)[len(second) - 1:len(second) - 1 + 8]
    assert float(np.abs(got - want).max()) < LOGIT_TOL


# -- the paged kernel at heads off 128 lanes: padded, against the oracle at their own width ---


@pytest.mark.parametrize("D,n_heads,n_kv,bs,M,T", [(64, 32, 8, 16, 8, 1), (64, 8, 2, 16, 5, 1), (16, 4, 2, 16, 6, 1),
                                                   (64, 8, 8, 8, 12, 3)])
def test_paged_kernel_at_padded_heads_against_the_oracle(D, n_heads, n_kv, bs, M, T):
    """Heads of ``D`` lanes cached in 128, as ``models/lfm2.py`` hands them to
    ``_attend_paged``: interpret mode against the gather + oracle at ``D`` lanes
    over scrambled tables, a dead row with a stale depth, ragged lengths on and
    around block and group edges; the padded lanes of the result are zero."""
    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.ops.attention import attention

    B, W = 5, 128
    rng = np.random.default_rng(D + n_heads + M)
    n_blocks = B * M + 1
    pad = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, W - D),))
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(2, n_blocks, n_kv, bs, D)).astype(np.float32)) for _ in range(2))
    lens = np.asarray([1, bs, bs + 1, M * bs - T, 57 % (M * bs - T) + 1])[:B]
    perm = rng.permutation(np.arange(1, n_blocks))
    tables = np.zeros((B, M), np.int32)
    for b in range(B):
        used = -(-(lens[b] + T) // bs)
        tables[b, :used] = perm[b * M:b * M + used]
    tables[1] = 0                                          # a dead row, whatever its depth says
    positions = jnp.asarray(lens[:, None] + np.arange(T)[None, :], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, T, n_heads, D)).astype(np.float32))
    assert pa.supports((B, T, n_heads, W), n_kv, M, bs, compiled=True)          # what the chip's compiler is asked
    assert not pa.supports((B, T, n_heads, D), n_kv, M, bs, compiled=True) or D % 128 == 0
    got = pa.paged_ragged_attention(pad(q), pad(k_pool), pad(v_pool), jnp.int32(1), jnp.asarray(tables), positions,
                                    D, interpret=True)
    view = lambda pool: jnp.moveaxis(pool[1][jnp.asarray(tables)], 2, 1).reshape(B, n_kv, M * bs, D)
    want = attention(q, view(k_pool), view(v_pool), positions, D)
    live = np.asarray([0, 2, 3, 4])
    assert float(jnp.abs(got[live, ..., :D] - want[live]).max()) < FORM_TOL
    assert not np.asarray(got[..., D:]).any() and not np.asarray(got[1]).any()
    # and the oracle itself takes padded lanes with the heads' own scale (the gather's fallback)
    wide = attention(pad(q), jnp.pad(view(k_pool), ((0, 0),) * 3 + ((0, W - D),)),
                     jnp.pad(view(v_pool), ((0, 0),) * 3 + ((0, W - D),)), positions, D)
    assert float(jnp.abs(wide[live, ..., :D] - want[live]).max()) < FORM_TOL


# -- through the scheduler: counters, spans, the state's bytes -----------------------------


def test_scheduler_serves_state_and_counters_in_one_step(bench, engine, tmp_path):
    """Through ``BatchScheduler``: interleaved requests finish and are the
    reference's tokens, the same prompt twice gives the same tokens with the
    prefix NOT reused (a tail is a function of the whole prefix), the routing
    counters reach the registry, and while a profiler listens the steps' spans
    carry ``kv_walk_blocks``, ``moe_step_held`` and ``moe_planes`` for the new
    readers and ``admit_commit`` the tail's bytes."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    pairs, skipped = reg.counter(telemetry.MOE_PAIRS), reg.counter(telemetry.PREFIX_REUSE_SKIPPED)
    held0, absent0, skip0 = pairs.total(where="held"), pairs.total(where="absent"), skipped.total(reason="recurrent_state")
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
        assert pairs.total(where="held") - held0 == tokens * 2 * 7 and pairs.total(where="absent") == absent0
        assert reg.gauge(telemetry.LAYER_KINDS).value(kind="conv") == 7
        assert reg.gauge(telemetry.STATE_POOL_BYTES).value() == 7 * 4 * 2 * 64 * 4
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
        steps = [st for name, _s, _e, st in children if name == "step_wait" and "moe_planes" in st]
        assert len(steps) == 40 and all("kv_walk_blocks" in st and "moe_step_held" in st for st in steps)
        assert [int(st["kv_walk_blocks"]) for st in steps] == [-(-(49 + i + 1) // 16) for i in range(40)]
        with open(os.path.join(BENCH, "layer_metrics", "moe_pairs_per_plane.json"), encoding="utf-8") as f:
            spec = json.load(f)
        ctx = {"trace": {}, "program_spans": spans}
        assert spec["reader"] == "slice_counters" and counters.read(ctx, **spec["args"]) == 1.0     # one row: a plane a pair
        commits = [st for name, _s, _e, st in children if name == "admit_commit" and "state_bytes" in st]
        assert [int(st["state_bytes"]) for st in commits] == [7 * 2 * 64 * 4]      # the tail alone
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
    with pytest.raises(ValueError, match="short-convolution layers and routed experts") as err:
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
    from dllama_tpu.formats.mfile import ArchType, ModelFile, RopeType
    from dllama_tpu.models.config import ModelConfig

    path = str(tmp_path / "walk.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path) as mf:
        h = mf.header
        assert (h.arch_type, h.rope_type, h.layer_period, h.n_dense_layers) == (ArchType.LFM2, RopeType.FALCON, 4, 2)
        assert (h.short_conv_kernel, h.moe_select_bias, h.moe_score_func, h.moe_router_width) == (3, 1, 1, 8)
        assert [h.lfm2_is_attn(l) for l in range(9)] == [k == "full_attention" for k in bench["model"]["layer_types"]]
        assert mf.tensors["block_conv_in.0"].shape == (192, 64) and mf.tensors["block_conv_taps.3"].shape == (3, 64)
        assert mf.tensors["block_norm_q.2"].shape == (16,) and "block_conv_in.2" not in mf.tensors
        assert mf.tensors["block_moe_bias.2"].shape == (8,) and "block_moe_bias.1" not in mf.tensors
        assert mf.tensors["block_matmul_w1.1"].shape == (128, 64) and "block_matmul_w1.2" not in mf.tensors
        last = max(mf.tensors.values(), key=lambda r: r.offset)
        assert last.offset + last.n_bytes == os.path.getsize(path)             # the walk ends where the file does
        cfg = ModelConfig.from_header(h)
    assert (cfg.n_attn_layers, cfg.n_conv_layers, cfg.conv_kernel, cfg.dense_hidden_dim, cfg.hidden_dim) == (2, 7, 3, 128, 32)


def test_converter_maps_the_config_and_says_it_has_no_tensor_map(tmp_path):
    from dllama_tpu.convert import hf
    from dllama_tpu.formats.mfile import ArchType
    from dllama_tpu.formats.quants import Q40

    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    (tmp_path / "config.json").write_text(json.dumps(published))
    params = hf.load_hf_config(tmp_path, Q40)
    assert params["arch_type"] == int(ArchType.LFM2) and params["layer_period"] == 4
    assert (params["dim"], params["hidden_dim"], params["dense_hidden_dim"], params["n_layers"]) == (2048, 1536, 11776, 18)
    assert (params["n_experts"], params["n_active_experts"], params["moe_router_width"], params["n_dense_layers"]) == (64, 4, 64, 2)
    assert (params["short_conv_kernel"], params["moe_select_bias"], params["moe_score_func"], params["head_dim"]) == (3, 1, 1, 64)
    assert (params["rope_theta"], params["norm_epsilon"], params["moe_routed_scale_milli"]) == (1000000, 5, 1000)
    with pytest.raises(NotImplementedError, match="tensor names are not"):
        hf.hf_tensor_plan(params)
    (tmp_path / "config.json").write_text(json.dumps(dict(published, layer_types=published["layer_types"][::-1])))
    with pytest.raises(ValueError, match="leading conv layers"):
        hf.load_hf_config(tmp_path, Q40)


def test_the_cell_configuration_is_the_issues_reckoning(bench):
    """Every width at its published value (the catalog's row, copied here);
    ``reduced`` exactly what was cut; the counts module's bytes are the
    issue's: 11.26 GB of weights as held, a step at 32 rows 9.8 GB where
    a plane a pair would read 20.5."""
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
                 "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
                 "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
                 "num_experts": 64, "num_experts_per_tok": 4, "num_key_value_heads": 8,
                 "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "vocab_size": 65536}
    assert {k: conf[k] for k in published} == published
    assert conf["reduced"] == ["num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["num_hidden_layers"] == 18 and conf["reduced_from"]["num_hidden_layers"] == 40
    assert conf["layer_types"] == ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
    assert set(conf["reduced_why"]) == set(conf["reduced"]) and conf["deployment"] and conf["memory"]
    assert (conf["engine"]["slots"], conf["engine"]["max_seq_len"], conf["engine"]["kv_block_size"]) == (32, 1024, 16)
    model, c = bench_run.model_view(conf), bench["counts"]
    assert bench["weights"].pattern(model) == (2, 4) and bench["reference"].pattern(model) == (2, 4)
    planes = (c.always_read_weights(model) + 16 * 64 * 3 * 2048 * 1536) * 1.0625
    assert 10.6e9 < planes < 10.8e9                                    # + 537 MB of embedding and head: 11.26 GB
    step = c.decode_step_bytes(model, rows=32, context_tokens=32 * 500)
    assert 9.6e9 < step < 10.0e9 and 55 < c.experts_touched(model, 32) < 56.5
    one = c.kernel_counts(model, "expert_gemv", rows=32)
    assert abs(one["bytes"] - 10.03e6) < 0.01e6 and one["pairs_per_layer"] == 128 and one["layers"] == 16
    assert 20.4e9 < one["bytes"] * 128 * 16 < 20.6e9
    walk = c.kernel_counts(model, "paged_ragged_attention", rows=32)
    assert (walk["bytes"], walk["layers"], walk["flops"]) == (2048.0, 4, 4.0 * 2048)
    with open(os.path.join(BENCH, "traffic", "batch-generate-lfm2.json"), encoding="utf-8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sizes_seed"], mix["engine"]) == ("closed", 32, 4401, {"slots": 32, "max_seq_len": 1024})
    assert [(m["prompt_tokens"], m["output_tokens"]) for m in mix["mix"]] == [
        ({"dist": "uniform", "low": 64, "high": 256}, {"dist": "uniform", "low": 256, "high": 640})]
    assert mix["sampling"]["temperature"] == 0.0 and "sessions" not in mix and "shared_prefix" not in mix


# -- the benchmark's seam, seen by tier-1 ------------------------------------------


@pytest.mark.parametrize("control, correct", [("none", True), ("shift", False), ("droplayer", False),
                                              ("dropblock", False), ("nobias", False), ("convsilu", False),
                                              ("notail", False), ("noqknorm", False), ("bf16router", False),
                                              ("biasweight", True)])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under each
    control the tokens can show. ``biasweight`` is the one they cannot (the
    chosen scores are all near 1, so weights from ``s + b`` are the same
    near-uniform weights: ``gap_tolerance.json`` names it)."""
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-lfm2.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "5", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]


def test_a_real_file_loads_through_the_streaming_loader(bench, tmp_path):
    """A ``.m`` with real tensors in the walk's order, through
    ``runtime/weights.load_params`` (no seam), served, against the reference."""
    import struct

    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.formats import mfile
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.runtime.serving import BatchScheduler

    from helpers import write_tensor

    path = str(tmp_path / "real.m")
    fields = bench["weights"].header_fields(bench["model"])
    rng = np.random.default_rng(5)
    with open(path, "wb") as f:
        data = b"".join(struct.pack("<ii", k if isinstance(k, int) else int(mfile.HeaderKey[k.upper()]), int(v))
                        for k, v in fields.items())
        f.write(struct.pack("<ii", mfile.MODEL_MAGIC, 8 + len(data)) + data)
        f.truncate(bench["weights"].walk_size(bench["model"], 8 + len(data)))
    with mfile.ModelFile.open(path) as mf:
        records = sorted(mf.tensors.values(), key=lambda r: r.offset)
    with open(path, "r+b") as f:
        f.seek(records[0].offset)
        for rec in records:
            ones = rec.name.startswith(("block_norm", "final_norm"))
            scale = {"block_moe_gate": 0.5, "block_moe_bias": 0.01, "block_conv_taps": 0.5}.get(rec.name, 0.1)
            x = np.ones(rec.shape, np.float32) if ones else (rng.standard_normal(rec.shape) * scale).astype(np.float32)
            write_tensor(f, x, rec.float_type)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(path, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        lp = eng.params.layers
        assert lp.conv.w_in.codes.shape == (7, 64, 192) and lp.conv.conv_w.shape == (7, 3, 64)
        assert lp.attn.wq.codes.shape == (2, 64, 64) and lp.attn.norm_q.shape == (2, 16)
        assert lp.we1.codes.shape == (7, 8, 64, 32) and lp.w1.codes.shape == (2, 64, 128)
        assert lp.moe_gate.shape == (7, 8, 64) and lp.moe_bias.shape == (7, 8)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            req = sched.submit(prompt, 6, stop_on_eos=False)
            assert req.done.wait(300) and not req.error
            r = bench["reference"].reference_gaps(bench["model"], eng.params, prompt, list(req.tokens))
            assert float(np.max(r["gap"])) == 0.0
        finally:
            sched.close()
    finally:
        eng.close()


def test_the_new_readers_read_what_the_program_counts_and_nothing_from_a_parent(bench):
    """The two readers PR 44 brings, on worked numbers: the paged walk's
    share of the HBM roof from the blocks the steps' spans carry (never slots x
    context) at the USEFUL bytes of a cached token, the grouped routed kernel's
    from the planes the slice added; and ``None``, not an error, where the
    program has no such span or total (a parent commit)."""
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    try:
        walk, planes, kernel = (_import(n, os.path.join(BENCH, "readers", n + ".py"))
                                for n in ("paged_walk_roofline", "expert_planes_roofline", "kernel_roofline"))
    finally:
        sys.path.remove(os.path.join(BENCH, "readers"))
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    model = bench_run.model_view(conf)
    tick = lambda st: {"children": [("step_dispatch", 0.0, 0.001, {}), ("step_wait", 0.001, 0.006, st)]}
    P = "paged_sampled_step_guarded"
    ctx = {"trace": {"device_ops": [(f"{P}/paged_ragged_attention.3 custom-call", 0.004),
                                    (f"{P}/expert_chunk.7 custom-call", 0.050),
                                    (f"{P}/quant_matmul.9 custom-call", 0.01), ("forward/expert_chunk.1 custom-call", 9.0)],
                     "modules": {f"jit_{P}(3)": [0.03, 0.03, 0.04]}},
           "counts": bench["counts"], "model": model, "conf": conf, "peaks": {"hbm_bytes_per_s": 819e9},
           "program_spans": {"ticks": [tick({"kv_walk_blocks": "900", "moe_planes": 5000}),
                                       tick({"kv_walk_blocks": 950, "moe_planes": "5880"}),
                                       tick({"kv_walk_blocks": 1000, "moe_planes": 6760}), tick({})]}}
    args = {"kernel": "paged_ragged_attention", "program": P}
    want = 100.0 * 2850 * 16 * 4 * 2048 / 819e9 / 0.004         # 2850 blocks x 16 tokens x 4 layers x 2 KB in 4 ms: 11.4%
    assert abs(walk.read(ctx, **args) - want) < 1e-9 and 11 < want < 12
    want = 100.0 * 1760 * 3 * 2048 * 1536 * 1.0625 / 819e9 / 0.050             # 1760 planes of 10.03 MB in 50 ms: 43.1%
    assert abs(planes.read(ctx, kernel="expert_chunk", program=P) - want) < 1e-9 and 43 < want < 44
    assert abs(kernel.read(ctx, share="time", **args) - 4.0) < 1e-9            # 4 ms of the step programs' 100
    parent = dict(ctx, program_spans={"ticks": [tick({"moe_pairs": "3"}), tick({})]})
    assert walk.read(parent, **args) is None and planes.read(parent, kernel="expert_chunk", program=P) is None
    assert walk.read(dict(ctx, trace=None), **args) is None and planes.read(dict(ctx, trace=None), kernel="expert_chunk", program=P) is None
    assert walk.read(ctx, kernel="mla_paged_step", program=P) is None          # no such op, no such kernel
    for name, reader in (("paged_attn_step_hbm_share", walk), ("paged_attn_step_share", kernel)):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        assert reader.read(ctx, **spec["args"]) is not None
