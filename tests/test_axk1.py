"""The decoder of latent attention (MLA) layers with a sigmoid group-limited
router over an expert share (``ArchType.AXK1``, ``models/axk1.py``,
``ops/mla.py``, ``models/share.py``; one pool of compressed rows a sequence,
``runtime/serving.py``) against its plain reference
(``benchmark/a_x_k1/reference.py``, imported from where it lies, no copy), at a
tiny size on the CPU: hidden 64, 4 heads of 16 nope + 8 rope lanes, latents of
32 (queries) and 32 (the cache's ``c``), a cached row of 40 values in 128
lanes, 4 layers (a leading dense one), 16 routed experts in 4 groups of which
8 are held (from the 4th), 4 a token of 2 groups, a shared expert, vocabulary
256, float32, seeded weights from the benchmark's own maker
(``benchmark/a_x_k1/weights.py``), so program and reference read the same
planes.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference
  compute the same float32 function in another order (absorbed against
  expanded keys and values, a running softmax over blocks against a dense
  mask, a grouped matmul over sorted pairs against every expert weighted);
  the worst seen is 4e-6. Each of the reference's variants (a control of the
  cell) reads 2.0 and more.
* ``FORM_TOL`` 2e-5 on attention outputs of spread 0.3: the three forms
  against the oracle, reduction order alone.
* ``SHARE_TOL`` 2e-4 on a layer's output of spread 1-3: eight partial sums
  added in another order than the uncut layer's one sum.
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
AXK1 = os.path.join(BENCH, "a_x_k1")
TINY = os.path.join(AXK1, "selftest", "configs", "tiny-a.x-k1.json")
MANIFEST = os.path.join(AXK1, "selftest", "manifest.json")
REAL = os.path.join(BENCH, "configs", "a.x-k1.json")
LOGIT_TOL, FORM_TOL, SHARE_TOL = 2e-3, 2e-5, 2e-4


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
    return {"weights": _import("axk1_weights", os.path.join(AXK1, "weights.py")),
            "reference": _import("axk1_reference", os.path.join(AXK1, "reference.py")),
            "counts": _import("axk1_counts", os.path.join(AXK1, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-axk1.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("axk1"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens, model=None, variant="none"):
    ref, dense, model = bench["reference"], dense_reference, model or bench["model"]
    T = len(tokens)
    padded = -(-T // dense.BLOCK_Q) * dense.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:T] = tokens
    fn = ref._layers_fn(json.dumps(model, sort_keys=True), variant)
    x = fn(jnp.asarray(ids), params.embedding, ref.layer_tree(params),
           *dense.control_handles(model["num_hidden_layers"], T, padded, "none"))
    h = dense._rms_norm(x, params.final_norm, float(model["norm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ dense._dequant(dense._planes(params.logits)))[:T]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


# -- the rotary table and the scale --------------------------------------------


@pytest.mark.parametrize("pos", [1, 700, 17000])
def test_yarn_table_and_scale_are_the_formula(bench, tmp_path, pos):
    """A.X-K1's own numbers, worked here in float64: theta 1e4 over the 64
    rope lanes, factor 32 over 4096, beta 32 / 1; mscale = mscale_all_dim = 1,
    so the tables are UNSCALED and 192^-0.5 (0.1 ln 32 + 1)^2 = 0.1309
    multiplies the whole score (not ``yarn_attention_factor``'s convention,
    which scales the tables)."""
    import math

    from dllama_tpu.formats.mfile import ModelFile
    from dllama_tpu.models import axk1, rope
    from dllama_tpu.models.config import ModelConfig

    r, theta, factor, orig = 64, 1e4, 32.0, 4096
    i = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2 * i / r)
    dim = lambda n: r * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), r - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = (e / factor) * ramp + e * (1 - ramp)
    with open(REAL, encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    path = str(tmp_path / "real.m")
    bench["weights"].write_sparse_model(path, model)          # sparse: a header and a hole
    with ModelFile.open(path, max_seq_len=17408) as mf:
        cfg = ModelConfig.from_header(mf.header, "bfloat16")
    cos, sin = axk1.rope_table(cfg)
    assert cos.shape == (17408, 32)
    assert np.allclose(cos[pos], np.cos(pos * inv), atol=2e-3) and np.allclose(sin[pos], np.sin(pos * inv), atol=2e-3)
    assert np.abs(cos).max() <= 1.0 + 1e-6                                   # unscaled: the ratio of the mscales is 1
    assert abs(cfg.attn_scale - 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2) < 1e-12 and abs(cfg.attn_scale - 0.1309) < 1e-4
    assert (cfg.latent_dim, cfg.latent_row, cfg.cache_heads, cfg.cache_width, cfg.cache_row_elems) == (576, 640, 1, 640, 640)
    assert rope.yarn_mscale(32.0, 1.0) == 0.1 * math.log(32) + 1 and rope.yarn_mscale(1.0, 1.0) == 1.0


# -- the three forms against the oracle ------------------------------------------


def _attention_case(seed, T, S, H=4, nope=16, r=8, kvl=32, v=16, row=128):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    n = lambda *shape: jax.random.normal(next(k), shape, jnp.float32)
    q_n, q_r, c, k_r = n(T, H, nope), n(T, H, r), n(S, kvl), n(S, r)
    wuk, wuv = n(H, nope, kvl) * kvl ** -0.5, n(H, v, kvl) * kvl ** -0.5
    rows = jnp.concatenate([c, k_r, jnp.zeros((S, row - kvl - r))], axis=-1)
    return q_n, q_r, c, k_r, wuk, wuv, rows


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("start,T", [(0, 40), (100, 64), (1000, 24)])
def test_the_chunk_form_is_the_oracle(start, T, form):
    """Absorbed, in blocks of the context under a running softmax, against the
    unabsorbed per-token form: a chunk at the column's start, one behind a
    prefix inside the first block, one that walks several blocks; the XLA
    walk, and the kernel in interpret mode over layer 1 of a three-layer
    column (tiles of query rows, each clamped to its own last visible block)."""
    from dllama_tpu.ops import mla

    S = 2048
    q_n, q_r, c, k_r, wuk, wuv, rows = _attention_case(start + T, T, S)
    scale = 0.21
    want = mla.latent_attention_oracle(q_n, q_r, c, k_r, wuk, wuv, start + jnp.arange(T), scale)
    qa = mla.absorb_q(q_n, q_r, wuk, 128)
    col = jnp.zeros((3, 1, 1, S, 128)).at[1, 0, 0].set(rows).at[0].set(7.0)
    assert mla._chunk_tiles(T * 4, S, False) == ({160: 32, 256: 256, 96: 32}[T * 4], 512)
    kernel = {"interpret": True} if form == "kernel" else None
    got = jax.jit(lambda qa, col: mla.mla_chunk(qa, col, jnp.int32(1), jnp.int32(start), scale, 32, kernel))(qa, col)
    got = mla.unabsorb_o(got, wuv, jnp.float32)
    assert got.shape == want.shape == (T, 4, 16) and float(jnp.abs(got - want).max()) < FORM_TOL
    assert float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_step_form_is_the_oracle(form):
    """Four rows at ragged depths over a scrambled block table of a three-layer
    pool (a dead row among them, whose table is null and whose depth is
    stale), absorbed, against the unabsorbed form over each row's own column:
    the XLA gather form, and the kernel in interpret mode (depths past one
    fetch group of 512 tokens, so the running softmax takes several steps)."""
    from dllama_tpu.ops import mla

    bs, M, L, B = 16, 48, 3, 4
    depths = [5, 700, 0, 530]                      # row 2 is dead
    rng = np.random.default_rng(3)
    pool = np.zeros((L, B * M + 1, 1, bs, 128), np.float32)
    tables = np.zeros((B, M), np.int32)
    free = rng.permutation(np.arange(1, B * M + 1)).tolist()
    cases = {}
    for b, depth in enumerate(depths):
        if b == 2:
            continue
        q_n, q_r, c, k_r, wuk, wuv, rows = _attention_case(10 + b, 1, depth + 1)
        cases[b] = (q_n, q_r, c, k_r)
        for j in range(-(-(depth + 1) // bs)):
            tables[b, j] = free.pop()
            blk = np.asarray(rows[j * bs:(j + 1) * bs])
            pool[1, tables[b, j], 0, :len(blk)] = blk
    qa = jnp.stack([mla.absorb_q(cases[b][0], cases[b][1], wuk, 128) if b in cases else jnp.ones((1, 4, 128))
                    for b in range(B)])
    pos0 = jnp.asarray([5, 700, 123, 530], jnp.int32)
    fn = (mla.mla_paged_step_xla if form == "xla"
          else lambda *a, **kw: mla.mla_paged_step(*a, **kw, interpret=True))
    got = fn(qa, jnp.asarray(pool), jnp.int32(1), jnp.asarray(tables), pos0, scale=0.21, vdim=32)
    assert got.shape == (B, 1, 4, 32) and not np.asarray(got[2]).any()
    for b, (q_n, q_r, c, k_r) in cases.items():
        want = mla.latent_attention_oracle(q_n, q_r, c, k_r, wuk, wuv, pos0[b:b + 1], 0.21)
        assert float(jnp.abs(mla.unabsorb_o(got[b], wuv, jnp.float32) - want).max()) < FORM_TOL, b


# -- the router -----------------------------------------------------------------


def test_the_router_is_the_reference_with_ties_and_a_group_limit_that_bites(bench, engine):
    """The program's router against the reference's on rows made to tell:
    row 0's two best scores tie exactly (the lower index wins); row 1's plain
    top 4 of 16 holds an expert of a group that is not among its 2 best
    groups, so the group limit changes the choice; 64 random rows beside
    them, of which the limit changes a stated share."""
    from dllama_tpu.models import share

    cfg, model, ref = engine.cfg, bench["model"], bench["reference"]
    assert (cfg.moe_score, cfg.moe_n_group, cfg.moe_topk_group, cfg.n_active_experts) == ("sigmoid", 4, 2, 4)
    d = cfg.dim
    gate = np.zeros((16, d), np.float32)
    gate[np.arange(16), np.arange(16)] = 1.0               # logit e of a row is its lane e
    h = np.zeros((66, d), np.float32)
    h[0, :16] = [3, 3, 1, 1, 2, 2, 0, 0, -1, -1, -1, -1, -2, -2, -2, -2]              # 0 and 1 tie
    # groups of 4: sums of each group's two largest sigmoids; group 0 holds the single best expert and nothing
    # else, groups 1 and 2 hold two good ones each
    h[1, :16] = [4, -6, -6, -6, 2, 2, -6, -6, 1.5, 1.5, -6, -6, -6, -6, -6, -6]
    h[2:, :16] = np.random.default_rng(5).normal(size=(64, 16)) * 2
    w, idx = jax.jit(lambda h, g: share.route(cfg, h, g))(jnp.asarray(h), jnp.asarray(gate))
    w_ref, idx_ref = ref.route(model, jnp.asarray(h), jnp.asarray(gate), "none")
    assert np.array_equal(np.asarray(idx), np.asarray(idx_ref)) and float(jnp.abs(w - w_ref).max()) < 1e-6
    assert np.asarray(idx[0]).tolist()[:2] == [0, 1]
    assert sorted(np.asarray(idx[1]).tolist()) == [4, 5, 8, 9]                        # expert 0 is the best and is left out
    _, plain = ref.route(model, jnp.asarray(h), jnp.asarray(gate), "nogroups")
    assert 0 in np.asarray(plain[1]).tolist()
    changed = np.mean([sorted(a) != sorted(b) for a, b in zip(np.asarray(idx[2:]).tolist(), np.asarray(plain[2:]).tolist())])
    assert 0.3 < changed < 0.95
    assert float(jnp.abs(w.sum(axis=1) - 2.5).max()) < 1e-5                           # renormalised, times 2.5


# -- the whole program against the reference ---------------------------------------


@pytest.mark.parametrize("T", [40, 300])
def test_whole_forward_logits(bench, engine, T):
    """One chunk over a latent column against the reference's full forward."""
    from dllama_tpu.models import axk1, llama

    cfg = engine.cfg
    tokens = _tokens(T)
    col = axk1.LatentColumn.zeros(cfg, jnp.float32)
    assert col.c.shape == (4, 1, 1, 512, 128) and (cfg.n_moe_layers, cfg.n_dense_layers) == (3, 1)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), col)
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)
    assert stats[0] + stats[1] == T * 4 * 3 and stats[4:].sum() == stats[0]          # every pair counted once
    assert not np.asarray(col.c[..., 40:]).any() and np.asarray(col.c[:, 0, 0, :T, :40]).all(axis=-1).all()


def test_whole_forward_logits_through_the_grouped_kernel(bench, engine, monkeypatch):
    """The same chunk with the kernels forced (interpret mode off a TPU): the
    routed layers' three projections each ONE ``expert_chunk`` call a traced
    body, the column's counters the pairs and the rows fed (whole tiles a
    run), the logits the reference's."""
    from dllama_tpu.models import axk1, llama
    from dllama_tpu.ops import expert_chunk as ec

    cfg, T, calls = engine.cfg, 40, []
    grouped = ec.expert_chunk
    monkeypatch.setattr(ec, "expert_chunk", lambda *a, **kw: calls.append(kw["rows_out"]) or grouped(*a, **kw))
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    tokens = _tokens(T)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), axk1.LatentColumn.zeros(cfg, jnp.float32))
    fed_bound = ec.fed_rows(T * min(cfg.n_active_experts, cfg.n_experts), cfg.n_experts)
    assert calls == [fed_bound, fed_bound, T]                  # gate and up into the fed layout, down back to the rows
    assert float(np.abs(np.asarray(logits[0]) - _reference_logits(bench, engine.params, tokens)).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)
    assert stats[0] <= stats[2] <= stats[0] + 3 * cfg.n_experts * (ec.TILE_ROWS - 1) and stats[2] % ec.TILE_ROWS == 0


@pytest.mark.parametrize("variant", ["nogroups", "bf16router", "nomscale", "norope", "nocnorm", "noshared", "latent8"])
def test_each_variant_of_the_reference_is_another_function(bench, engine, variant):
    """What the cell's controls break in the reference moves the logits far
    past ``LOGIT_TOL``: the checks can see each of them."""
    tokens = _tokens(120, seed=11)
    honest = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(_reference_logits(bench, engine.params, tokens, variant=variant) - honest).max()) > 100 * LOGIT_TOL


def _decode(gen, slots, n_steps):
    """Greedy decode of ``slots`` by hand over the generator's own pool, one
    step program a token, keeping the logits: what ``PagedGenerator.step``
    dispatches, less the sampler."""
    from dllama_tpu.models import llama

    step = jax.jit(lambda params, *args: llama.paged_forward(params, gen.cfg, *args))   # its own trace cache
    rows = {s: [] for s in slots}
    for _ in range(n_steps):
        for s in slots:
            gen._ensure_blocks(s, int(gen.pos[s]))
        logits, (gen.pkv, gen.moe_stats) = step(
            gen.eng.params, jnp.asarray(gen.next_token[:, None], jnp.int32), jnp.asarray(gen.pos, jnp.int32),
            (gen.pkv, gen.moe_stats), jnp.asarray(gen.tables))
        for s in slots:
            rows[s].append(np.asarray(logits[s, 0]))
            gen.next_token[s] = int(rows[s][-1].argmax())
            gen.pos[s] += 1
    return {s: np.stack(r) for s, r in rows.items()}


# 20: one padded chunk; 70: a chunk of 64 and a padded one; 300: a 256-token chunk and two more; 257: exactly one
# widest chunk. 40 decode steps cross two block boundaries. kernel "fused": the steps' attention through
# mla_paged_step and the routed feed-forward through expert_gemv, both in interpret mode off a TPU, a dead slot
# with a stale depth beside the live one.
@pytest.mark.parametrize("n_prompt,kernel", [(20, None), (70, None), (300, None), (257, None),
                                             (70, "fused"), (300, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops import mla
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"attention": 0, "experts": 0}
    entry, gemv = mla.mla_paged_step, eg.expert_gemv
    monkeypatch.setattr(mla, "mla_paged_step",
                        lambda *a, **kw: calls.__setitem__("attention", calls["attention"] + 1) or entry(*a, **kw))
    monkeypatch.setattr(eg, "expert_gemv",
                        lambda *a, **kw: calls.__setitem__("experts", calls["experts"] + 1) or gemv(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    assert gen.pkv.v is None and gen.pkv.k.shape == (4, 2 * 32 + 1, 1, 16, 128)      # ONE pool, one row a token
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 40
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # traced once: one layer body's walk, one routed body of three GEMVs
    assert (calls["attention"], calls["experts"]) == ((1, 3) if kernel else (0, 0))
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)
    # the pool holds [c | k_r | 0] and nothing per head: the padding lanes stay zero
    assert not np.asarray(gen.pkv.k[..., 40:]).any()


def test_a_later_turn_behind_matched_blocks_is_the_same_prompt_run_cold(bench, engine):
    """A session's second turn (the first turn's prompt, its answer, new
    tokens) finds the first turn's PROMPT in the index: its whole blocks are
    shared and the partly filled one copied (copy-on-write), the chunks
    attend over the gathered latent rows as they lie, and the logits are those
    of the same prompt on a generator that has never seen it, and the
    reference's. ``cfg.prefix_reuse_skipped`` is None: the list of blocks is
    one, by token range."""
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    assert engine.cfg.prefix_reuse_skipped is None and engine.cfg.paged_only
    warm = PagedGenerator(engine, n_slots=2)
    first = _tokens(75, seed=31)
    warm.admit(Request(rid=1, prompt_ids=first, max_tokens=10, stop_on_eos=False), 0)
    answer = _decode(warm, [0], 10)[0].argmax(axis=1).tolist()
    warm._retire(0)
    second = first + answer + _tokens(50, seed=32)
    matched0 = warm.prefix_totals()[0]
    warm.admit(Request(rid=2, prompt_ids=second, max_tokens=12, stop_on_eos=False), 1)
    assert warm.prefix_totals()[0] - matched0 == 74          # the first prompt less its last token: 4 blocks and 10 rows
    assert warm._n_shared[1] == 4
    got = _decode(warm, [1], 12)[1]
    cold = PagedGenerator(engine, n_slots=2)
    cold.admit(Request(rid=3, prompt_ids=second, max_tokens=12, stop_on_eos=False), 1)
    assert cold.prefix_totals()[0] == 0
    alone = _decode(cold, [1], 12)[1]
    assert float(np.abs(got - alone).max()) < 1e-5
    emitted = got.argmax(axis=1).tolist()
    want = _reference_logits(bench, engine.params, second + emitted)[len(second) - 1:len(second) - 1 + 12]
    assert float(np.abs(got - want).max()) < LOGIT_TOL


# -- the shares add up ---------------------------------------------------------------


@pytest.fixture(scope="module")
def uncut(bench, tmp_path_factory):
    """The same tiny model with all 16 experts held: what the deployment's
    chips hold between them."""
    model = dict(bench["model"], n_routed_experts=16, first_expert=0)
    eng = _engine(bench, tmp_path_factory.mktemp("axk1-uncut"), model=model, seed=11)
    yield eng, model
    eng.close()


def test_the_eight_shares_add_up_to_the_uncut_layer(bench, uncut):
    """Eight chips hold 2 of the 16 experts each (a router group of 4 lies on
    two chips, as A.X-K1's group of 24 lies on two of its 16). Each computes
    its own experts' part for the pairs routed to them; the shared expert is
    every chip's alike and is counted ONCE. Their sum is the uncut reference's
    routed layer, for every routed layer; every pair is held by exactly one
    share. The leading dense layer is every chip's alike."""
    import dataclasses

    from dllama_tpu.models import share as share_mod

    eng, model = uncut
    cfg, ref = eng.cfg, bench["reference"]
    lp, tree = eng.params.layers, ref.layer_tree(eng.params)
    T = 48
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, cfg.dim), jnp.float32)
    live = jnp.ones((T,), bool)
    at = lambda t, i: jax.tree.map(lambda a: a[i], t)
    for m in range(cfg.n_moe_layers):
        with jax.default_matmul_precision("highest"):
            want = ref.routed_ffn(model, x[0], at(tree["routed"], m), "none")
        routed, held = [], 0
        for s in range(8):
            c = dataclasses.replace(cfg, n_experts=2, moe_first_expert=2 * s)
            part = lp._replace(ws1=None, ws2=None, ws3=None,
                               **{n: jax.tree.map(lambda a: a[:, 2 * s:2 * s + 2], getattr(lp, n))
                                  for n in ("we1", "we2", "we3")})
            y, stats = share_mod.routed_ffn(c, x, part, jnp.int32(m), live)
            routed.append(y)
            held += int(stats[0])
            assert int(stats[0]) + int(stats[1]) == T * 4              # every pair is held or absent, once
        plane = lambda w: jax.tree.map(lambda a: a[m], w)
        shared = share_mod.swiglu(cfg, x, plane(lp.ws1), plane(lp.ws2), plane(lp.ws3))          # counted once
        assert held == T * 4                                          # the eight shares hold every pair between them
        assert float(jnp.abs(sum(routed) + shared - want[None]).max()) < SHARE_TOL, m
        assert float(jnp.abs(routed[0]).max()) > 0.05
    h0 = dense_reference._rms_norm(x, lp.norm_ffn[0], cfg.norm_epsilon)
    got, stats = share_mod.ffn_half(cfg, x, lp, jnp.int32(0), live, may_be_dense=True)
    with jax.default_matmul_precision("highest"):
        want0 = dense_reference.swiglu(h0[0], *(at(tree["dense"], 0)[n] for n in ("w1", "w2", "w3")))
    assert float(jnp.abs(got - x - want0[None]).max()) < SHARE_TOL and int(stats.sum()) == 0


# -- the routed kernel, striped ----------------------------------------------------------


@pytest.mark.parametrize("K,N,stripe_fast", [(7168, 2048, 512), (2048, 7168, 1792), (3072, 1024, 1024), (1024, 3072, 3072)])
def test_expert_gemv_striped_against_its_xla_form(K, N, stripe_fast):
    """A.X-K1's expert planes (7168 x 2048 and its transpose) do not fit VMEM
    whole and are walked in stripes of output columns; laguna's (3072 x 1024)
    fit and stay one stripe, the kernel it was. Interpret mode, exact float32:
    the kernel against ``expert_gemv_xla`` over the same planes, three held
    pairs of five (the tail is not computed)."""
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops.linear import QuantizedWeight

    assert eg.stripe(128, K, N, True) == stripe_fast and eg.supports(128, K, N, True)
    tn = eg.stripe(5, K, N, False, compiled=False)
    assert tn is not None and N % tn == 0 and (tn < N) == (K * N > 3072 * 1024 * 2)
    rng = np.random.default_rng(K)
    stack = QuantizedWeight(scales=jnp.asarray(rng.uniform(0.01, 0.03, (2, 3, K // 32, N)).astype(np.float32)),
                            codes=jnp.asarray(rng.integers(-8, 8, (2, 3, K, N), dtype=np.int8)))
    x = jnp.asarray(rng.normal(size=(5, K)).astype(np.float32))
    experts, n = jnp.asarray([2, 0, 1, 1, 2], jnp.int32), jnp.int32(3)
    got = eg.expert_gemv(x, stack, jnp.int32(1), experts, n, interpret=True, fast=False)
    want = eg.expert_gemv_xla(x, stack, jnp.int32(1), experts, n, fast=False)
    assert got.shape == (5, N)
    err = float(jnp.abs(got[:3] - want[:3]).max())
    assert err < 1e-3 * float(jnp.abs(want[:3]).max()) and float(jnp.abs(want[:3]).max()) > 1.0
    assert not np.asarray(got[3:]).any()


def _pairs_by_hand(cfg, lp, x, local, weights, m):
    """The chunk form's sum written pair by pair on the host: for each (row,
    held expert) pair, the expert's SwiGLU over that one row, float32."""
    from dllama_tpu.models import share
    from dllama_tpu.ops.linear import LayerSlice, QuantizedWeight

    E, k = cfg.n_experts, weights.shape[1]
    flat = lambda we: QuantizedWeight(*(a.reshape((-1,) + a.shape[2:]) for a in we))
    f1, f2, f3 = flat(lp.we1), flat(lp.we2), flat(lp.we3)
    want = np.zeros(x.shape, np.float32)
    for p, e in enumerate(np.asarray(local)):
        if e < E:
            i = jnp.int32(int(m) * E + int(e))
            row = share.swiglu(cfg, x[p // k][None], LayerSlice(f1, i), LayerSlice(f2, i), LayerSlice(f3, i))[0]
            want[p // k] += float(weights[p // k, p % k]) * np.asarray(row, np.float32)
    return want


@pytest.mark.parametrize("n_live", [35, 3, 40])
def test_the_chunk_form_is_its_pairs_summed(engine, n_live):
    """``share._experts_chunk`` (every chosen held expert over every row, a
    plane read where it lies in the flattened stack, the rows that did not
    choose it weighted 0) against the same pairs computed one at a time, on
    the tiny model's second routed layer: 40 rows of which some are padding,
    so some held experts get no row (with 3 live rows, most of them)."""
    from dllama_tpu.models import share

    cfg, lp = engine.cfg, engine.params.layers
    x = jnp.asarray(np.random.default_rng(3).normal(size=(40, cfg.dim)).astype(np.float32))
    live = jnp.arange(40) < n_live
    m = jnp.int32(1)

    def form(x, live, m):
        weights, idx = share.route(cfg, x, lp.moe_gate[m])
        local, _stats = share.routed_pairs(cfg, idx, live)
        return share._experts_chunk(cfg, x, local, weights, m, lp)[0], local, weights

    got, local, weights = jax.jit(form)(x, live, m)
    want = _pairs_by_hand(cfg, lp, x, local, weights, m)
    assert np.abs(want).max() > 0.1 and np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    assert not np.asarray(got[n_live:]).any()                                  # padding rows are not routed
    assert (np.asarray(local) < cfg.n_experts).sum() > (0 if n_live == 3 else 8)


def test_a_chunk_of_the_windowed_decoder_takes_the_same_form(monkeypatch):
    """One chunk form for both clients of the share: whatever the plane's
    size, ``routed_ffn`` sends more rows than a step's to ``_experts_chunk``
    and a step's rows to ``_experts_step``."""
    import types

    from dllama_tpu.models import share
    from dllama_tpu.ops.quant_matmul import FUSED_MAX_M

    seen = []
    monkeypatch.setattr(share, "_experts_chunk", lambda cfg, x, *a: seen.append(("chunk", x.shape[0])) or (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    monkeypatch.setattr(share, "_experts_step", lambda cfg, x, *a: seen.append(("step", x.shape[0])) or jnp.zeros(x.shape, jnp.float32))
    cfg = types.SimpleNamespace(n_experts=4, moe_first_expert=0, n_active_experts=2, moe_n_group=0, moe_score="softmax",
                                moe_norm_topk=True, moe_routed_scale=1.0, moe_norm_eps=0.0,
                                moe_router_width=192)     # ``share.step_form`` (PR 54): 16 rows x 2 do not outnumber 192
    lp = types.SimpleNamespace(moe_gate=jnp.ones((1, 8, 16), jnp.float32), ws1=None)
    for rows in (FUSED_MAX_M, FUSED_MAX_M + 1, 256):
        share.routed_ffn(cfg, jnp.ones((1, rows, 16), jnp.float32), lp, jnp.int32(0), jnp.ones((rows,), bool))
    assert seen == [("step", FUSED_MAX_M), ("chunk", FUSED_MAX_M + 1), ("chunk", 256)]


# -- the scheduler, the index and the counters -------------------------------------------


def test_scheduler_serves_sessions_through_the_latent_pool_and_counts(bench, engine, tmp_path):
    """Through ``BatchScheduler``: interleaved requests finish; a second turn
    reuses the first turn's prompt blocks (counted, not skipped); the routing
    counters reach the registry; while a profiler listens ``step_wait``
    carries ``mla_walk_blocks`` and the prefix totals, ``admit_begin`` its own
    admissions' matched and prompt tokens, ``admit_commit`` the latent bytes
    written, and the benchmark's readers read them."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    pairs, reused = reg.counter(telemetry.MOE_PAIRS), reg.counter(telemetry.PREFIX_REUSE_TOKENS)
    skipped = reg.counter(telemetry.PREFIX_REUSE_SKIPPED)
    held0, absent0, reused0 = pairs.total(where="held"), pairs.total(where="absent"), reused.total()
    skip0 = skipped.total()
    sched = BatchScheduler(engine, n_slots=3)
    try:
        prompts = [_tokens(n, seed=n) for n in (90, 33, 150)]
        reqs = [sched.submit(p, 12, stop_on_eos=False) for p in prompts]
        for r in reqs:
            assert r.done.wait(300) and not r.error
        turn = prompts[0] + list(reqs[0].tokens) + _tokens(40, seed=41)
        again = sched.submit(turn, 12, stop_on_eos=False)
        assert again.done.wait(300) and not again.error
        assert reused.total() - reused0 == 89 and skipped.total() == skip0            # the first prompt less its last token
        held, absent = pairs.total(where="held") - held0, pairs.total(where="absent") - absent0
        computed = sum(len(p) - 1 + 12 for p in prompts) + (len(turn) - 1 - 89) + 12   # prefilled + decoded positions
        assert held + absent == computed * 4 * 3
        assert reg.gauge(telemetry.LAYER_KINDS).value(kind="latent") == 4 and reg.gauge(telemetry.LAYER_KINDS).value(kind="full") == 0
        want = _reference_logits(bench, engine.params, turn + list(again.tokens))
        assert [int(r.argmax()) for r in want[len(turn) - 1:-1]] == list(again.tokens)
        import program_spans        # benchmark/program_spans.py
        sys.path.insert(0, os.path.join(BENCH, "readers"))
        try:
            counters, walk = (_import(n, os.path.join(BENCH, "readers", n + ".py"))
                              for n in ("slice_counters", "latent_walk_roofline"))
        finally:
            sys.path.remove(os.path.join(BENCH, "readers"))
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            third = turn + list(again.tokens) + _tokens(30, seed=42)
            traced = sched.submit(third, 20, stop_on_eos=False)
            assert traced.done.wait(300) and not traced.error
        spans = program_spans.load(program_spans.newest_trace(trace_dir))
        children = [(name, st) for t in spans["ticks"] for name, _s, _e, st in t["children"]]
        steps = [st for name, st in children if name == "step_wait" and "mla_walk_blocks" in st]
        assert len(steps) == 20 and all("moe_pairs" in st and "prefix_tokens" in st for st in steps)
        # one live row at depth len(third) - 1 + i walks ceil((depth + 1) / 16) blocks
        assert [int(st["mla_walk_blocks"]) for st in steps] == [-(-(len(third) + i) // 16) for i in range(20)]
        begun = [st for name, st in children if name == "admit_begin" and int(st.get("admitted", 0))]
        assert len(begun) == 1 and int(begun[0]["prefix_tokens"]) == len(turn) - 1 and int(begun[0]["prompt_tokens"]) == len(third) - 1
        commits = [st for name, st in children if name == "admit_commit" and "latent_bytes" in st]
        own_blocks = -(-(len(third) - 1) // 16) - (len(turn) - 1) // 16
        assert len(commits) == 1 and int(commits[0]["latent_bytes"]) == own_blocks * 4 * 16 * 128 * 4
        ctx = {"trace": {"device_ops": [("paged_sampled_step_guarded/mla_paged_step.1 custom-call", 0.002)]},
               "program_spans": spans, "counts": bench["counts"], "model": bench["model"],
               "conf": {"engine": {"kv_block_size": 16}}, "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}
        tokens = sum(int(st["mla_walk_blocks"]) for st in steps) * 16 * 4
        args = {"kernel": "mla_paged_step", "program": "paged_sampled_step_guarded"}
        assert abs(walk.read(ctx, roof="hbm", **args) - 100.0 * tokens * 40 * 2 / 819e9 / 0.002) < 1e-9
        assert abs(walk.read(ctx, roof="mxu", **args) - 100.0 * tokens * 2 * 4 * (40 + 32) / 197e12 / 0.002) < 1e-9
        # the slice's steps all ran after its one admission: nothing was added between the first and the last
        hit = {"what": "ratio", "over": ["prefix_tokens"], "under": ["prompt_tokens"], "scale": 100.0}
        assert counters.read(ctx, **hit) is None
    finally:
        sched.close()
    assert sched.gen.pool.used_blocks() == 0


def test_the_new_readers_on_worked_numbers_and_nothing_from_a_parent(bench):
    """``latent_walk_roofline`` and the ``prefix_hit_share`` spec on numbers
    worked by hand at the real configuration's sizes, and ``None``, not an
    error, where the program has no such span, total or kernel."""
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    try:
        walk, counters, chunk = (_import(n, os.path.join(BENCH, "readers", n + ".py"))
                                 for n in ("latent_walk_roofline", "slice_counters", "latent_chunk_roofline"))
    finally:
        sys.path.remove(os.path.join(BENCH, "readers"))
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    model = bench_run.model_view(conf)
    tick = lambda st: {"children": [("step_dispatch", 0.0, 0.001, {}), ("step_wait", 0.001, 0.006, st)]}
    ctx = {"trace": {"device_ops": [("paged_sampled_step_guarded/mla_paged_step.3 custom-call", 0.060),
                                    ("paged_sampled_step_guarded/expert_gemv.9 custom-call", 0.5),
                                    ("forward/mla_paged_step.1 custom-call", 9.0)]},
           "counts": bench["counts"], "model": model, "conf": conf,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
           "program_spans": {"ticks": [tick({"mla_walk_blocks": "11000"}), tick({"mla_walk_blocks": 11016}), tick({})]}}
    args = {"kernel": "mla_paged_step", "program": "paged_sampled_step_guarded"}
    tokens = 22016 * 16 * 9                       # blocks x 16 tokens x 9 layers
    hbm, mxu = walk.read(ctx, roof="hbm", **args), walk.read(ctx, roof="mxu", **args)
    assert abs(hbm - 100.0 * tokens * 1152 / 819e9 / 0.060) < 1e-9 and 7 < hbm < 8          # 3.65 GB in 60 ms: 7.4%
    assert abs(mxu - 100.0 * tokens * 2 * 64 * 1088 / 197e12 / 0.060) < 1e-9
    assert abs(mxu / hbm - (2 * 64 * 1088 / 1152) / (197e12 / 819e9)) < 1e-9                  # 121 FLOP/B against a ridge of 240
    for broken in (dict(ctx, program_spans={"ticks": [tick({"moe_pairs": 3})]}), dict(ctx, trace=None),
                   dict(ctx, program_spans=None), dict(ctx, counts=dense_reference),
                   dict(ctx, trace={"device_ops": [("forward/mla_paged_step.1 custom-call", 9.0)]})):
        assert walk.read(broken, roof="hbm", **args) is None
    # two chunks of 256 at depths 10240 and 0 and one of 64 at 300, in 9 layers; a call that only paged blocks in
    # (bucket 0) and a parent's span (no start) count nothing
    disp = lambda st: {"children": [("prefill_dispatch", 0.0, 0.001, st)]}
    cctx = dict(ctx, trace={"device_ops": [("forward/mla_chunk.2 custom-call", 0.050), ("forward/quant_matmul.1 custom-call", 0.2),
                                           ("paged_sampled_step_guarded/mla_chunk.9 custom-call", 3.0)]},
                program_spans={"ticks": [disp({"rid": 1, "tokens": 256, "bucket": 256, "start": 10240}),
                                         disp({"rid": 2, "tokens": 200, "bucket": 256, "start": "0"}),
                                         disp({"rid": 2, "tokens": 40, "bucket": 64, "start": 300}),
                                         disp({"rid": 3, "tokens": 0, "bucket": 0, "start": 77}),
                                         disp({"rid": 4, "tokens": 256, "bucket": 256})]})
    with open(os.path.join(BENCH, "layer_metrics", "mla_chunk_mxu_share.json"), encoding="utf-8") as f:
        cspec = json.load(f)
    attended = 256 * 10240 + 256 * 257 / 2 + 256 * 257 / 2 + 64 * 300 + 64 * 65 / 2
    want = 100.0 * attended * model["num_hidden_layers"] * 2 * 64 * 1088 / 197e12 / 0.050
    assert cspec["reader"] == "latent_chunk_roofline" and abs(chunk.read(cctx, **cspec["args"]) - want) < 1e-9 and 30 < want < 40
    assert chunk.read(dict(cctx, program_spans={"ticks": [disp({"rid": 4, "tokens": 256, "bucket": 256})]}), **cspec["args"]) is None
    assert chunk.read(dict(cctx, trace=None), **cspec["args"]) is None and chunk.read(dict(cctx, counts=dense_reference), **cspec["args"]) is None
    totals = [{"prefix_tokens": 50000, "prompt_tokens": 90000}, {"prefix_tokens": "58000", "prompt_tokens": "100000"},
              {"prefix_tokens": 65400, "prompt_tokens": 110000}]
    sliced = {"trace": {}, "program_spans": {"ticks": [tick(st) for st in totals]}}
    with open(os.path.join(BENCH, "layer_metrics", "prefix_hit_share.json"), encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["reader"] == "slice_counters" and counters.read(sliced, **spec["args"]) == 77.0
    assert counters.read({"trace": {}, "program_spans": {"ticks": [tick({"moe_held": 1}), tick({"moe_held": 2})]}},
                         **spec["args"]) is None                                               # a parent's spans
    for name in ("mla_step_share", "mla_step_hbm_share", "mla_step_mxu_share"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            assert json.load(f)["args"]["kernel"] == bench["counts"].STEP_KERNEL
    with open(os.path.join(BENCH, "layer_metrics", "mla_chunk_share.json"), encoding="utf-8") as f:
        assert json.load(f)["args"] == {"kernel": "mla_chunk", "program": "forward", "share": "time"}


# -- what is refused, the header, the converter --------------------------------------


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_block_size": 0}, "--kv-block-size"),
    ({"kv_block_size": 0}, "the latent pool"),
    ({"spec_lookup": 3}, "--spec-lookup"),
    ({"kv_host_blocks": 32}, "--kv-host-blocks"),
    ({"kv_host_blocks": 32}, "kvwire export/ingest and mid-stream resume"),
    ({"tp": 2}, "--tp > 1"),
    ({"sp": 2}, "--sp > 1"),
    ({"pp": 2}, "--pp > 1"),
    ({"dp": 2}, "--dp > 1"),
    ({"weight_mode": "offload"}, "--weight-mode offload"),
    ({"numerics_taps": True}, "--numerics-taps"),
    ({"sync_type": 3}, "q80"),
])
def test_refused_at_construction_with_the_flag_named(bench, tmp_path, kwargs, named):
    with pytest.raises(ValueError, match="latent attention and an expert share") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_generator_refuses_what_has_no_construction_flag(engine):
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=1)
    with pytest.raises(ValueError, match="one plane of compressed rows"):
        gen.export_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="one plane of compressed rows"):
        gen.ingest_prefix([1, 2, 3], [])
    with pytest.raises(ValueError, match="latent column"):
        gen.begin_admit(Request(rid=1, prompt_ids=[1, 2, 3], max_tokens=1, score=True), 0)
    with pytest.raises(RuntimeError, match="BatchScheduler"):
        engine.prefill([1, 2, 3])


def test_header_round_trip_and_walk(bench, tmp_path):
    from dllama_tpu.formats.mfile import ArchType, ModelFile, RopeType
    from dllama_tpu.models.config import ModelConfig

    path = str(tmp_path / "tiny.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    with ModelFile.open(path, max_seq_len=512) as mf:
        h = mf.header
        assert (h.arch_type, h.rope_type) == (ArchType.AXK1, RopeType.YARN)
        assert (h.q_lora_rank, h.kv_lora_rank, h.qk_nope_head_dim, h.qk_rope_head_dim, h.v_head_dim, h.head_dim) \
            == (32, 32, 16, 8, 16, 24)
        assert (h.moe_n_group, h.moe_topk_group, h.moe_score_func, h.yarn_mscale, h.yarn_mscale_all_dim) == (4, 2, 1, 1.0, 1.0)
        assert (h.n_experts, h.moe_router_width, h.moe_first_expert, h.n_active_experts) == (8, 16, 4, 4)
        assert (h.n_dense_layers, h.dense_hidden_dim, h.hidden_dim, h.shared_expert_dim) == (1, 128, 32, 32)
        assert h.moe_routed_scale_milli == 2500 and h.rope_scaling_factor == 4.0
        t = mf.tensors
        assert t["block_mla_dq.0"].shape == (32, 64) and t["block_mla_uq.1"].shape == (4 * 24, 32)
        assert t["block_mla_dkv.2"].shape == (40, 64) and t["block_mla_ukv.3"].shape == (4 * 32, 32)
        assert t["block_matmul_wo.0"].shape == (64, 64) and t["block_mla_norm_kv.1"].shape == (32,)
        assert "block_matmul_w1.0" in t and "block_moe_gate.0" not in t and "block_expert_w1.1.7" in t
        assert "block_expert_w1.1.8" not in t and t["block_shared_w2.3"].shape == (64, 32)
        cfg = ModelConfig.from_header(h, "float32")
    assert cfg.has_latent_cache and cfg.has_expert_share and cfg.paged_only and not cfg.has_window_layers and cfg.is_moe
    assert (cfg.n_kv_layers, cfg.n_moe_layers, cfg.latent_dim, cfg.latent_row) == (4, 3, 40, 128)
    assert cfg.moe_routed_scale == 2.5 and cfg.prefix_reuse_skipped is None and cfg.moe_score == "sigmoid"
    bad = dict(bench["model"], first_expert=12)             # 12 + 8 held runs past the router's 16
    bench["weights"].write_sparse_model(path, bad)
    with pytest.raises(ValueError, match="held of a router over 16"):
        ModelFile.open(path)
    bench["weights"].write_sparse_model(path, dict(bench["model"], n_group=3))
    with pytest.raises(ValueError, match="16 experts in 3 groups"):
        ModelFile.open(path)
    with pytest.raises(ValueError, match="models/axk1.py implements"):
        bench["weights"].write_sparse_model(path, dict(bench["model"], rope_pairing="interleaved"))


def test_converter_maps_the_config_and_says_it_has_no_tensor_map(tmp_path):
    from dllama_tpu.convert import hf
    from dllama_tpu.formats.mfile import ArchType, RopeType

    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    published.update(conf["reduced_from"])          # a whole checkpoint's config
    (tmp_path / "config.json").write_text(json.dumps(published))
    params = hf.load_hf_config(tmp_path, 2)
    assert params["arch_type"] == int(ArchType.AXK1) and params["rope_type"] == int(RopeType.YARN)
    assert (params["q_lora_rank"], params["kv_lora_rank"], params["qk_nope_head_dim"], params["qk_rope_head_dim"],
            params["v_head_dim"], params["head_dim"]) == (1536, 512, 128, 64, 128, 192)
    assert (params["hidden_dim"], params["dense_hidden_dim"], params["shared_expert_dim"]) == (2048, 18432, 2048)
    assert (params["moe_router_width"], params["n_experts"], params["n_active_experts"]) == (192, 192, 8)
    assert (params["moe_n_group"], params["moe_topk_group"], params["moe_score_func"]) == (8, 4, 1)
    assert (params["rope_theta"], params["rope_scaling_factor"], params["rope_scaling_orig_max_seq_len"]) == (10000, 32, 4096)
    assert params["moe_routed_scale_milli"] == 2500 and params["n_dense_layers"] == 1 and params["n_layers"] == 61
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf.hf_tensor_plan(params)
    (tmp_path / "config.json").write_text(json.dumps(dict(published, topk_method="noaux_tc")))
    with pytest.raises(ValueError, match="score-correction bias"):
        hf.load_hf_config(tmp_path, 2)


def test_the_cell_configuration_is_the_issue_reckoning(bench):
    """The real configuration against the catalog's widths and the issue's
    arithmetic: what is held, a cached token, a step's bytes."""
    with open(REAL, encoding="utf-8") as f:
        conf = json.load(f)
    model = bench_run.model_view(conf)
    assert conf["reduced"] == ["n_routed_experts", "vocab_size", "num_hidden_layers", "max_position_embeddings"]
    assert conf["reduced_from"] == {"n_routed_experts": 192, "vocab_size": 163840, "num_hidden_layers": 61,
                                    "max_position_embeddings": 131072}
    assert (model["hidden_size"], model["intermediate_size"], model["moe_intermediate_size"], model["q_lora_rank"],
            model["kv_lora_rank"], model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"],
            model["num_attention_heads"], model["num_experts_per_tok"], model["n_group"], model["topk_group"]) \
        == (7168, 18432, 2048, 1536, 512, 128, 64, 128, 64, 8, 8, 4)
    assert (model["n_routed_experts"], model["router_width"], model["first_expert"], model["vocab_size"],
            model["num_hidden_layers"]) == (12, 192, 0, 20480, 9)
    assert "topk_method" in conf["assumed"] and "16 chips" in conf["deployment"] and "DATA-parallel" in conf["deployment"]
    c = bench["counts"]
    expert = 3 * 7168 * 2048
    assert c.kernel_counts(model, "expert_gemv", rows=16)["bytes"] == expert * 1.0625            # 46.8 MB a pair
    step = c.kernel_counts(model, "mla_paged_step", rows=16)
    assert (step["bytes"], step["flops"], step["layers"]) == (1152.0, 2.0 * 64 * 1088, 9)
    assert c.kernel_counts(model, "no_such_kernel", rows=16) is None
    assert abs(c.pairs_held(model, 16) - 8.0) < 1e-9 and 5.5 < c.experts_touched(model, 16) < 6.5
    attention = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 + 8192 * 7168
    assert 101.0e6 < attention < 101.2e6                                                         # the issue's 101.1 M
    held = c.always_read_weights(model) + 8 * 12 * expert + 9 * 512 * 64 * 256
    assert 6.4e9 < held * 1.0625 + 2 * 20480 * 7168 * 2 < 7.2e9                                   # 7.0 GB of weights in 9 layers
    # a step at 16 rows x 11k reads 1.8 GB of latent rows (2.0 in the issue's 10 layers) beside the weights it touches
    latent = c.decode_step_bytes(model, rows=16, context_tokens=16 * 11000) - c.decode_step_bytes(model, rows=16, context_tokens=0)
    assert abs(latent - 9 * 1152 * 16 * 11000) < 1.0 and 1.7e9 < latent < 1.9e9
    assert 3.6e9 < c.decode_step_bytes(model, rows=16, context_tokens=0) < 4.9e9
    # a 256-token chunk at depth 12k: 4.0 TFLOP of latent attention against 0.9 of matmuls (4.4 and 1.0 in 10 layers)
    deep = c.prefill_chunk_flops(model, chunk=256, context_before=12000)
    flat = c.prefill_chunk_flops(model, chunk=256, context_before=0)
    assert 3.6e12 < deep - flat < 4.2e12 and 0.7e12 < flat < 1.2e12


# -- the pool check: the third limit of ``correct`` -----------------------------------


@pytest.mark.parametrize("variant, low, high", [("none", 0.0, 1e-5), ("latent8", 0.02, 0.05), ("nocnorm", 0.3, 2.0)])
def test_the_pool_check_reads_the_rows_the_server_holds(bench, engine, variant, low, high):
    """``reference.pool_rows_gap`` finds the generator that serves the
    engine's params, the blocks of a finished request's prompt through the
    prefix index, and holds the pool's rows to the reference's ``[c | k_r]``:
    float32 rounding for the honest reference, some 3% where the reference
    rounds them to 8 bits (e4m3 keeps 3 bits of mantissa: a relative step of
    2^-4 at most), far more where a term is wrong; and its entry of ``gap``
    is over the tolerance exactly where the distance is over its limit."""
    from dllama_tpu.runtime.serving import BatchScheduler

    ref, model = bench["reference"], bench["model"]
    prompt = _tokens(100, seed=51)
    padded = -(-(len(prompt) + 3) // dense_reference.BLOCK_Q) * dense_reference.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:len(prompt)] = prompt
    _x, rows = ref._stack_fn(json.dumps(model, sort_keys=True), variant)(
        jnp.asarray(ids), engine.params.embedding, ref.layer_tree(engine.params),
        *dense_reference.control_handles(model["num_hidden_layers"], len(prompt), padded, "none"))
    assert rows.shape == (4, padded, 40)
    ref._served.update(of=None, gens=[])
    sched = BatchScheduler(engine, n_slots=2)
    try:
        req = sched.submit(prompt, 4, stop_on_eos=False)
        assert req.done.wait(300) and not req.error
        ref._served.update(of=None, gens=[])
        gap = ref.pool_rows_gap(engine.params, prompt, rows)
        assert gap.shape == (4, 96)                  # the prompt less its last token: 6 whole blocks in the index
        assert low <= float(np.quantile(gap, 0.25)) <= high
        entry = ref.pool_entry(engine.params, prompt, rows, "float32")
        assert (entry > ref.tolerance("float32")) is (variant != "none")
        assert ref.pool_rows_gap(engine.params, _tokens(100, seed=52), rows) is None      # a prompt the index never saw
    finally:
        sched.close()
        ref._served.update(of=None, gens=[])


# -- the benchmark's seam, seen by tier-1 ------------------------------------------


@pytest.mark.parametrize("control, correct", [("none", True), ("shift", False), ("droplayer", False),
                                              ("dropblock", False), ("nogroups", False), ("bf16router", False),
                                              ("nomscale", False), ("norope", False), ("nocnorm", False),
                                              ("noshared", False), ("latent8", False)])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own (sessions of three turns behind one
    shared prompt, so later turns find matched latent blocks): ``correct``
    true, and false under each control the reference knows."""
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-a.x-k1.sessions", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "5", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]
