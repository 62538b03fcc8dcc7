"""The decoder of window and full attention layers with an expert share
(``ArchType.LAGUNA``, ``models/laguna.py``; two block pools a sequence,
``runtime/serving.py``) against its plain reference
(``benchmark/laguna/reference.py``, imported from where it lies, no copy), at a
tiny size on the CPU: hidden 64, heads of 32 (16 / 24 query heads over 8 K/V
heads), 8 layers = two periods of [full, sliding x 3], window 32, a leading
dense layer, 16 routed experts of which 8 are held (from the 4th), 4 a token,
a shared expert, vocabulary 256, float32, seeded weights from the benchmark's
own maker (``benchmark/laguna/weights.py``), so program and reference read the
same Q40 planes.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference
  compute the same float32 function with their sums in another order (a
  grouped matmul over sorted pairs against every expert weighted, a paged walk
  against a dense mask); the worst seen is 4e-5. The same model computed in
  bfloat16 where float32 is stated reads 0.02 and more
  (``test_bfloat16_where_float32_is_stated_fails``), a window off by one
  position or an expert of the wrong share 0.1 and more.
* ``SHARE_TOL`` 2e-4 on a layer's output of spread 1-3: sixteen partial sums
  added in another order than the uncut layer's one sum.
"""

import dataclasses
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
LAGUNA = os.path.join(BENCH, "laguna")
TINY = os.path.join(LAGUNA, "selftest", "configs", "tiny-laguna.json")
MANIFEST = os.path.join(LAGUNA, "selftest", "manifest.json")
LOGIT_TOL, SHARE_TOL = 2e-3, 2e-4


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)           # as run.py puts it, and as benchmark/selftest/test_*.py do
import reference as dense_reference  # noqa: E402
import run as bench_run  # noqa: E402
import weights as dense_weights  # noqa: E402


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
    return {"weights": _import("laguna_weights", os.path.join(LAGUNA, "weights.py")),
            "reference": _import("laguna_reference", os.path.join(LAGUNA, "reference.py")),
            "counts": _import("laguna_counts", os.path.join(LAGUNA, "counts.py")),
            "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, dtype="float32", model=None, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-laguna.m")
    bench["weights"].write_sparse_model(path, model or bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype=dtype, **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("laguna"))
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


# -- the rotary tables ---------------------------------------------------------


@pytest.mark.parametrize("pos", [1, 700, 4000])
def test_yarn_table_is_the_formula(pos):
    """The full layers' table at Laguna-S-2.1's own numbers against the
    issue's formula, worked here in float64: theta 500000 over 64 rotating
    lanes, factor 128 over 8192, beta 32 / 1, cos and sin times 0.1 ln 128 +
    1 = 1.4852030263919618 (the config's own ``attention_factor``)."""
    import math

    from dllama_tpu.models import rope

    r, theta, factor, orig = 64, 500000.0, 128.0, 8192
    i = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2 * i / r)
    dim = lambda n: r * math.log(orig / (2 * math.pi * n)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), r - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    inv = (e / factor) * ramp + e * (1 - ramp)
    assert (low, high) == (9, 18) and rope.yarn_attention_factor(factor) == 1.4852030263919618
    cos, sin = rope.build_partial_rope_cache(4096, r, theta, (factor, orig, 32.0, 1.0))
    assert cos.shape == (4096, 32)
    # float32 angles: pos * inv rounds at 2**-24 of up to 4000 radians
    np.testing.assert_allclose(cos[pos], np.cos(pos * inv) * 1.4852030263919618, atol=6e-4)
    np.testing.assert_allclose(sin[pos], np.sin(pos * inv) * 1.4852030263919618, atol=6e-4)
    # untouched above the band, divided by the factor below it
    np.testing.assert_allclose(inv[:10], e[:10])
    np.testing.assert_allclose(inv[18:], e[18:] / factor)


def test_partial_rotary_leaves_the_other_lanes():
    from dllama_tpu.models import rope

    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 2, 32)), jnp.float32)
    cos, sin = rope.build_partial_rope_cache(16, 16, 10000.0)
    y = rope.apply_rope_partial(x, cos, sin, jnp.asarray([[0, 5, 9]]))
    np.testing.assert_array_equal(np.asarray(y[..., 16:]), np.asarray(x[..., 16:]))
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-7)       # position 0: no turn
    assert np.abs(np.asarray(y[:, 1, :, :16] - x[:, 1, :, :16])).max() > 0.1
    # half-split pairing: lane j turns with lane j + 8
    j, c, s = 3, cos[5, 3], sin[5, 3]
    np.testing.assert_allclose(float(y[0, 1, 0, j]), float(x[0, 1, 0, j] * c - x[0, 1, 0, j + 8] * s), atol=1e-6)


# -- the model against the reference ---------------------------------------------


@pytest.mark.parametrize("T", [40, 300])
def test_whole_forward_logits(bench, engine, T):
    """One chunk over a dense column: 40 is under two windows, 300 past the
    window and a 256-token chunk; the routed layers run their chunk form."""
    from dllama_tpu.models import laguna, llama

    cfg = engine.cfg
    tokens = _tokens(T)
    col = laguna.LagunaColumn.zeros(cfg, jnp.float32)
    assert (col.k.shape[0], col.wk.shape[0]) == (2, 6) and (cfg.n_kv_layers, cfg.n_window_layers, cfg.n_moe_layers) == (2, 6, 7)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), col)
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)          # one chunk: a column carries its own counters, one row
    assert stats[0] + stats[1] == T * 4 * 7 and stats[4:].sum() == stats[0]      # every pair counted once
    assert 0.35 < stats[0] / (T * 4 * 7) < 0.65                                   # half the experts are held


def test_whole_forward_logits_through_the_grouped_kernel(bench, engine, monkeypatch):
    """The same chunk with the kernels forced (interpret mode off a TPU): each
    routed body's three projections ONE ``expert_chunk`` call (two bodies are
    traced: a period's full layer and its sliding ones), the column's
    counters the pairs and the rows fed (whole tiles a run), the logits the
    reference's."""
    from dllama_tpu.models import laguna, llama
    from dllama_tpu.ops import expert_chunk as ec

    cfg, T, calls = engine.cfg, 40, []
    grouped = ec.expert_chunk
    monkeypatch.setattr(ec, "expert_chunk", lambda *a, **kw: calls.append(kw["rows_out"]) or grouped(*a, **kw))
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    tokens = _tokens(T)
    logits, col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), laguna.LagunaColumn.zeros(cfg, jnp.float32))
    fed_bound = ec.fed_rows(T * min(cfg.n_active_experts, cfg.n_experts), cfg.n_experts)
    assert calls == [fed_bound, fed_bound, T] * 2              # gate and up into the fed layout, down back to the rows
    assert float(np.abs(np.asarray(logits[0]) - _reference_logits(bench, engine.params, tokens)).max()) < LOGIT_TOL
    stats = np.asarray(col.stats)
    assert stats[0] <= stats[2] <= stats[0] + 7 * cfg.n_experts * (ec.TILE_ROWS - 1) and stats[2] % ec.TILE_ROWS == 0


def _decode(gen, slots, n_steps):
    """Greedy decode of ``slots`` by hand over the generator's own pools, one
    step program a token, keeping the logits: what ``PagedGenerator.step``
    dispatches, less the sampler."""
    from dllama_tpu.models import llama

    step = jax.jit(lambda params, *args: llama.paged_forward(params, gen.cfg, *args))   # its own trace cache
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


# 20: under the window, one padded chunk; 70: a chunk of 64 and a padded one, two windows deep; 300: past the
# window AND a 256-token chunk (256, 32, 11 padded to 32); 257: exactly one widest chunk. 40 decode steps cross
# the window and two block boundaries, so blocks go back while the row decodes. kernel "fused": the steps'
# attention through paged_ragged_attention (window and full) and the routed feed-forward through expert_gemv,
# both in interpret mode off a TPU, a dead slot with a stale depth beside the live one.
@pytest.mark.parametrize("n_prompt,kernel", [(20, None), (70, None), (300, None), (257, None),
                                             (70, "fused"), (300, "fused")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import expert_gemv as eg
    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = {"attention": [], "experts": 0}
    entry, gemv = pa.paged_ragged_attention, eg.expert_gemv
    monkeypatch.setattr(pa, "paged_ragged_attention",
                        lambda *a, **kw: calls["attention"].append(kw.get("window")) or entry(*a, **kw))
    monkeypatch.setattr(eg, "expert_gemv",
                        lambda *a, **kw: calls.__setitem__("experts", calls["experts"] + 1) or gemv(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    n_steps = 40
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=n_steps, stop_on_eos=False), 1)
    got = _decode(gen, [1], n_steps)[1]
    emitted = got.argmax(axis=1).tolist()
    # traced once a program (the step, and the tick program of each of the two buckets either prompt pads to, whose
    # decode rows walk the pools too): one full layer's body, one sliding layer's; two routed bodies of three GEMVs
    # in the step ALONE (a tick program's routed half is the chunk form)
    assert (sorted(calls["attention"]), calls["experts"]) == (([0] * 3 + [32] * 3, 6) if kernel else ([], 0))
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt - 1 + n_steps]
    assert float(np.abs(got - want).max()) < LOGIT_TOL
    # the window pool holds the window and no more; the table's entries behind it are null
    first = (n_prompt - 1 + n_steps - 32 + 1) // 16
    assert sorted(gen._wbids[1]) == list(range(first, (n_prompt - 1 + n_steps - 1) // 16 + 1))
    assert not gen.wtables[1, :first].any() and gen.wtables[1, first] != 0
    assert len(gen._seq_bids[1]) == -(-(n_prompt - 1 + n_steps) // 16)          # the full pool keeps every block


def test_bfloat16_where_float32_is_stated_fails(bench, tmp_path):
    """The tolerance is tight enough that the lower precision fails it: the
    same weights served in bfloat16 compute read far over ``LOGIT_TOL``."""
    from dllama_tpu.models import laguna, llama

    eng = _engine(bench, tmp_path, dtype="bfloat16")
    try:
        tokens = _tokens(40)
        col = laguna.LagunaColumn.zeros(eng.cfg, jnp.bfloat16)
        logits, _ = jax.jit(lambda params, ids, col: llama.forward(params, eng.cfg, ids, jnp.int32(0), col))(
            eng.params, jnp.asarray([tokens], jnp.int32), col)
        want = _reference_logits(bench, eng.params, tokens)
        assert float(np.abs(np.asarray(logits[0]) - want).max()) > 10 * LOGIT_TOL
    finally:
        eng.close()


@pytest.mark.parametrize("rounded", ["rows", "product"])
def test_a_bfloat16_router_in_the_program_fails(bench, engine, monkeypatch, rounded):
    """The router is stated float32 and the weights make that visible
    (``benchmark/laguna/weights.py``, "How the router's rows are drawn", part
    2): the PROGRAM with its router's rows rounded to bfloat16, or its product
    taken as the MXU's default one-pass one, is no longer the reference's
    function, by the logits' tolerance and by the benchmark's own comparison."""
    from dllama_tpu.models import laguna, llama, share

    round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    honest = share.route

    def route(cfg, h, gate):
        return honest(cfg, round16(h.astype(jnp.float32)) if rounded == "product" else h, round16(gate))

    monkeypatch.setattr(share, "route", route)
    tokens = _tokens(96, seed=3)
    col = laguna.LagunaColumn.zeros(engine.cfg, jnp.float32)
    logits, _ = jax.jit(lambda params, ids, col: llama.forward(params, engine.cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), col)
    logits = np.asarray(logits[0])
    # what the benchmark's comparison sees at a position: the reference's logit of the program's token, under its best
    want = _reference_logits(bench, engine.params, tokens)
    gap = (want.max(axis=1) - want[np.arange(len(tokens)), logits.argmax(axis=1)]) / want.std(axis=1)
    assert float(np.abs(logits - want).max()) > 100 * LOGIT_TOL
    assert float(gap.max()) > 10 * bench["reference"].tolerance("float32")


# -- the share --------------------------------------------------------------------


@pytest.fixture(scope="module")
def uncut(bench, tmp_path_factory):
    """The tiny model with EVERY expert held (router width 16 = experts 16):
    cfg, params and the model as the reference sees it."""
    from dllama_tpu.formats.mfile import ModelFile
    from dllama_tpu.models.config import ModelConfig

    model = dict(bench["model"], num_experts=16, router_width=16, first_expert=0)
    path = str(tmp_path_factory.mktemp("uncut") / "uncut.m")
    bench["weights"].write_sparse_model(path, model)
    with ModelFile.open(path, max_seq_len=512) as mf:
        cfg = ModelConfig.from_header(mf.header, "float32")
    return cfg, dense_weights.device_params(cfg, None, 11, bench["weights"].params_builder), model


def test_the_eight_shares_add_up_to_the_uncut_layer(bench, uncut):
    """One test ties the share to the model: the routed parts of all 8 expert
    shares (2 of 16 each), the attention parts of all 8 head shares (1 K/V
    head and its 2 / 3 query heads each), and what every chip computes alike
    (the shared expert, the dense layer) counted ONCE, add up to the uncut
    reference's layer output, in a full layer and in a sliding one. The parts
    are the PROGRAM's (``models/laguna.py``'s routed feed-forward told which
    experts it holds, its attention half over sliced planes); the whole is the
    reference's."""
    from dllama_tpu.models import laguna
    from dllama_tpu.models import share as share_mod
    from dllama_tpu.ops.attention import attention
    from dllama_tpu.ops.linear import QuantizedWeight

    cfg, params, model = uncut
    ref, lp, hd = bench["reference"], params.layers, cfg.head_dim
    tree = ref.layer_tree(params)
    T = dense_reference.BLOCK_Q          # the reference attends in blocks of this many rows
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, T, cfg.dim)), jnp.float32)
    positions = jnp.arange(T)[None, :]
    hide = jnp.asarray((T, 0, 0), jnp.int32)
    at = lambda t, i: jax.tree.map(lambda a: a[i], t)
    t_full, t_slide = laguna.rope_tables(cfg)

    def cols(w, lo, hi):       # output features lo..hi of a stacked plane [N, in, out]
        return QuantizedWeight(scales=w.scales[..., lo:hi], codes=w.codes[..., lo:hi])

    def rows(w, lo, hi):       # input features lo..hi
        return QuantizedWeight(scales=w.scales[:, lo // 32:hi // 32], codes=w.codes[:, lo:hi])

    with jax.default_matmul_precision("highest"):
        for kind, stack, heads, table, window, l in (("full_attention", lp.full, cfg.n_heads, t_full, 0, 4),
                                                     ("sliding_attention", lp.slide, cfg.n_heads_sliding, t_slide, 32, 5)):
            i_stack = l // 4 if window == 0 else l - l // 4 - 1
            want_x1 = ref.attention_half(model, x[0], at(tree["full" if not window else "slide"], i_stack),
                                         positions[0], hide, kind, "none")
            G = heads // 8
            share_cfg = dataclasses.replace(cfg, n_heads=G, n_heads_sliding=G, n_kv_heads=1)
            parts = []
            for s in range(8):
                ap = laguna.AttnParams(
                    wq=cols(stack.wq, s * G * hd, (s + 1) * G * hd), wk=cols(stack.wk, s * hd, (s + 1) * hd),
                    wv=cols(stack.wv, s * hd, (s + 1) * hd), wo=rows(stack.wo, s * G * hd, (s + 1) * G * hd),
                    wg=stack.wg[:, s * G:(s + 1) * G], norm_att=stack.norm_att)
                attend = lambda q, k, v: attention(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), positions, hd,
                                                   window=window)
                parts.append(laguna._attention_half(share_cfg, x, at(ap, i_stack), G, table, positions, attend) - x)
            x1 = x + sum(parts)
            assert float(jnp.abs(x1[0] - want_x1).max()) < SHARE_TOL, kind

            m = l - 1
            h2 = dense_reference._rms_norm(x1, lp.norm_ffn[l], cfg.norm_epsilon)
            want = ref.routed_ffn(model, h2[0], at(tree["routed"], m), "none")
            live = jnp.ones((T,), bool)
            routed, held = [], 0
            for s in range(8):
                c = dataclasses.replace(cfg, n_experts=2, moe_first_expert=2 * s)
                share = lp._replace(ws1=None, ws2=None, ws3=None,
                                    **{n: jax.tree.map(lambda a: a[:, 2 * s:2 * s + 2], getattr(lp, n))
                                       for n in ("we1", "we2", "we3")})
                y, stats = share_mod.routed_ffn(c, h2, share, jnp.int32(m), live)
                routed.append(y)
                held += int(stats[0])
                assert int(stats[0]) + int(stats[1]) == T * 4          # every pair is held or absent, once
            shared = share_mod.swiglu(cfg, h2, at(lp.ws1, m), at(lp.ws2, m), at(lp.ws3, m))     # counted once
            assert held == T * 4                                        # the eight shares hold every pair between them
            assert float(jnp.abs(sum(routed) + shared - want[None]).max()) < SHARE_TOL, kind
        # the leading dense layer is every chip's alike: the program's once is the reference's
        h0 = dense_reference._rms_norm(x, lp.norm_ffn[0], cfg.norm_epsilon)
        got, stats = share_mod.ffn_half(cfg, x, lp, jnp.int32(0), live, may_be_dense=True)
        want0 = dense_reference.swiglu(h0[0], *(at(tree["dense"], 0)[n] for n in ("w1", "w2", "w3")))
        assert float(jnp.abs(got - x - want0[None]).max()) < SHARE_TOL and int(stats.sum()) == 0


def test_the_held_share_is_the_whole_layer_less_the_absent_experts(bench, engine, uncut):
    """The share as the benchmark holds it: half the experts. The program with
    8 of 16 held equals the reference given the same share, and both differ
    from the uncut layer by what the absent experts would have added."""
    tokens = _tokens(24, seed=3)
    share = _reference_logits(bench, engine.params, tokens)
    cfg, params, model = uncut
    whole = _reference_logits(bench, params, tokens, model=model)
    assert share.shape == whole.shape      # another seed's weights: only the shapes are compared here
    r = bench["reference"]
    h = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], r.layer_tree(params)["routed"])
    cut = {n: (jax.tree.map(lambda a: a[4:12], lp[n]) if n.startswith("we") else lp[n]) for n in lp}
    with jax.default_matmul_precision("highest"):
        y_whole = r.routed_ffn(model, h, lp, "none")
        y_cut = r.routed_ffn(dict(model, num_experts=8, first_expert=4), h, cut, "none")
        rest = r.routed_ffn(dict(model, num_experts=4, first_expert=0), h,
                            {n: (jax.tree.map(lambda a: a[:4], lp[n]) if n.startswith("we") else lp[n]) for n in lp},
                            "noshared") \
            + r.routed_ffn(dict(model, num_experts=4, first_expert=12), h,
                           {n: (jax.tree.map(lambda a: a[12:], lp[n]) if n.startswith("we") else lp[n]) for n in lp},
                           "noshared")
    assert float(jnp.abs(y_cut + rest - y_whole).max()) < SHARE_TOL
    assert float(jnp.abs(y_cut - y_whole).max()) > 0.05


# -- two pools: the allocator ------------------------------------------------------


def test_a_returned_block_is_reused_while_the_first_row_still_decodes(bench, engine):
    """Row A decodes past its window and gives blocks back; row B is admitted
    and takes them (parked, and the free list dry) while A still decodes; both
    rows' logits stay the reference's. After both retire, both pools' free counts
    are what they started at."""
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=2)
    free0, wfree0 = gen.pool.free_blocks(), gen.wpool.free_blocks()
    a, b = _tokens(60, seed=21), _tokens(45, seed=22)
    gen.admit(Request(rid=1, prompt_ids=a, max_tokens=64, stop_on_eos=False), 0)
    held_at_start = set(gen._wbids[0].values())
    # 21 steps write positions 59..79: at 64 the row gives its oldest block back and takes it again for the
    # block 64 opens; at 79 the next one falls behind the window, and nothing new is needed until 80
    got_a1 = _decode(gen, [0], 21)[0]
    returned = held_at_start - set(gen._wbids[0].values())
    assert returned and gen._m_wblocks_returned.total() >= len(returned)
    # they are the prompt's last window, which the commit registered: given back, they PARK (a session's next turn
    # would match them) and are what an allocation takes once the free list is dry
    assert returned <= set(gen.wpool._cached) and gen.wpool.cached_blocks() == len(returned)
    spare = [gen.wpool.alloc() for _ in range(len(gen.wpool._free) - 2)]     # B needs three, A one more at 80
    gen.admit(Request(rid=2, prompt_ids=b, max_tokens=64, stop_on_eos=False), 1)
    assert returned & set(gen._wbids[1].values())                 # B holds blocks A gave back
    for bid in spare:
        gen.wpool.release(bid)
    # A's row rode B's first chunk (the tick program, PR 57): one token of A's whose logits nobody kept, written at
    # position 80 into a block the window pool handed out after B had taken its own
    assert gen.take_rows_rode() and gen.pos[0] == 81
    rode = int(gen.next_token[0])
    both = _decode(gen, [0, 1], 20)
    emitted_a = got_a1.argmax(axis=1).tolist() + [rode] + both[0].argmax(axis=1).tolist()
    want = _reference_logits(bench, engine.params, a + emitted_a)[len(a) - 1:len(a) - 1 + 42]
    assert float(np.abs(got_a1 - want[:21]).max()) < LOGIT_TOL and int(want[21].argmax()) == rode
    assert float(np.abs(both[0] - want[22:]).max()) < LOGIT_TOL
    emitted = both[1].argmax(axis=1).tolist()
    want = _reference_logits(bench, engine.params, b + emitted)[len(b) - 1:len(b) - 1 + len(emitted)]
    assert float(np.abs(both[1] - want).max()) < LOGIT_TOL
    gen._retire(0)
    gen._retire(1)
    assert (gen.pool.free_blocks(), gen.wpool.free_blocks()) == (free0, wfree0)
    assert not gen.wtables.any() and not gen.tables.any()


def test_admission_asks_both_pools(engine):
    from dllama_tpu.runtime.kvblocks import BlockPoolExhausted
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=2)
    req = Request(rid=1, prompt_ids=_tokens(50), max_tokens=8, stop_on_eos=False)
    assert gen.can_admit(req)
    taken = [gen.wpool.alloc() for _ in range(gen.wpool.free_blocks())]       # the window pool alone is dry
    assert not gen.can_admit(req)
    with pytest.raises(BlockPoolExhausted):
        gen.begin_admit(req, 0)
    assert gen.pool.used_blocks() == 0                                        # nothing of the full pool leaked
    for bid in taken:
        gen.wpool.release(bid)
    assert gen.can_admit(req)
    taken = [gen.pool.alloc() for _ in range(gen.pool.free_blocks())]         # the full pool alone is dry
    assert not gen.can_admit(req)
    with pytest.raises(BlockPoolExhausted):
        gen.begin_admit(req, 0)
    assert gen.wpool.used_blocks() == 0
    for bid in taken:
        gen.pool.release(bid)
    # a live slot's window may yet grow to its cap: that room is owed
    gen.admit(Request(rid=2, prompt_ids=_tokens(5), max_tokens=200, stop_on_eos=False), 1)
    owed = gen._wprice(1) - len(gen._wbids[1])
    assert owed == gen._wcap - 1
    spare = [gen.wpool.alloc() for _ in range(gen.wpool.free_blocks() - owed - 2)]
    assert not gen.can_admit(req) and spare


def test_scheduler_serves_through_two_pools_and_counts(bench, engine, tmp_path):
    """Through ``BatchScheduler``: interleaved requests finish, the same prompt
    twice gives the same tokens with its prefix REUSED the second time (80 of
    its 89 positions: the full pool's five blocks, whose last window the
    window pool had parked), the routing counters reach the registry with the
    steps' tokens."""
    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    reg = telemetry.registry()
    pairs, skipped = reg.counter(telemetry.MOE_PAIRS), reg.counter(telemetry.PREFIX_REUSE_SKIPPED)
    held0, absent0 = pairs.total(where="held"), pairs.total(where="absent")
    skip0 = skipped.total()
    sched = BatchScheduler(engine, n_slots=3)
    try:
        prompts = [_tokens(n, seed=n) for n in (90, 33, 150)]
        reqs = [sched.submit(p, 12, stop_on_eos=False) for p in prompts]
        for r in reqs:
            assert r.done.wait(300) and not r.error
        again = sched.submit(prompts[0], 12, stop_on_eos=False)
        assert again.done.wait(300) and list(again.tokens) == list(reqs[0].tokens)
        assert skipped.total() == skip0 and sched.gen.prefix_totals()[0] == 80 and sched.gen.window_totals()[:2] == (80, 1)
        held, absent = pairs.total(where="held") - held0, pairs.total(where="absent") - absent0
        tokens = sum(len(p) - 1 + 12 for p in prompts + [prompts[0]]) - 80    # prefilled + decoded positions
        assert held + absent == tokens * 4 * 7
        assert reg.gauge(telemetry.MOE_EXPERTS_HELD).value() == 8 and reg.gauge(telemetry.MOE_EXPERTS_TOTAL).value() == 16
        assert reg.gauge(telemetry.KV_WINDOW_BLOCKS_TOTAL).value() == 2 * 3 * (32 // 16 + 2)   # live and parked
        want = _reference_logits(bench, engine.params, prompts[1] + list(reqs[1].tokens))
        assert [int(r.argmax()) for r in want[len(prompts[1]) - 1:-1]] == list(reqs[1].tokens)
        # while a profiler listens the steps' spans carry the counters' running totals, and the benchmark's reader
        # takes what the traced slice added: one request of 50 + 40 positions, less the slice's first step
        import program_spans        # benchmark/program_spans.py
        counters = _import("slice_counters", os.path.join(BENCH, "readers", "slice_counters.py"))
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            traced = sched.submit(_tokens(50, seed=50), 40, stop_on_eos=False)
            assert traced.done.wait(300) and not traced.error
        spans = program_spans.load(program_spans.newest_trace(trace_dir))
        steps = [st for t in spans["ticks"] for name, _s, _e, st in t["children"] if name == "step_wait" and "moe_held" in st]
        assert len(steps) == 40 and all("moe_pairs" in st for st in steps)
        ctx = {"trace": {}, "program_spans": spans}
        share = counters.read(ctx, what="ratio", over=["moe_held"], under=["moe_held", "moe_absent"], scale=100.0)
        added = (int(steps[-1]["moe_held"]) - int(steps[0]["moe_held"]), int(steps[-1]["moe_absent"]) - int(steps[0]["moe_absent"]))
        assert sum(added) == 39 * 4 * 7 and share == 100.0 * added[0] / sum(added)
        assert int(steps[-1]["moe_held"]) == pairs.total(where="held")           # the registry's own total
        assert counters.read(ctx, what="spread", series="moe_tokens") >= 1.0
        # 50 + 40 positions pass a window of 32: blocks came back while the row decoded
        assert counters.read(ctx, what="ratio", over=["wblocks_returned"], under=["wblocks_allocated"]) > 0
    finally:
        sched.close()
    assert sched.gen.wpool.used_blocks() == 0


# -- what is refused, the header, the converter --------------------------------------


@pytest.mark.parametrize("kwargs, named", [
    ({"kv_block_size": 0}, "--kv-block-size"),
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
    with pytest.raises(ValueError, match="window layers and an expert share") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_generator_refuses_what_has_no_construction_flag(engine):
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=1)
    with pytest.raises(ValueError, match="two pools"):
        gen.export_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="two pools"):
        gen.ingest_prefix([1, 2, 3], [])
    with pytest.raises(ValueError, match="window layers"):
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
        assert (h.arch_type, h.rope_type) == (ArchType.LAGUNA, RopeType.YARN)
        assert (h.layer_period, h.sliding_window, h.n_heads, h.n_heads_sliding, h.rope_dim) == (4, 32, 16, 24, 16)
        assert (h.n_experts, h.moe_router_width, h.moe_first_expert, h.n_active_experts) == (8, 16, 4, 4)
        assert (h.n_dense_layers, h.dense_hidden_dim, h.hidden_dim, h.shared_expert_dim) == (1, 128, 32, 32)
        assert h.moe_routed_scale_milli == 2500 and h.rope_scaling_factor == 4.0
        t = mf.tensors
        assert t["block_matmul_q.0"].shape == (512, 64) and t["block_matmul_q.1"].shape == (768, 64)
        assert t["block_attn_gate.1"].shape == (24, 64) and t["block_moe_gate.1"].shape == (16, 64)
        assert "block_matmul_w1.0" in t and "block_moe_gate.0" not in t and "block_expert_w1.1.7" in t
        assert "block_expert_w1.1.8" not in t and t["block_shared_w2.3"].shape == (64, 32)
        cfg = ModelConfig.from_header(h, "float32")
    assert cfg.has_window_layers and cfg.paged_only and not cfg.is_hybrid and cfg.is_moe
    assert (cfg.n_periods, cfg.n_kv_layers, cfg.n_window_layers, cfg.n_moe_layers) == (2, 2, 6, 7)
    assert cfg.moe_routed_scale == 2.5 and cfg.prefix_reuse_skipped is None
    bad = dict(bench["model"], first_expert=12)             # 12 + 8 held runs past the router's 16
    bench["weights"].write_sparse_model(path, bad)
    with pytest.raises(ValueError, match="held of a router over 16"):
        ModelFile.open(path)
    with pytest.raises(ValueError, match="models/laguna.py implements"):
        bench["weights"].write_sparse_model(path, dict(bench["model"], router_score="sigmoid"))


def test_converter_maps_the_config_and_says_it_has_no_tensor_map(tmp_path):
    from dllama_tpu.convert import hf
    from dllama_tpu.formats.mfile import ArchType, RopeType

    with open(os.path.join(BENCH, "configs", "laguna-s-2.1.json"), encoding="utf-8") as f:
        conf = json.load(f)
    published = {k: v for k, v in conf.items() if k not in bench_run.HARNESS_SECTIONS and not k.startswith("reduced")}
    published["num_experts"] = 256          # a whole checkpoint's config: every expert
    (tmp_path / "config.json").write_text(json.dumps(published))
    params = hf.load_hf_config(tmp_path, 2)
    assert params["arch_type"] == int(ArchType.LAGUNA) and params["rope_type"] == int(RopeType.YARN)
    assert (params["layer_period"], params["sliding_window"], params["n_heads"], params["n_heads_sliding"]) == (4, 512, 6, 9)
    assert (params["hidden_dim"], params["dense_hidden_dim"], params["shared_expert_dim"]) == (1024, 12288, 1024)
    assert (params["moe_router_width"], params["n_experts"], params["n_active_experts"]) == (256, 256, 10)
    assert (params["rope_theta"], params["rope_theta_sliding"], params["rope_dim"]) == (500000, 10000, 64)
    assert (params["rope_scaling_factor"], params["rope_scaling_orig_max_seq_len"]) == (128, 8192)
    assert params["moe_routed_scale_milli"] == 2500 and params["n_dense_layers"] == 1
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf.hf_tensor_plan(params)


def test_the_cell_configuration_is_the_issue_reckoning(bench):
    """The real configuration's counts: what is held, and the bytes of a step."""
    with open(os.path.join(BENCH, "configs", "laguna-s-2.1.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    c = bench["counts"]
    expert = 3 * 3072 * 1024
    assert c.kernel_counts(model, "expert_gemv", rows=16)["bytes"] == expert * 1.0625            # 10 MB a pair
    assert c.kernel_counts(model, "no_such_kernel", rows=16) is None
    assert abs(c.pairs_held(model, 16) - 20.0) < 1e-9 and 14 < c.experts_touched(model, 16) < 16
    assert abs(c.pairs_held(model, 256) - 320.0) < 1e-9 and c.experts_touched(model, 256) > 31.99
    held = c.always_read_weights(model) + 23 * 32 * expert
    assert 7.4e9 < held < 7.6e9                                        # 7.5 B weights in planes: 8.0 GB as held
    step = lambda rows: c.decode_step_bytes(model, rows=rows, context_tokens=rows * 600)
    assert 0.9e9 < step(1) < 1.3e9 and 2.4e9 < step(8) < 3.2e9 and 3.6e9 < step(16) < 4.6e9   # the issue's 1.0 / 2.7 / 4.2 GB
    # the window bounds what the sliding layers read, whatever the context
    assert c.window_tokens(model, 4, 4 * 3000) == 4 * 512 and c.window_tokens(model, 4, 4 * 100) == 400
    assert c.decode_step_bytes(model, rows=4, context_tokens=12000) - c.decode_step_bytes(model, rows=4, context_tokens=2048) \
        == 2 * 128 * 2 * 6 * (12000 - 2048)


# -- the benchmark's seam, seen by tier-1 ------------------------------------------


@pytest.mark.parametrize("control, correct", [("none", True), ("shift", False), ("droplayer", False),
                                              ("dropblock", False), ("misroute", False), ("noshared", False),
                                              ("nogate", False), ("nowindow", False), ("bf16router", False)])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with this configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under each
    control the reference knows."""
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-laguna.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "5", "--control", control])       # under six workers a shorter window completes nothing
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
            scale = 0.5 if rec.name == "block_moe_gate" else 0.1
            x = np.ones(rec.shape, np.float32) if ones else (rng.standard_normal(rec.shape) * scale).astype(np.float32)
            write_tensor(f, x, rec.float_type)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(path, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        lp = eng.params.layers
        assert lp.full.wq.codes.shape == (2, 64, 512) and lp.slide.wq.codes.shape == (6, 64, 768)
        assert lp.we1.codes.shape == (7, 8, 64, 32) and lp.ws2.codes.shape == (7, 32, 64) and lp.w1.codes.shape == (1, 64, 128)
        assert lp.moe_gate.shape == (7, 16, 64) and lp.slide.wg.shape == (6, 24, 64)
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
    """The two readers PR 34 brings, on worked numbers: the routed kernel's
    roofline share from the pairs the steps' spans carry (never slots x k), and
    the counters' ratio and spread over the TRACED SLICE from the running
    totals on the same spans (last less first: what the warm-up and the probe
    counted is in neither); and ``None``, not an error, where the program has
    no such span or total (a parent commit)."""
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    try:
        roofline, counters = (_import(n, os.path.join(BENCH, "readers", n + ".py"))
                              for n in ("expert_pairs_roofline", "slice_counters"))
    finally:
        sys.path.remove(os.path.join(BENCH, "readers"))
    with open(os.path.join(BENCH, "configs", "laguna-s-2.1.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    tick = lambda pairs: {"children": [("step_dispatch", 0.0, 0.001, {}), ("step_wait", 0.001, 0.006, pairs)]}
    ctx = {"trace": {"device_ops": [("paged_sampled_step_guarded/expert_gemv.3 custom-call", 0.010),
                                    ("paged_sampled_step_guarded/quant_matmul.9 custom-call", 0.5),
                                    ("forward/expert_gemv.1 custom-call", 9.0)]},
           "counts": bench["counts"], "model": model, "peaks": {"hbm_bytes_per_s": 819e9},
           "program_spans": {"ticks": [tick({"moe_pairs": "300"}), tick({"moe_pairs": "260"}), tick({})]}}
    args = {"kernel": "expert_gemv", "program": "paged_sampled_step_guarded"}
    want = 100.0 * (3 * 3072 * 1024 * 1.0625) * 560 / 819e9 / 0.010           # 560 pairs of 10.03 MB in 10 ms: 68.6%
    assert abs(roofline.read(ctx, **args) - want) < 1e-9 and 68 < want < 69
    assert roofline.read(dict(ctx, program_spans={"ticks": [tick({})]}), **args) is None      # a parent's spans
    assert roofline.read(dict(ctx, trace=None), **args) is None
    # the warm-up left 9000 held of 10000; the slice added 125 held and 875 absent, 40 blocks taken and 3 returned
    totals = [{"moe_held": 9000, "moe_absent": 1000, "moe_tokens": "500/500/500/500", "wblocks_allocated": 700, "wblocks_returned": 0},
              {"moe_held": "9100", "moe_absent": "1700", "moe_tokens": "510/560/520/510", "wblocks_allocated": 730, "wblocks_returned": 2},
              {"moe_held": 9125, "moe_absent": 1875, "moe_tokens": "520/590/525/515", "wblocks_allocated": 740, "wblocks_returned": 3}]
    sliced = {"trace": {}, "program_spans": {"ticks": [tick(st) for st in totals] + [tick({})]}}
    held = {"what": "ratio", "over": ["moe_held"], "under": ["moe_held", "moe_absent"], "scale": 100.0}
    assert counters.read(sliced, **held) == 12.5
    assert counters.read(sliced, what="ratio", over=["wblocks_returned"], under=["wblocks_allocated"], scale=100.0) == 7.5
    assert counters.read(sliced, what="spread", series="moe_tokens") == 90 * 4 / 150        # 20 90 25 15 added: 2.4
    parent = {"trace": {}, "program_spans": {"ticks": [tick({"moe_pairs": "3"}), tick({})]}}
    assert counters.read(parent, **held) is None and counters.read(parent, what="spread", series="moe_tokens") is None
    assert counters.read({"trace": None}, **held) is None                                    # an untraced run
    assert counters.read(dict(sliced, program_spans={"ticks": [tick(totals[0])]}), **held) is None   # one step: nothing to subtract
    for name in ("moe_held_pair_share", "window_blocks_returned_share", "moe_expert_load_max_over_mean"):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json"), encoding="utf-8") as f:
            spec = json.load(f)
        assert spec["reader"] == "slice_counters" and counters.read(sliced, **spec["args"]) is not None
