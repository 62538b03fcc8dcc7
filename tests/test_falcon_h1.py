"""The decoder with an SSD (Mamba-2) mixer and grouped-query attention side by
side in every layer (``ArchType.FALCON_H1``, ``models/falcon_h1.py``,
``ops/ssd.py``) against its plain reference
(``benchmark/falcon_h1/reference.py``, imported from where it lies, no copy),
at a tiny size on the CPU: hidden 64, 5 query heads on 1 K/V head of 32, 4
mixer heads of 16 in 2 groups with a state of 16, 4 layers, vocabulary 128,
float32, the PUBLISHED multipliers, seeded weights from the benchmark's own
maker (``benchmark/falcon_h1/weights.py``), so program and reference read the
same planes.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference
  compute the same float32 function with their sums in another order (chunk
  form against per-token recurrence, blocked attention against the oracle, the
  in-projection as two planes against one); the worst seen is 2e-4. A dropped
  ``lm_head_multiplier`` reads 100 and more, a dropped ``key_multiplier`` 0.1
  and more, a state held in bfloat16 between calls 5e-3 and more: each of the
  three is asserted to FAIL the tolerance.
* ``FORM_TOL`` 2e-4 between the mixer's three forms on random inputs of unit
  size: float32 rounding of 200 tokens' products (worst seen 5e-6).
* the step kernel in ``interpret`` mode against its XLA twin: 1e-5, they are
  the same float32 operations per element.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os
import struct
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
FH1 = os.path.join(BENCH, "falcon_h1")
TINY = os.path.join(FH1, "selftest", "configs", "tiny-falcon-h1.json")
MANIFEST = os.path.join(FH1, "selftest", "manifest.json")
LOGIT_TOL, FORM_TOL = 2e-3, 2e-4


def _import(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, BENCH)           # as run.py puts it, and as benchmark/selftest/test_*.py do
import run as bench_run  # noqa: E402


@pytest.fixture(autouse=True)
def _engine_loader_put_back():
    """The weights module's seam replaces the engine's tensor-reading call
    for the process: every test here hands it back as it found it."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.models.llama import load_params_from_mfile

    yield
    engine_mod.load_params_from_mfile = load_params_from_mfile


def _bench(folder, tiny, prefix):
    with open(tiny, encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    return {"weights": _import(prefix + "_weights", os.path.join(folder, "weights.py")),
            "reference": _import(prefix + "_reference", os.path.join(folder, "reference.py")),
            "counts": _import(prefix + "_counts", os.path.join(folder, "counts.py")), "model": model}


@pytest.fixture(scope="module")
def bench():
    """The configuration's modules, imported from their files, and the tiny model."""
    return _bench(FH1, TINY, "fh1")


def _engine(bench, tmp_path, *, seed=7, seq_len=512, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype="float32", **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("fh1"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens):
    return bench["reference"].reference_logits(bench["model"], params, tokens)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, size=n).tolist()


# -- the mixer's forms --------------------------------------------------------


def _ssd_inputs(T, seed=0, B=2, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm, Cm = jax.random.normal(ks[3], (B, T, G, N)), jax.random.normal(ks[4], (B, T, G, N))
    return x, dt, A, Bm, Cm, jax.random.normal(ks[5], (B, H, P, N))


# 200 = 25 sub-chunks of gcd(200, 128) = 8: a chunk boundary that is no multiple of 128
@pytest.mark.parametrize("T,chunk", [(96, 32), (200, 128), (256, 128), (128, 128)])
def test_chunk_form_is_the_recurrence(T, chunk):
    from dllama_tpu.ops import ssd

    x, dt, A, Bm, Cm, S0 = _ssd_inputs(T)
    y0, S_end = ssd.ssd_recurrent(x, dt, A, Bm, Cm, S0)
    y1, S1 = jax.jit(lambda *a: ssd.ssd_chunk(*a, chunk))(x, dt, A, Bm, Cm, S0)
    assert float(jnp.abs(y1 - y0).max()) < FORM_TOL and float(jnp.abs(S1 - S_end).max()) < FORM_TOL


def test_chunk_form_over_two_calls_carries_the_state_and_padding_leaves_it():
    """State in, state out: 160 tokens as 96 + 64 are the 160 at once; a chunk
    of 96 padded to 128 with ``dt`` = 0 behind ``n_valid`` leaves the state of
    the 96."""
    from dllama_tpu.ops import ssd

    x, dt, A, Bm, Cm, S0 = _ssd_inputs(160, seed=3)
    cut = lambda t, a, b: t[:, a:b]
    _y, S_all = ssd.ssd_recurrent(x, dt, A, Bm, Cm, S0)
    _y, S_a = ssd.ssd_chunk(*(cut(t, 0, 96) for t in (x, dt)), A, cut(Bm, 0, 96), cut(Cm, 0, 96), S0, 32)
    y_b, S_b = ssd.ssd_chunk(*(cut(t, 96, 160) for t in (x, dt)), A, cut(Bm, 96, 160), cut(Cm, 96, 160), S_a, 32)
    assert float(jnp.abs(S_b - S_all).max()) < FORM_TOL
    real = (jnp.arange(128) < 96)[None, :, None]
    _y, S_pad = ssd.ssd_chunk(cut(x, 0, 128), jnp.where(real, cut(dt, 0, 128), 0.0), A, cut(Bm, 0, 128),
                              cut(Cm, 0, 128), S0, 32)
    assert float(jnp.abs(S_pad - S_a).max()) < FORM_TOL


@pytest.mark.parametrize("interpret", [None, True])
def test_step_form_is_one_recurrence_step_over_the_pool_in_place(interpret):
    """The XLA twin and the Pallas kernel (interpret mode): the rows named
    change as the recurrence says, every other cell of the pool keeps its bits."""
    from dllama_tpu.ops import ssd

    x, dt, A, Bm, Cm, _S0 = _ssd_inputs(1, seed=5, B=3)
    pool = jax.random.normal(jax.random.PRNGKey(11), (3, 6, 4, 8, 16))
    rows = jnp.asarray([2, 0, 5], jnp.int32)
    args = (pool, jnp.int32(1), rows, x[:, 0], dt[:, 0], jnp.exp(dt[:, 0] * A), Bm[:, 0], Cm[:, 0])
    y, out = ssd.ssd_step_xla(*args) if interpret is None else ssd.ssd_step(*args, interpret=True)
    y_ref, S_ref = ssd.ssd_recurrent(x, dt, A, Bm, Cm, pool[1][rows])
    assert float(jnp.abs(y - y_ref[:, 0]).max()) < 1e-5
    assert float(jnp.abs(out[1][rows] - S_ref).max()) < 1e-5
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, [2, 0, 5]] = False
    np.testing.assert_array_equal(np.asarray(out)[untouched], np.asarray(pool)[untouched])


def test_packed_plane_and_float32_dt_rows_are_the_unpacked_in_projection(engine):
    """The program's two planes (the 192-wide ``z x B C`` Q40 plane and the
    float32 ``dt`` rows) against the ONE in-projection of the published
    layout, joined back and multiplied by ``mup_vector``: the same ``z``,
    the same ``dt`` and, through taps of 1 on the last lane, the same xBC."""
    from dllama_tpu.models import ssd_mixer
    from dllama_tpu.models.llama import _stack_at
    from dllama_tpu.ops.linear import dequantize_weight

    cfg = engine.cfg
    lp = _stack_at(engine.params.layers, jnp.int32(2), ())
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 5, cfg.dim))
    # the last tap 1 and the rest 0, no bias: the convolution hands its input through
    lp = lp._replace(conv_w=jnp.zeros_like(lp.conv_w).at[-1].set(1.0), conv_b=jnp.zeros_like(lp.conv_b))
    tail = jnp.zeros((1, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim))
    with jax.default_matmul_precision("highest"):
        packed, dt, z = ssd_mixer.mixer_project(cfg, u, lp)
        x, dt, Bm, Cm, _tail = ssd_mixer.mixer_conv(cfg, packed, dt, lp, tail, None)
        w = jnp.concatenate([dequantize_weight(lp.w_in).T, lp.w_dt], axis=0)       # [192 + 4, dim]: as published
        m, d, gn = cfg.mult, cfg.ssm_inner_dim, cfg.ssm_groups * cfg.ssm_state_dim
        mup = jnp.concatenate([jnp.full((d,), m.ssm_z), jnp.full((d,), m.ssm_x), jnp.full((gn,), m.ssm_b),
                               jnp.full((gn,), m.ssm_c), jnp.full((cfg.ssm_heads,), m.ssm_dt)])
        proj = (u @ w.T) * mup
    assert w.shape == (cfg.ssm_in_dim + cfg.ssm_heads, cfg.dim) == (196, 64)
    np.testing.assert_allclose(z, proj[..., :d], atol=1e-5)
    np.testing.assert_allclose(dt, jax.nn.softplus(proj[..., -cfg.ssm_heads:] + lp.dt_bias), atol=1e-5)
    got = jnp.concatenate([x.reshape(1, 5, -1), Bm.reshape(1, 5, -1), Cm.reshape(1, 5, -1)], axis=-1)
    np.testing.assert_allclose(got, jax.nn.silu(proj[..., d:-cfg.ssm_heads]), atol=1e-5)


# -- the model against the reference -----------------------------------------


def _forward_logits(engine, cfg, tokens):
    from dllama_tpu.models import llama
    from dllama_tpu.runtime.kvblocks import StateColumn
    from dllama_tpu.runtime.kvcache import KVCache

    kv = KVCache.create(cfg, dtype=jnp.float32)
    col = StateColumn.zeros(cfg, kv.k, kv.v, jnp.float32)
    assert kv.k.shape[0] == col.s.shape[0] == col.conv.shape[0] == cfg.n_layers     # K/V AND a state in every layer
    # a function of this test's own: jax.jit(llama.forward) would share its trace cache with
    # every other jit of that function in the worker
    logits, _col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), col)
    return np.asarray(logits[0])


@pytest.mark.parametrize("T", [96, 256])
def test_whole_forward_logits(bench, engine, T):
    tokens = _tokens(T)
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(_forward_logits(engine, engine.cfg, tokens) - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("dropped", ["lm_head", "key", "embedding", "ssm_out", "mlp_down", "ssm_b"])
def test_a_dropped_multiplier_fails_the_logits(bench, engine, dropped):
    """The comparison is tight enough to hold every multiplier: the program
    with one of them left at 1 is NOT within the tolerance. ``lm_head``
    scales every logit alike, which no greedy token and no gap can see: this
    is the test that holds it."""
    tokens = _tokens(96)
    cfg = dataclasses.replace(engine.cfg, mult=engine.cfg.mult._replace(**{dropped: 1.0}))
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(_forward_logits(engine, cfg, tokens) - want).max()) > 10 * LOGIT_TOL


def _decode_logits(gen, slot, n_steps):
    """Greedy decode of ``slot`` by hand over the generator's own pools, one
    step program a token, keeping the logits: what ``PagedGenerator.step``
    dispatches, less the sampler."""
    from dllama_tpu.models import llama

    step = jax.jit(lambda params, *args: llama.paged_forward(params, gen.cfg, *args))   # its own trace cache
    rows, emitted = [], []
    for _ in range(n_steps):
        gen._ensure_blocks(slot, int(gen.pos[slot]))
        logits, (gen.pkv, gen.spool) = step(
            gen.eng.params, jnp.asarray(gen.next_token[:, None], jnp.int32),
            jnp.asarray(gen.pos, jnp.int32), (gen.pkv, gen.spool), jnp.asarray(gen.tables))
        rows.append(np.asarray(logits[slot, 0]))
        emitted.append(int(rows[-1].argmax()))
        gen.next_token[slot] = emitted[-1]
        gen.pos[slot] += 1
    return np.stack(rows), emitted


# 70: a chunk of 64, then 5 tokens padded to 32; 20: shorter than one sub-chunk, padded to 32;
# 300: 256, 32, then 11 padded to 32; 257: exactly one widest chunk, nothing padded.
# kernel "pallas": the decode steps through ``paged_ragged_attention`` at a group of 5 query heads a
# K/V head and through the ``ssd_step`` kernel (interpret mode off a TPU), a dead slot with a stale
# depth beside the live one.
@pytest.mark.parametrize("n_prompt,kernel", [(70, None), (20, None), (300, None), (257, None),
                                             (70, "pallas"), (300, "pallas")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.ops import ssd
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = []
    attn, step = pa.paged_ragged_attention, ssd.ssd_step
    monkeypatch.setattr(pa, "paged_ragged_attention", lambda *a, **kw: calls.append("attn") or attn(*a, **kw))
    monkeypatch.setattr(ssd, "ssd_step", lambda *a, **kw: calls.append("ssd") or step(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=8, stop_on_eos=False), 1)
    got, emitted = _decode_logits(gen, 1, 8)
    # the one layer body, traced once a program: the step here, and in the admission the tick program of each of the
    # prompt's two buckets (64 and 32, 256 and 32), whose dead rows go through both kernels too
    assert sorted(calls) == (["attn"] * 3 + ["ssd"] * 3 if kernel else [])
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt + 7]
    assert float(np.abs(got - want).max()) < LOGIT_TOL


def test_a_state_held_in_bfloat16_fails_the_logits(bench, engine):
    """``engine.state_dtype`` float32: the same prefill and decode with the
    slot's committed state rounded to bfloat16 once is NOT within the
    tolerance."""
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=2)
    prompt = _tokens(150, seed=4)
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=8, stop_on_eos=False), 1)
    gen.spool = gen.spool._replace(s=gen.spool.s.astype(jnp.bfloat16).astype(jnp.float32))
    got, emitted = _decode_logits(gen, 1, 4)
    want = _reference_logits(bench, engine.params, prompt + emitted)[len(prompt) - 1:len(prompt) + 3]
    assert float(np.abs(got - want).max()) > 2 * LOGIT_TOL


def test_a_step_changes_only_the_cells_it_writes(engine):
    """K/V pool and state pool ride the layer scan's carry and are written in
    place: after a step every layer's K/V differs at each live row's own cell
    and nowhere else (the null block aside), and the state rows of the slots
    whose table is null keep their bits (the null row takes their writes)."""
    from dllama_tpu.models.llama import paged_forward
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    cfg = engine.cfg
    rng = np.random.default_rng(9)
    B, M, bs = 3, 4, 16
    tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32)
    tables[1] = 0
    pos = np.asarray([5, 40, 33], np.int32)
    shape = (cfg.n_layers, 1 + B * M, cfg.n_kv_heads, bs, cfg.head_dim)
    pkv = PagedKVCache(k=jnp.asarray(rng.standard_normal(shape), jnp.float32),
                       v=jnp.asarray(rng.standard_normal(shape), jnp.float32))
    spool = StatePool(s=jnp.asarray(rng.standard_normal(cfg.state_shape(B + 1)), jnp.float32),
                      conv=jnp.asarray(rng.standard_normal(cfg.conv_shape(B + 1)), jnp.float32))
    toks = jnp.asarray(rng.integers(1, 127, (B, 1)).astype(np.int32))
    logits, (out, sout) = jax.jit(lambda *a: paged_forward(a[0], cfg, *a[1:]))(
        engine.params, toks, jnp.asarray(pos), (pkv, spool), jnp.asarray(tables))
    assert np.all(np.isfinite(np.asarray(logits)))
    want = np.zeros((cfg.n_layers, shape[1], bs), bool)
    for b in (0, 2):
        want[:, tables[b, pos[b] // bs], pos[b] % bs] = True
    for got, was in ((out.k, pkv.k), (out.v, pkv.v)):
        changed = (np.asarray(got) != np.asarray(was)).any(axis=(2, 4))
        np.testing.assert_array_equal(changed[:, 1:], want[:, 1:])
    for got, was in ((sout.s, spool.s), (sout.conv, spool.conv)):
        changed = (np.asarray(got) != np.asarray(was)).reshape(cfg.n_layers, B + 1, -1).any(axis=2)
        np.testing.assert_array_equal(changed, np.tile([True, True, False, True], (cfg.n_layers, 1)))


def test_the_compiled_step_holds_no_second_pool(engine):
    """K/V pool and state pool donated: the compiled step's temporaries stay
    under half of ONE K/V pool."""
    from helpers import compile_paged_step

    compiled, pool = compile_paged_step(engine.cfg, engine.params, n_slots=4, n_blocks=2048, block_size=16,
                                        table_width=4, pool_dtype=jnp.float32)
    assert pool == engine.cfg.n_layers * 2048 * engine.cfg.n_kv_heads * 16 * engine.cfg.head_dim * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool // 2


# -- the state pool's rules, whichever architecture owns its shape ------------


@pytest.fixture(scope="module")
def olmo_engine(tmp_path_factory):
    folder = os.path.join(BENCH, "olmo_hybrid")
    olmo = _bench(folder, os.path.join(folder, "selftest", "configs", "tiny-olmo-hybrid.json"), "olmo")
    eng = _engine(olmo, tmp_path_factory.mktemp("olmo"))
    yield eng
    eng.close()


@pytest.mark.parametrize("arch", ["falcon_h1", "olmo_hybrid"])
def test_state_pool_rules_hold_for_both_architectures(arch, engine, olmo_engine):
    """``StatePool``'s rules with the shape the ARCHITECTURE gives it: the
    null row 0 and one row a slot; an admission's column starts at zero; the
    state is written once, at commit, to the slot's row and to no other;
    a step leaves the rows of slots it does not serve bit-identical."""
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    eng = engine if arch == "falcon_h1" else olmo_engine
    cfg = eng.cfg
    gen = PagedGenerator(eng, n_slots=3)
    assert gen.spool.s.shape == cfg.state_shape(4) and gen.spool.conv.shape == cfg.conv_shape(4)
    assert gen.spool.s.shape[0] == cfg.n_state_layers == (4 if arch == "falcon_h1" else 6)
    assert gen.spool.s.dtype == jnp.float32 and gen.spool.NULL == 0
    assert not np.asarray(gen.spool.s).any()
    before = np.asarray(gen.spool.s)
    adm = gen.begin_admit(Request(rid=1, prompt_ids=_tokens(40, seed=1), max_tokens=4, stop_on_eos=False), 1)
    assert not np.asarray(adm.col.s).any() and not np.asarray(adm.col.conv).any()     # zero at admission
    assert adm.col.k.shape[0] == cfg.n_kv_layers
    while not gen.continue_admit(adm):
        # not before the commit; the null row takes the writes of a tick program's dead rows, as it takes a step's
        np.testing.assert_array_equal(np.asarray(gen.spool.s)[:, 1:], before[:, 1:])
    after = np.asarray(gen.spool.s)
    assert after[:, 2].any()                                                          # slot 1 owns row 2
    np.testing.assert_array_equal(np.delete(after, [0, 2], axis=1), np.delete(before, [0, 2], axis=1))
    gen.step()
    stepped = np.asarray(gen.spool.s)
    assert (stepped[:, 2] != after[:, 2]).any()
    np.testing.assert_array_equal(stepped[:, [1, 3]], after[:, [1, 3]])               # other slots' rows: untouched


# -- through the scheduler -----------------------------------------------------


def _serve(sched, prompt, n=10):
    req = sched.submit(prompt, n, stop_on_eos=False)
    assert req.done.wait(300) and req.error is None, req.error
    return list(req.tokens)


def _gap(bench, engine, prompt, emitted):
    return float(bench["reference"].reference_gaps(bench["model"], engine.params, prompt, emitted)["gap"].max())


def test_scheduler_interleaved_slots_reuse_and_same_prompt_twice(bench, engine):
    """Through ``BatchScheduler``: two requests of different lengths, the
    second admitted while the first decodes (no cross-talk); a slot reused
    after retirement starts from a zero state; the same prompt twice gives
    the same tokens and counts one skipped prefix reuse. An emitted token is
    held against the reference's full forward by its gap: 0 is the
    reference's own argmax at that position."""
    import threading

    from dllama_tpu.runtime import telemetry
    from dllama_tpu.runtime.serving import BatchScheduler

    skipped = telemetry.registry().counter(telemetry.PREFIX_REUSE_SKIPPED)
    sched = BatchScheduler(engine, n_slots=2)
    try:
        a, b = _tokens(150, seed=1), _tokens(37, seed=2)
        first_token = threading.Event()
        req_a = sched.submit(a, 24, stop_on_eos=False, on_token=lambda *_: first_token.set())
        assert first_token.wait(300)
        out_b = _serve(sched, b, 12)          # admitted mid-decode of a, into the other slot
        assert req_a.done.wait(300) and req_a.error is None
        assert _gap(bench, engine, a, list(req_a.tokens)) == 0.0
        assert _gap(bench, engine, b, out_b) == 0.0
        c = _tokens(90, seed=3)               # both slots have held a sequence: the next one reuses a row
        before = skipped.total()
        out_c = _serve(sched, c)
        assert _gap(bench, engine, c, out_c) == 0.0
        assert skipped.total() == before
        assert _serve(sched, c) == out_c
        assert skipped.total() == before + 1
        reg = telemetry.registry()
        assert (reg.gauge(telemetry.STATE_SLOTS_USED).value(), reg.gauge(telemetry.STATE_SLOTS_TOTAL).value()) == (0, 2)
        rendered = reg.render()
        assert 'dllama_ssd_paths{form="step",path="xla",program="paged_sampled_step"' in rendered
        # every chunk of this family goes through its tick program (PR 52), which runs both forms
        assert 'dllama_ssd_paths{form="chunk",path="xla",program="forward_and_step"' in rendered
        assert 'dllama_ssd_paths{form="step",path="xla",program="forward_and_step"' in rendered
    finally:
        sched.close()


def test_a_real_file_loads_through_the_streaming_loader(bench, tmp_path):
    """A ``.m`` with real tensors in the walk's order, through
    ``runtime/weights.load_params`` (no seam), served, against the reference."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.formats import mfile
    from dllama_tpu.models.llama import load_params_from_mfile
    from dllama_tpu.runtime import telemetry
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
    # gains that keep a signal under the published multipliers (weights.py has the reasons)
    gains = {"embedding": 0.2, "block_matmul_k": 10.0, "block_matmul_wo": 3.0, "block_ssm_in": 2.0, "block_ssm_out": 1.0,
             "block_matmul_w1": 0.7, "block_matmul_w2": 10.0, "final_matmul_logits": 16.0, "block_ssm_dt": 0.5,
             "block_ssm_a_log": 0.0, "block_ssm_d": 0.0, "block_ssm_dt_bias": 0.0}
    with open(path, "r+b") as f:
        f.seek(records[0].offset)
        for rec in records:
            if rec.name.startswith(("block_norm", "final_norm", "block_ssm_norm")):
                x = np.ones(rec.shape, np.float32)
            else:
                x = (rng.standard_normal(rec.shape) * 0.1 * gains.get(rec.name, 1.0)).astype(np.float32)
            if rec.name == "block_ssm_d":
                x += 1.0
            if rec.name == "block_ssm_dt_bias":
                x -= 2.0
            write_tensor(f, x, rec.float_type)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(path, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        kinds = telemetry.registry().gauge(telemetry.LAYER_KINDS)       # set when an engine is built
        assert (kinds.value(kind="ssm_beside_full"), kinds.value(kind="full"), kinds.value(kind="linear")) == (4, 0, 0)
        assert eng.params.layers.w_in.codes.shape == (4, 64, 192) and eng.params.layers.w_dt.shape == (4, 4, 64)
        assert eng.params.layers.conv_b.shape == (4, 128) and eng.params.layers.norm_ssm.shape == (4, 64)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            emitted = _serve(sched, prompt, 6)
            assert _gap(bench, eng, prompt, emitted) == 0.0
            got = _forward_logits(eng, eng.cfg, prompt)
            assert float(np.abs(got - _reference_logits(bench, eng.params, prompt)).max()) < LOGIT_TOL
        finally:
            sched.close()
    finally:
        eng.close()


# -- what is refused, the header, the dense decoders --------------------------


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
    with pytest.raises(ValueError, match="SSD mixer beside attention") as err:
        _engine(bench, tmp_path, **kwargs)
    assert named in str(err.value)


def test_generator_refuses_what_has_no_construction_flag(engine):
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    gen = PagedGenerator(engine, n_slots=1)
    with pytest.raises(ValueError, match="recurrent state"):
        gen.export_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="recurrent state"):
        gen.ingest_prefix([1, 2, 3], [])
    with pytest.raises(ValueError, match="recurrent state"):
        gen.begin_admit(Request(rid=1, prompt_ids=[1, 2, 3], max_tokens=1, score=True), 0)
    with pytest.raises(RuntimeError, match="BatchScheduler"):
        engine.prefill([1, 2, 3])


def test_header_round_trip_and_walk(bench, tmp_path):
    from dllama_tpu.formats import mfile
    from dllama_tpu.models.config import ModelConfig

    path = str(tmp_path / "h.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    f32 = lambda x: float(np.float32(x))
    with mfile.ModelFile.open(path) as mf:
        h = mf.header
        assert h.arch_type == mfile.ArchType.FALCON_H1 and h.rope_type == mfile.RopeType.FALCON
        assert (h.ssm_n_heads, h.ssm_head_dim, h.ssm_n_groups, h.ssm_state_dim, h.ssm_conv_kernel,
                h.ssm_chunk_size) == (4, 16, 2, 16, 4, 32)
        assert h.rope_theta == f32(1e11)                      # the integer key could not hold it
        assert (h.key_mult, h.lm_head_mult, h.mlp_down_mult, h.ssm_mult_c) == (
            f32(0.011048543456039804), 0.0078125, f32(0.011160714285714284), 0.5)
        assert mf.tensors["block_ssm_in.0"].shape == (192, 64) and mf.tensors["block_ssm_dt.3"].shape == (4, 64)
        assert mf.tensors["block_matmul_q.1"].shape == (160, 64) and mf.tensors["block_ssm_conv_bias.2"].shape == (128,)
        cfg = ModelConfig.from_header(h)
    assert (cfg.has_ssm, cfg.has_state, cfg.is_hybrid, cfg.paged_only) == (True, True, False, True)
    assert cfg.n_kv_layers == cfg.n_state_layers == cfg.n_layers == 4 and cfg.kv_mul == 5
    assert (cfg.ssm_inner_dim, cfg.ssm_conv_dim, cfg.ssm_in_dim) == (64, 128, 192)
    assert cfg.state_shape(3) == (4, 3, 4, 16, 16) and cfg.conv_shape(3) == (4, 3, 3, 128)
    assert len(cfg.mult) == 15 and cfg.mult.residual == 1.0 and cfg.mult.embedding == f32(5.656854249492381) and hash(cfg) is not None
    # the writer takes the floats themselves and stores their bits
    path2 = str(tmp_path / "h2.m")
    with open(path2, "wb") as f:
        mfile.write_header(f, {"version": 1, "arch_type": int(mfile.ArchType.FALCON_H1), "weight_float_type": 2,
                               "key_mult": 0.011048543456039804, "rope_theta_f32": 1e11})
    with open(path2, "rb") as f:
        raw = f.read()
    keys = dict(struct.unpack_from("<ii", raw, 8 + 8 * i) for i in range((len(raw) - 8) // 8))
    assert mfile.f32_from_bits(keys[int(mfile.HeaderKey.KEY_MULT)]) == f32(0.011048543456039804)
    # a build without the architecture refuses its id or its first key
    with open(path, "r+b") as f:
        raw = bytearray(f.read(4096))
    raw[8:12] = struct.pack("<i", 99)
    with pytest.raises(ValueError, match="unsupported header key"):
        mfile.parse_header(bytes(raw), 4096)


def test_converter_maps_the_config_and_says_it_has_no_tensor_map(tmp_path):
    from dllama_tpu.convert import hf
    from dllama_tpu.formats.mfile import ArchType

    with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json"), encoding="utf-8") as f:
        published = json.load(f)
    (tmp_path / "config.json").write_text(json.dumps(published))
    params = hf.load_hf_config(tmp_path, 2)
    assert params["arch_type"] == int(ArchType.FALCON_H1) and "rope_theta" not in params
    assert (params["ssm_n_heads"], params["ssm_state_dim"], params["ssm_chunk_size"], params["head_dim"]) == (32, 256, 128, 128)
    assert (params["rope_theta_f32"], params["key_mult"], params["ssm_mult_dt"]) == (
        1e11, 0.011048543456039804, 0.3535533905932738)
    # the equation here carries no projection bias: a config that has one is another model
    (tmp_path / "config.json").write_text(json.dumps(dict(published, mamba_proj_bias=True)))
    with pytest.raises(ValueError, match="not carried"):
        hf.load_hf_config(tmp_path, 2)
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf.hf_tensor_plan(params)


def test_dense_decoders_compile_what_they_compiled():
    """The new branches of ``forward`` / ``paged_forward``, ``ModelConfig``'s
    new fields and ``causal_conv``'s bias leave the dense configurations'
    lowered programs as they were."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import dense_hlo_digest
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    with open(os.path.join(ROOT, "tests", "goldens", "dense_hlo_sha256.json"), encoding="utf-8") as f:
        assert dense_hlo_digest.digests() == json.load(f)


def test_counts_follow_the_issue_reckoning():
    """The counts module at the published sizes: 430.08 M plane weights a
    layer (less the 32 float32 dt rows), 2 KB of K/V a token a layer, 4.19 MB
    of state a row a layer."""
    counts = _import("fh1_counts_34b", os.path.join(FH1, "counts.py"))
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    L = model["num_hidden_layers"]
    assert L == 12 and counts.layer_matmul_weights(model) // L == 430_080_000 - 32 * 5120
    one = counts.decode_step_bytes(model, rows=0, context_tokens=1) - counts.decode_step_bytes(
        model, rows=0, context_tokens=0)
    assert one == L * 2 * 4 * 128 * 2 == L * 2048
    k = counts.kernel_counts(model, "ssd_step", rows=1)
    assert k["calls_per_program"] == L and 32 * 128 * 256 * 4 == 4_194_304
    assert abs(k["bytes"] - 2 * 4_194_304) / k["bytes"] < 0.01
    assert counts.kernel_counts(model, "no_such_kernel", rows=1) is None
    # a step at 16 rows: 8.15 GB of weights and head, 1.6 GB of state
    step = counts.decode_step_bytes(model, rows=16, context_tokens=0)
    assert 9.6e9 < step < 10.0e9 and 1.60e9 < counts.state_bytes(model, 16) < 1.63e9


# -- the benchmark's seam, seen by tier-1 -------------------------------------


@pytest.mark.parametrize("control, correct", [
    ("none", True), ("shift", False), ("droplayer", False), ("dropblock", False), ("dropstate", False),
    ("nodecay", False), ("dropssm", False), ("bf16state", False)])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with the configuration's modules at the tiny
    preset, from a manifest of its own: ``correct`` true, and false under each
    control the reference knows."""
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-falcon-h1.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "1", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]
