"""The hybrid decoder (``ArchType.OLMO_HYBRID``: gated delta-rule layers with a
slot-indexed recurrent state beside the K/V blocks, ``models/hybrid.py``)
against its plain reference (``benchmark/olmo_hybrid/reference.py``, imported
from where it lies, no copy), at a tiny size on the CPU: hidden 64, 2 heads of
8 / 16, 8 layers = two periods, vocabulary 128, float32, seeded weights from
the benchmark's own maker (``benchmark/olmo_hybrid/weights.py``), so program
and reference read the same Q40 planes.

Tolerances, each with its reason:

* ``LOGIT_TOL`` 2e-3 of a logit whose spread is 1: program and reference
  compute the same float32 function with their sums in another order (chunk
  form against per-token recurrence, blocked attention against the oracle);
  the worst seen is 1e-4. A chunk whose state was carried wrongly, or a
  padded position that entered the state, reads 0.3 and more.
* ``FORM_TOL`` 2e-4 between the mixer's three forms on random inputs of unit
  size: float32 rounding of 192 tokens' products (worst seen 7e-5).
* the step kernel in ``interpret`` mode against its XLA twin: 1e-5, they are
  the same float32 operations in the same order per element.
"""

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

from helpers import delta_chunk_form

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
HYBRID = os.path.join(BENCH, "olmo_hybrid")
TINY = os.path.join(HYBRID, "selftest", "configs", "tiny-olmo-hybrid.json")
MANIFEST = os.path.join(HYBRID, "selftest", "manifest.json")
LOGIT_TOL, FORM_TOL = 2e-3, 2e-4


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
    # the function the engine imported, not "what was there before": a
    # module-scoped engine is built (and the seam installed) before a
    # function-scoped fixture could look
    engine_mod.load_params_from_mfile = load_params_from_mfile


@pytest.fixture(scope="module")
def bench():
    """The hybrid's modules, imported from their files, and the tiny model."""
    with open(TINY, encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    return {"weights": _import("hybrid_weights", os.path.join(HYBRID, "weights.py")),
            "reference": _import("hybrid_reference", os.path.join(HYBRID, "reference.py")),
            "counts": _import("hybrid_counts", os.path.join(HYBRID, "counts.py")),
            "dense_reference": dense_reference, "run": bench_run, "model": model}


def _engine(bench, tmp_path, *, seed=7, seq_len=512, **kw):
    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path / "tiny-hybrid.m")
    bench["weights"].write_sparse_model(path, bench["model"])
    bench["weights"].install_seam(seed)
    kw.setdefault("kv_block_size", 16)
    return InferenceEngine(path, None, max_seq_len=seq_len, compute_dtype="float32", **kw)


@pytest.fixture(scope="module")
def engine(bench, tmp_path_factory):
    eng = _engine(bench, tmp_path_factory.mktemp("hybrid"))
    yield eng
    eng.close()


def _reference_logits(bench, params, tokens):
    """The reference's logits at every position of ``tokens``, float32."""
    ref, dense = bench["reference"], bench["dense_reference"]
    model = bench["model"]
    T = len(tokens)
    padded = -(-T // dense.BLOCK_Q) * dense.BLOCK_Q
    ids = np.zeros(padded, np.int32)
    ids[:T] = tokens
    fn = ref._layers_fn(json.dumps(model, sort_keys=True), "none")
    x = fn(jnp.asarray(ids), params.embedding, ref.layer_tree(params),
           *dense.control_handles(model["num_hidden_layers"], T, padded, "none"))
    h = dense._rms_norm(x, params.final_norm, float(model["norm_epsilon"]))
    with jax.default_matmul_precision("highest"):
        return np.asarray(h @ dense._dequant(dense._planes(params.logits)))[:T]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, size=n).tolist()


# -- the mixer's forms --------------------------------------------------------


def _mixer_inputs(T, seed=0, B=2, H=3, dk=8, dv=16):
    from dllama_tpu.ops import gated_delta as gd

    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    # keys behind a SiLU are nearly parallel: the case that broke the closed
    # product for the triangular inverse
    k = gd.l2norm(jax.nn.silu(jax.random.normal(ks[1], (B, T, H, dk)) + 1.0))
    q = gd.l2norm(jax.random.normal(ks[0], (B, T, H, dk))) / dk ** 0.5
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -jax.random.uniform(ks[3], (B, T, H)) * 0.1
    beta = jax.random.uniform(ks[4], (B, T, H)) * 2.0
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dk, dv))


# the kernel at every bucket's sub-chunk count (T 32: one sub-chunk of 32; 256: four of 64), at 48 (sub-chunks of 16: ONE
# solve block) and at olmo's head shape cut in H only (dk 96 is not a lane tile, dv 192 is a tile and a half)
@pytest.mark.parametrize("T,form,shape", [
    (192, "xla", {}), (32, "xla", {}),
    (32, "kernel", {}), (48, "kernel", {}), (64, "kernel", {}), (128, "kernel", {}), (192, "kernel", {}), (256, "kernel", {}),
    (128, "kernel", dict(B=1, H=2, dk=96, dv=192))])
def test_chunk_form_is_the_per_token_recurrence(T, form, shape):
    from dllama_tpu.ops import gated_delta as gd

    args = _mixer_inputs(T, **shape)       # the state coming in is noise, not zeros
    o_rec, s_rec = gd.gated_delta_recurrent(*args)
    o, s = delta_chunk_form(form)(*args)
    assert float(jnp.abs(o - o_rec).max()) < FORM_TOL
    assert float(jnp.abs(s - s_rec).max()) < FORM_TOL
    if form == "kernel":                   # and its twin
        o_x, s_x = delta_chunk_form("xla")(*args)
        assert float(jnp.abs(o - o_x).max()) < FORM_TOL and float(jnp.abs(s - s_x).max()) < FORM_TOL


def test_step_form_iterated_is_the_per_token_recurrence():
    from dllama_tpu.ops import gated_delta as gd

    q, k, v, g, beta, s0 = _mixer_inputs(8)
    o_rec, s_rec = gd.gated_delta_recurrent(q, k, v, g, beta, s0)
    # rows 1 and 2 of layer 1 of a pool; row 0 (null) and layer 0 stay as they were
    pool = jnp.zeros((2, 3) + s0.shape[1:], jnp.float32).at[1, 1:].set(s0)
    rows = jnp.array([1, 2], jnp.int32)
    for t in range(q.shape[1]):
        o, pool = gd.gated_delta_step_xla(pool, jnp.int32(1), rows, q[:, t], k[:, t], v[:, t],
                                          jnp.exp(g[:, t]), beta[:, t])
        assert float(jnp.abs(o - o_rec[:, t]).max()) < FORM_TOL
    assert float(jnp.abs(pool[1, 1:] - s_rec).max()) < FORM_TOL
    assert float(jnp.abs(pool[0]).max()) == 0.0 and float(jnp.abs(pool[1, 0]).max()) == 0.0


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_masked_positions_leave_the_state_untouched(form):
    from dllama_tpu.ops import gated_delta as gd
    from dllama_tpu.ops.causal_conv import causal_conv

    q, k, v, g, beta, s0 = _mixer_inputs(64)
    real = (jnp.arange(64) < 41)[None, :, None]
    _o, s = delta_chunk_form(form)(q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0), s0)
    _o, s_41 = gd.gated_delta_recurrent(q[:, :41], k[:, :41], v[:, :41], g[:, :41], beta[:, :41], s0)
    assert float(jnp.abs(s - s_41).max()) < FORM_TOL
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 6))
    tail = jnp.ones((1, 3, 6))
    _y, new_tail = causal_conv(x, tail, jnp.ones((4, 6)), jnp.int32(2))
    np.testing.assert_allclose(new_tail[0], jnp.concatenate([tail[0, 2:], x[0, :2]]))


def test_step_kernel_in_interpret_mode_is_its_xla_twin():
    from dllama_tpu.ops import gated_delta as gd

    q, k, v, g, beta, _s0 = _mixer_inputs(1, B=4, H=6, dk=16, dv=128)
    pool = jax.random.normal(jax.random.PRNGKey(3), (3, 5, 6, 16, 128))
    rows = jnp.array([2, 0, 4, 0], jnp.int32)     # two rows on the null row, as inactive slots are
    args = (jnp.int32(1), rows, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0])
    o_x, pool_x = gd.gated_delta_step_xla(pool, *args)
    o_p, pool_p = gd.gated_delta_step(pool, *args, interpret=True)
    d_pool = np.abs(np.asarray(pool_x) - np.asarray(pool_p))
    assert float(np.abs(np.asarray(o_x) - np.asarray(o_p))[[0, 2]].max()) < 1e-5     # the live rows
    assert float(d_pool[:, 1:].max()) < 1e-5                                   # all but the null row
    assert float(np.abs(np.asarray(pool_p) - np.asarray(pool))[[0, 2]].max()) == 0.0   # other layers untouched


# -- the model against the reference -----------------------------------------


@pytest.mark.parametrize("T", [96, 256])
def test_whole_forward_logits(bench, engine, T):
    from dllama_tpu.models import hybrid, llama
    from dllama_tpu.runtime.kvcache import KVCache

    cfg = engine.cfg
    tokens = _tokens(T)
    kv = KVCache.create(cfg, dtype=jnp.float32)
    col = hybrid.HybridColumn.zeros(cfg, kv.k, kv.v, jnp.float32)
    assert kv.k.shape[0] == 2 and col.s.shape[0] == 6      # K/V in the full layers only
    # a function of this test's own: jax.jit(llama.forward) would share its
    # trace cache with every other jit of that function in the worker, and
    # other files count that cache (test_paged_attention's retrace check)
    logits, _col = jax.jit(lambda params, ids, col: llama.forward(params, cfg, ids, jnp.int32(0), col))(
        engine.params, jnp.asarray([tokens], jnp.int32), col)
    want = _reference_logits(bench, engine.params, tokens)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < LOGIT_TOL


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
# kernel "pallas": the decode steps' full layers through ``paged_ragged_attention`` (interpret mode
# off a TPU; ``kv_mul`` 1, a dead slot with a stale depth beside the live one): the benchmark's
# ``correct`` hardly sees that path (a lost block under a long prompt reads as the honest run), the
# logits do. 70 ends inside the walk's first fetch group, 300 walks three.
@pytest.mark.parametrize("n_prompt,kernel", [(70, None), (20, None), (300, None), (257, None),
                                             (70, "pallas"), (300, "pallas")])
def test_padded_chunked_prefill_then_decode_logits(bench, engine, n_prompt, kernel, monkeypatch):
    from dllama_tpu.ops import paged_attention as pa
    from dllama_tpu.runtime.serving import PagedGenerator, Request

    calls = []
    entry = pa.paged_ragged_attention
    monkeypatch.setattr(pa, "paged_ragged_attention", lambda *a, **kw: calls.append(kw) or entry(*a, **kw))
    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    gen = PagedGenerator(engine, n_slots=2)
    gen.pos[0] = 123                      # a retired slot's stale depth
    prompt = _tokens(n_prompt, seed=n_prompt)
    gen.admit(Request(rid=1, prompt_ids=prompt, max_tokens=8, stop_on_eos=False), 1)
    admitted = len(calls)                 # the chunks' tick program walks its (dead) rows through the same kernel
    got, emitted = _decode_logits(gen, 1, 8)
    assert calls[admitted:] == ([{"interpret": True, "window": 0}] if kernel else [])   # traced once: one full layer's body
    want = _reference_logits(bench, engine.params, prompt + emitted)[n_prompt - 1:n_prompt + 7]
    assert float(np.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("kernel", [None, "pallas"])
def test_a_step_changes_only_the_cells_it_writes(engine, kernel, monkeypatch):
    """The full layers' K/V pool rides the period scan's carry beside the state and is written in
    place: after a step every period's pool differs from what went in at each live row's own cell
    and nowhere else (the null block, where an inactive row writes, aside); with the kernel forced,
    ``paged_ragged_attention`` is handed the whole pool and the period."""
    from dllama_tpu.models.llama import paged_forward
    from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool

    if kernel:
        monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", kernel)
    cfg = engine.cfg
    rng = np.random.default_rng(9)
    B, M, bs = 3, 4, 16
    tables = rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32)
    tables[1] = 0
    pos = np.asarray([5, 40, 33], np.int32)
    shape = (cfg.n_periods, 1 + B * M, cfg.n_kv_heads, bs, cfg.head_dim)
    pkv = PagedKVCache(k=jnp.asarray(rng.standard_normal(shape), jnp.float32),
                       v=jnp.asarray(rng.standard_normal(shape), jnp.float32))
    toks = jnp.asarray(rng.integers(1, 127, (B, 1)).astype(np.int32))
    # a fresh lambda a mode: a jit around the same function would reuse the other mode's program
    logits, (out, _) = jax.jit(lambda *a: paged_forward(a[0], cfg, *a[1:]))(
        engine.params, toks, jnp.asarray(pos), (pkv, StatePool.create(cfg, B, jnp.float32)), jnp.asarray(tables))
    assert np.all(np.isfinite(np.asarray(logits)))
    want = np.zeros((cfg.n_periods, shape[1], bs), bool)
    for b in (0, 2):
        want[:, tables[b, pos[b] // bs], pos[b] % bs] = True
    for got, was in ((out.k, pkv.k), (out.v, pkv.v)):
        changed = (np.asarray(got) != np.asarray(was)).any(axis=(2, 4))
        np.testing.assert_array_equal(changed[:, 1:], want[:, 1:])


def test_the_compiled_step_holds_no_second_pool(engine):
    """As ``tests/test_kvblocks.py``'s, for the period scan: K/V pool and state pool donated, the
    compiled step's temporaries stay under half of ONE K/V pool (2 x 4.2 MB here; the state pool,
    in place since PR 30, is 0.6 MB)."""
    from helpers import compile_paged_step

    compiled, pool = compile_paged_step(engine.cfg, engine.params, n_slots=4, n_blocks=2048, block_size=16,
                                        table_width=4, pool_dtype=jnp.float32)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert pool == engine.cfg.n_periods * 2048 * engine.cfg.n_kv_heads * 16 * engine.cfg.head_dim * 4
    assert temp < pool // 2, (temp, pool)


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
        # both slots have held a sequence: whichever the next one takes is reused
        c = _tokens(90, seed=3)
        before = skipped.total()
        out_c = _serve(sched, c)
        assert _gap(bench, engine, c, out_c) == 0.0
        assert skipped.total() == before
        assert _serve(sched, c) == out_c
        assert skipped.total() == before + 1
        used = telemetry.registry().gauge(telemetry.STATE_SLOTS_USED)
        total = telemetry.registry().gauge(telemetry.STATE_SLOTS_TOTAL)
        assert (used.value(), total.value()) == (0, 2)
    finally:
        sched.close()


def test_a_real_file_loads_through_the_streaming_loader(bench, tmp_path):
    """A ``.m`` with real tensors in the walk's order, through
    ``runtime/weights.load_params`` (no seam), served, against the reference."""
    import dllama_tpu.runtime.engine as engine_mod
    from dllama_tpu.formats import mfile, quants
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
            ones = rec.name.startswith(("block_norm", "final_norm", "block_gdn_norm"))
            scale = {"block_gdn_a_log": 0.0, "block_gdn_dt_bias": 1.0}.get(rec.name, 0.1)
            x = np.ones(rec.shape, np.float32) if ones else (rng.standard_normal(rec.shape) * scale).astype(np.float32)
            if rec.name == "block_gdn_dt_bias":
                x -= 4.0
            write_tensor(f, x, rec.float_type)
    engine_mod.load_params_from_mfile = load_params_from_mfile
    eng = InferenceEngine(path, None, max_seq_len=256, compute_dtype="float32", kv_block_size=16)
    try:
        assert eng.params.layers.lin.w_in.codes.shape == (6, 64, 96)
        assert eng.params.layers.full.norm_q.shape == (2, 64)
        sched = BatchScheduler(eng, n_slots=2)
        try:
            prompt = _tokens(75, seed=9)
            assert _gap(bench, eng, prompt, _serve(sched, prompt, 6)) == 0.0
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
    with pytest.raises(ValueError, match="hybrid decoder") as err:
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
    with mfile.ModelFile.open(path) as mf:
        h = mf.header
        assert h.arch_type == mfile.ArchType.OLMO_HYBRID
        assert (h.layer_period, h.linear_n_key_heads, h.linear_n_value_heads, h.linear_key_head_dim,
                h.linear_value_head_dim, h.linear_conv_kernel, h.linear_neg_eigval) == (4, 2, 2, 8, 16, 4, 1)
        assert mf.tensors["block_gdn_in.0"].shape == (96, 64) and "block_matmul_q.0" not in mf.tensors
        assert mf.tensors["block_matmul_q.3"].shape == (64, 64) and mf.tensors["block_norm_q.7"].shape == (64,)
        cfg = ModelConfig.from_header(h)
    assert (cfg.is_hybrid, cfg.n_periods, cfg.n_linear_layers, cfg.n_kv_layers) == (True, 2, 6, 2)
    assert (cfg.lin_conv_dim, cfg.lin_in_dim) == (64, 96)
    # the reference's reader (and an older build of this one) refuses a key it does not know
    with open(path, "r+b") as f:
        raw = bytearray(f.read(4096))
    raw[8:12] = struct.pack("<i", 99)
    with pytest.raises(ValueError, match="unsupported header key"):
        mfile.parse_header(bytes(raw), 4096)


def test_converter_maps_the_config_and_says_it_has_no_tensor_map(tmp_path):
    from dllama_tpu.convert import hf
    from dllama_tpu.formats.mfile import ArchType

    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json"), encoding="utf-8") as f:
        published = json.load(f)
    (tmp_path / "config.json").write_text(json.dumps(published))
    params = hf.load_hf_config(tmp_path, 2)
    assert params["arch_type"] == int(ArchType.OLMO_HYBRID)
    assert (params["layer_period"], params["linear_key_head_dim"], params["linear_value_head_dim"],
            params["head_dim"]) == (4, 96, 192, 128)
    # the architecture implies no rotary embedding: a config that carries a theta is another model
    published["rope_parameters"] = {"rope_theta": 500000}
    (tmp_path / "config.json").write_text(json.dumps(published))
    with pytest.raises(ValueError, match="no rotary embedding"):
        hf.load_hf_config(tmp_path, 2)
    with pytest.raises(NotImplementedError, match="tensor names"):
        hf.hf_tensor_plan(params)


def test_dense_decoders_compile_what_they_compiled():
    """The period scan and the ``ModelConfig`` changes leave the two dense
    configurations' programs as they were: digests of the lowered decode step
    and prefill chunk, written from PR 30's parent commit (the chunk's again
    by PR 35, whose ``forward`` walks the layer index at chunk width: the
    step's two digests are still PR 30's parent's)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import dense_hlo_digest
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
    with open(os.path.join(ROOT, "tests", "goldens", "dense_hlo_sha256.json"), encoding="utf-8") as f:
        assert dense_hlo_digest.digests() == json.load(f)


def test_counts_follow_the_issue_reckoning():
    """The counts module at the published sizes: 6.65 B plane weights, 123 KB
    of K/V a token, 53.1 MB of state a slot."""
    counts = _import("hybrid_counts_7b", os.path.join(HYBRID, "counts.py"))
    with open(os.path.join(BENCH, "configs", "olmo-hybrid-7b.json"), encoding="utf-8") as f:
        model = bench_run.model_view(json.load(f))
    assert round(counts.layer_matmul_weights(model) / 1e9, 2) == 6.65
    one = counts.decode_step_bytes(model, rows=0, context_tokens=1) - counts.decode_step_bytes(
        model, rows=0, context_tokens=0)
    assert one == 2 * 8 * 3840 * 2 == 122880
    k = counts.kernel_counts(model, "gated_delta_step", rows=1)
    assert k["calls_per_program"] == 24 and 24 * 30 * 96 * 192 * 4 == 53084160
    assert abs(k["bytes"] - 2 * 30 * 96 * 192 * 4) / k["bytes"] < 0.05
    assert counts.kernel_counts(model, "no_such_kernel", rows=1) is None


# -- the benchmark's seam, seen by tier-1 -------------------------------------


@pytest.mark.parametrize("control, correct", [("none", True), ("droplayer", False), ("dropblock", False),
                                              ("dropstate", False), ("nodecay", False), ("bf16state", False)])
def test_whole_command_rehearsal(control, correct, capsys):
    """``benchmark/run.py`` with the hybrid's modules at the tiny preset, from
    a manifest of its own: ``correct`` true, and false under each control the
    reference knows."""
    rc = bench_run.main(["--manifest", MANIFEST, "--workload", "tiny-olmo-hybrid.closed", "--seed",
                         str(3000000000 + int(hashlib.sha256(control.encode()).hexdigest(), 16) % 1000),
                         "--seconds", "1", "--control", control])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["failed"] == 0 and line["correct"] is correct, line["gap"]
