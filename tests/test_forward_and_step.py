"""A tick that carries a prefill chunk as ONE program (PR 47):
``models.llama.forward_and_step`` against ``forward`` followed by
``paged_sampled_step_guarded`` on the same inputs, then the paged generator
that dispatches it: every plain chunk of a dense decoder goes through it (one
executable a bucket), the tick's live rows ride the tick's first chunk and no
step follows in that tick, and every request's tokens stay the solo engine's.
CPU, tiny configurations; nothing here is a timing claim."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import mfile, tfile
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.ops import sampling
from dllama_tpu.runtime import flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.kvblocks import PagedKVCache
from dllama_tpu.runtime.kvcache import KVCache
from dllama_tpu.runtime.serving import BatchScheduler, PagedGenerator, Request

from helpers import byte_vocab_tokenizer, tiny_header_params, write_tiny_model

ARCHS = {"llama": (mfile.ArchType.LLAMA, mfile.RopeType.LLAMA),
         "qwen3": (mfile.ArchType.QWEN3, mfile.RopeType.FALCON)}
R, BS, M = 4, 16, 8          # slots, block size, table width (seq_len 128)


@pytest.fixture(autouse=True)
def _fresh_recorder():
    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()


# -- the program ---------------------------------------------------------------


@pytest.fixture(scope="module", params=sorted(ARCHS))
def model(request):
    arch, rope = ARCHS[request.param]
    cfg = ModelConfig(arch=arch, dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
                      vocab_size=128, seq_len=128, norm_epsilon=1e-5, rope_theta=10000.0, rope_type=rope,
                      compute_dtype="float32")
    params = llama.init_random_params(cfg, quantized=True, scale=0.3)
    programs = (jax.jit(llama.forward, static_argnums=1),
                jax.jit(llama.paged_sampled_step_guarded, static_argnums=1),
                jax.jit(llama.forward_and_step, static_argnums=1),
                jax.jit(sampling.sampled_token))
    return cfg, params, programs


def _inputs(cfg, T, live, sampled, seed=0):
    """A column and a pool of noise (what is not written must come back as it
    went in), ``live`` rows with tables of their own at positions inside them,
    the others dead (null tables, a stale position)."""
    rng = np.random.default_rng([seed, T, len(live)])
    noise = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool = jax.eval_shape(lambda: PagedKVCache.create(cfg, R * M + 1, BS, dtype=jnp.float32))
    pool = PagedKVCache(k=noise(pool.k.shape), v=noise(pool.v.shape))
    col = jax.eval_shape(lambda: KVCache.create(cfg, dtype=jnp.float32))
    col = KVCache(k=noise(col.k.shape), v=noise(col.v.shape))
    tables = np.zeros((R, M), np.int32)
    pos = rng.integers(0, 100, size=R).astype(np.int32)
    for i in live:
        n = int(pos[i]) // BS + 1
        tables[i, :n] = 1 + i * M + np.arange(n)
    temps, topps, coins = np.zeros(R, np.float32), np.zeros(R, np.float32), np.zeros(R, np.float32)
    if sampled:
        for i in live[::2] or [0]:
            temps[i], topps[i], coins[i] = 0.8, 0.9, rng.random()
    tokens = rng.integers(0, cfg.vocab_size, size=(R, 1)).astype(np.int32)
    chunk = rng.integers(0, cfg.vocab_size, size=(1, T)).astype(np.int32)
    return col, pool, tables, pos, tokens, chunk, (temps, topps, coins)


def _both(model, col, pool, tables, pos, tokens, chunk, knobs, chunk_pos, poison=0.0):
    cfg, params, (fwd, step, tick, sample) = model
    poison = np.float32(poison)
    _logits, col_a = fwd(params, cfg, chunk, jnp.int32(chunk_pos), col)
    (tok_a, nf_a), pool_a = step(params, cfg, tokens, pos, pool, tables, *knobs, poison)
    (tok_b, nf_b, logits), (col_b, pool_b) = tick(params, cfg, tokens, pos, (col, pool), tables, chunk,
                                                  jnp.int32(chunk_pos), poison)
    np.testing.assert_array_equal(np.asarray(tok_b), np.argmax(np.asarray(logits), axis=-1))
    if (knobs[0] > 0).any():         # a row samples: the generator runs the sampler over the rows' logits
        tok_b = sample(logits, *knobs)
    return (tok_a, nf_a, col_a, pool_a), (tok_b, nf_b, col_b, pool_b)


def _same_cache(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("live", [[], [2], [0, 1, 2, 3]], ids=["no-row", "one-row", "every-row"])
@pytest.mark.parametrize("T", [32, 64])
def test_the_tick_program_is_forward_then_the_step(model, T, live, sampled):
    """Tokens and non-finite counts of the LIVE rows, the whole column and the
    whole pool equal what the two programs give on the same inputs: greedy
    from the program's own argmax, and with the same coins from the sampler
    over the logits it hands back; a dead row writes the null block alone."""
    inputs = _inputs(model[0], T, live, sampled)
    (tok_a, nf_a, col_a, pool_a), (tok_b, nf_b, col_b, pool_b) = _both(model, *inputs, chunk_pos=16)
    np.testing.assert_array_equal(np.asarray(tok_a)[live], np.asarray(tok_b)[live])
    np.testing.assert_array_equal(np.asarray(nf_a), np.asarray(nf_b))
    assert not np.asarray(nf_b).any()
    _same_cache(col_a, col_b)
    _same_cache(pool_a, pool_b)
    # ... and what neither wrote is what went in: a live row's blocks past its position, the rest of the column
    pool0 = inputs[1]
    np.testing.assert_array_equal(np.asarray(pool_b.k)[:, R * M], np.asarray(pool0.k)[:, R * M])
    np.testing.assert_array_equal(np.asarray(col_b.k)[:, :, :, 16 + T:], np.asarray(inputs[0].k)[:, :, :, 16 + T:])
    assert np.any(np.asarray(col_b.k)[:, :, :, 16:16 + T] != np.asarray(inputs[0].k)[:, :, :, 16:16 + T])


def test_a_poisoned_row_fails_alone(model):
    """A non-finite value in ONE row's cached keys reaches that row's logits
    and no other's, nor the chunk's column; the failpoint's selector poisons
    every row's logits, as the step's does."""
    col, pool, tables, pos, tokens, chunk, knobs = _inputs(model[0], 32, [0, 1, 2, 3], False)
    bad = pool.k.at[:, int(tables[1, 0]), :, 0, :].set(jnp.nan)
    pool = PagedKVCache(k=bad, v=pool.v)
    pos = np.maximum(pos, 1).astype(np.int32)     # row 1 attends over the poisoned first cell
    (tok_a, nf_a, col_a, _), (tok_b, nf_b, col_b, _) = _both(model, col, pool, tables, pos, tokens, chunk, knobs, 0)
    nf_b = np.asarray(nf_b)
    assert nf_b[1] > 0 and not nf_b[[0, 2, 3]].any()
    np.testing.assert_array_equal(np.asarray(nf_a), nf_b)
    np.testing.assert_array_equal(np.asarray(tok_a)[[0, 2, 3]], np.asarray(tok_b)[[0, 2, 3]])
    assert np.isfinite(np.asarray(col_b.k)).all()
    _same_cache(col_a, col_b)
    col, pool, *rest = _inputs(model[0], 32, [0, 1, 2, 3], False)
    _, (_tok, nf, _col, _pool) = _both(model, col, pool, *rest, 0, poison=1.0)
    assert (np.asarray(nf) == model[0].vocab_size).all()


def test_the_program_is_the_dense_decoders_alone(model):
    """Another architecture owns a ``forward`` / ``paged_forward`` pair of its
    own: the program refuses it instead of running the Llama equations."""
    import dataclasses

    cfg, params, _ = model
    other = dataclasses.replace(cfg, sliding_window=64)
    assert other.paged_only
    col, pool, tables, pos, tokens, chunk, knobs = _inputs(cfg, 32, [0], False)
    with pytest.raises(ValueError, match="dense decoders"):
        llama.forward_and_step(params, other, tokens, pos, (col, pool), tables, chunk, jnp.int32(0),
                               np.float32(0))


def test_the_module_is_named_for_the_chunk_not_the_step():
    """The benchmark tells a chunk's program from a step's by the XLA module's
    name: this one is timed as a chunk (``jit_forward``), never as a step."""
    from dllama_tpu.runtime import steppack

    name = "jit_" + steppack.packed_program(llama.forward_and_step).__name__
    assert "jit_forward" in name and "paged_sampled_step" not in name


def test_joined_rows_are_a_chunk_to_the_kernel_and_walk_the_layer_index():
    """272 rows (the widest bucket and 16 slots) at Mistral's and Qwen3's
    plane shapes: the fused kernel's chunk regime takes them, and the layer
    scan walks the index, as for a 256-row ``forward``; past the regime's
    edge both say no."""
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.ops.linear import QuantizedWeight

    cfg = ModelConfig(arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                      head_dim=16, vocab_size=128, seq_len=128, norm_epsilon=1e-5, rope_theta=10000.0,
                      rope_type=mfile.RopeType.LLAMA, compute_dtype="bfloat16")
    S = jax.ShapeDtypeStruct
    planes = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096),
              (2560, 4096), (2560, 1024), (4096, 2560), (2560, 9728), (9728, 2560)]
    for k, n in planes:
        w = QuantizedWeight(scales=S((k // 32, n), jnp.bfloat16), codes=S((k, n), jnp.int8))
        for rows in (256, 256 + 16, qm.CHUNK_MAX_M):
            assert qm.fused_path((1, rows, k), w, True) == "chunk", (k, n, rows)
            assert qm._decode_blocks(rows, k, n, True)[0] == qm._decode_blocks(256, k, n, True)[0]
        assert qm.fused_path((1, qm.CHUNK_MAX_M + 1, k), w, True) is None
    assert qm.CHUNK_MAX_M >= 256 + 16
    assert llama._scan_by_index(cfg, 256 + 16) and llama._scan_by_index(cfg, qm.CHUNK_MAX_M)
    assert not llama._scan_by_index(cfg, qm.CHUNK_MAX_M + 1)


# -- through the generator and the scheduler ------------------------------------


@pytest.fixture(scope="module", params=sorted(ARCHS))
def files(request, tmp_path_factory):
    arch, rope = ARCHS[request.param]
    d = tmp_path_factory.mktemp(f"tick_{request.param}")
    mpath, tpath = d / "m.m", d / "t.t"
    extra = {"head_dim": 16} if request.param == "qwen3" else {}
    write_tiny_model(mpath, tiny_header_params(arch=arch, rope_type=rope, vocab_size=268, seq_len=256, **extra),
                     np.random.default_rng(47))
    tfile.write_tfile(tpath, byte_vocab_tokenizer())
    return str(mpath), str(tpath)


@pytest.fixture(scope="module")
def engine(files):
    eng = InferenceEngine(*files, tp=1, temperature=0.0, seed=3, kv_block_size=16)
    yield eng
    eng.close()


def _drive(sched, reqs, limit=600):
    for n in range(limit):
        if all(r.done.is_set() for r in reqs):
            return n
        sched._tick()
    raise AssertionError("requests did not finish")


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 250, size=n).tolist()


def _ticks():
    return [t for t in flightrec.recorder().snapshot()["ticks"] if not t.get("open")]


def _carried_ticks():
    """The finished ticks whose chunk carried the rows: the step's phases
    straight behind a ``prefill_dispatch`` (behind an ``admit_commit`` it is
    a plain step after a chunk nobody rode; with no upload, a step that found
    no row). With each, its phases' names."""
    out = []
    for t in _ticks():
        names = [n for n, _off, _ms in t["phase_spans"]]
        if any(run == ("prefill_dispatch", "step_prepare", "step_upload")
               for run in zip(names, names[1:], names[2:])):
            out.append((t, names))
    return out


def test_staggered_prompts_emit_the_solo_engines_tokens(files, engine):
    """Requests admitted while others decode, prompts of one to three chunks
    (one that ends on a carried chunk, one on a chunk with no live row): every
    request's greedy tokens are a fresh solo engine's; the chunks with live
    rows were counted, and a tick that carried one dispatched no step."""
    prompts = [_prompt(n, seed=n) for n in (70, 33, 130, 97, 40, 161)]
    want = []
    for p in prompts:
        solo = InferenceEngine(*files, tp=1, temperature=0.0, seed=3)
        want.append(solo.generate(p, 12, stop_on_eos=False).tokens)
        solo.close()
    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    gen = sched.gen
    assert isinstance(gen, PagedGenerator) and gen._tick is not None
    chunks = tm.registry().counter(tm.PREFILL_CHUNKS)
    live0, none0 = chunks.total(rows="live"), chunks.total(rows="none")
    try:
        reqs = [sched.submit(prompts[0], 12, stop_on_eos=False)]
        for p in prompts[1:]:
            for _ in range(3):
                sched._tick()
            reqs.append(sched.submit(p, 12, stop_on_eos=False))
        _drive(sched, reqs)
    finally:
        sched.close()
    for r, w in zip(reqs, want):
        assert r.error is None and r.tokens == w
    live, none = chunks.total(rows="live") - live0, chunks.total(rows="none") - none0
    assert live > 0 and none > 0            # the first prompt's chunks had nobody beside them
    assert (gen._n_chunks, gen._n_chunks_rows) == (live + none, live)
    # the ledger's view: no plain forward at all, and one step program
    programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                if e["scope"] == engine.introspection_scope}
    assert "forward_and_step" in programs and "forward" not in programs
    carried = _carried_ticks()
    assert carried
    for t, names in carried:
        assert t["prefill_tokens"] and t["decode_tokens"]
        assert names.count("step_upload") == names.count("step_wait") == 1, names     # one program a tick


def test_one_tick_executable_a_bucket_and_none_from_churn(engine):
    """Admit / retire churn over every bucket compiles the tick program once a
    bucket and then nothing: live rows or none, first chunk or later, the
    executable is the bucket's."""
    ledger = introspection.ledger()
    scope = engine.introspection_scope
    of_scope = lambda: [e for e in ledger.snapshot()["events"] if e["scope"] == scope]
    n0 = len(of_scope())                 # a generator's programs are its own: an earlier test's are not these
    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    seen0 = set(engine.seen_buckets)
    engine.seen_buckets.clear()
    lengths = (33, 65, 129, 257 - 16, 97, 40)

    def wave(seed):
        reqs = []
        for i, n in enumerate(lengths):
            reqs.append(sched.submit(_prompt(n, seed=seed + i), 6, stop_on_eos=False))
            sched._tick()
        _drive(sched, reqs)
        assert all(r.error is None for r in reqs)

    try:
        wave(100)
        wave(200)
        before = ledger.compile_count(scope)
        events = of_scope()[n0:]
        wave(300)
        wave(400)
        assert ledger.compile_count(scope) == before
        buckets = set(engine.seen_buckets)
    finally:
        sched.close()
        engine.seen_buckets |= seen0
    ticks = [e for e in events if e["program"] == "forward_and_step"]
    assert len(ticks) == len(buckets) == 3          # 128, 64 and 32: live rows or none, first chunk or later
    assert sum(e["program"] == "paged_sampled_step" for e in events) == 1
    assert not any(e["program"] == "forward" for e in events)


@pytest.mark.parametrize("why", ["spec_lookup", "decode_chunk", "too_many_slots"])
def test_everything_else_keeps_its_two_programs(files, why):
    """Speculative verify, a fused decode chunk and a batch wider than the
    kernel's chunk regime leaves room for keep ``forward`` and a step of their
    own: no knob, the generator reads what it was built with."""
    from dllama_tpu.ops.quant_matmul import CHUNK_MAX_M

    kw = {"spec_lookup": 3} if why == "spec_lookup" else {}
    eng = InferenceEngine(*files, tp=1, temperature=0.0, seed=3, kv_block_size=16, **kw)
    try:
        if why == "decode_chunk":
            eng.decode_chunk = 2         # refused at construction with paged KV: a direct caller's
        slots = CHUNK_MAX_M - max(eng.prefill_buckets) + 1 if why == "too_many_slots" else 2
        gen = PagedGenerator(eng, n_slots=slots)
        assert gen._tick is None
        a = Request(rid=1, prompt_ids=_prompt(40, 1), max_tokens=4, stop_on_eos=False)
        b = Request(rid=2, prompt_ids=_prompt(70, 2), max_tokens=4, stop_on_eos=False)
        gen.admit(a, 0)
        gen.step()
        gen.admit(b, 1)                  # with a live row beside it: still the plain forward
        assert not gen.take_rows_rode() and len(a.tokens) == 1
        while gen.n_active:
            gen.step()
        programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                    if e["scope"] == eng.introspection_scope}
        assert "forward" in programs and "forward_and_step" not in programs
        assert (gen._n_chunks, gen._n_chunks_rows) == (4, 0)    # 39 and 69 tokens: two chunks each
    finally:
        eng.close()


@pytest.mark.parametrize("family", ["OLMO_HYBRID", "LAGUNA", "FALCON_H1", "AXK1", "LFM2", "NEMOTRON_H"])
def test_every_other_family_keeps_its_two_programs_and_falcon_h1_brings_its_own(family, tmp_path):
    """A generator takes the tick program its FAMILY brings and asks no name
    (``test_family.TICK`` is the table): those that bring none dispatch
    ``forward`` for a chunk beside a live row and a step behind it, count no
    chunk as carried and never load ``forward_and_step``; one that brings its
    own (falcon_h1 since PR 52, lfm2 since PR 53, the hybrid since PR 55) loads no ``forward`` and
    counts one chunk as carried."""
    import dllama_tpu.runtime.engine as engine_mod
    from test_falcon_h1 import BENCH, _bench, _engine
    from test_family import TICK, TINY         # the six families' selftest files, and which of the eight bring a tick

    arch = mfile.ArchType[family]
    (folder, tiny), has_tick = TINY[arch], TICK[arch] is not None
    folder = os.path.join(BENCH, folder)
    try:
        eng = _engine(_bench(folder, os.path.join(folder, "selftest", "configs", tiny), f"tick_table_{family}"), tmp_path)
    finally:
        engine_mod.load_params_from_mfile = llama.load_params_from_mfile       # the weights module's seam
    try:
        gen = PagedGenerator(eng, n_slots=2)
        assert (gen._tick is not None) == has_tick
        small = lambda n, seed: [t % 100 + 1 for t in _prompt(n, seed)]   # inside every tiny vocabulary
        a = Request(rid=1, prompt_ids=small(40, 1), max_tokens=4, stop_on_eos=False)
        b = Request(rid=2, prompt_ids=small(70, 2), max_tokens=4, stop_on_eos=False)
        gen.admit(a, 0)
        gen.step()
        gen.admit(b, 1)                  # with a live row beside it
        assert gen.take_rows_rode() == has_tick and len(a.tokens) == 1 + has_tick
        while gen.n_active:
            gen.step()
        assert a.error is None and b.error is None and len(a.tokens) == len(b.tokens) == 4
        programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                    if e["scope"] == eng.introspection_scope}
        assert ("forward_and_step" in programs) == has_tick and ("forward" in programs) == (not has_tick)
        assert (gen._n_chunks, gen._n_chunks_rows) == (4, int(has_tick))  # 39 and 69 tokens: two chunks each
    finally:
        eng.close()


def test_a_direct_caller_sees_rows_ride_the_first_chunk_after_a_step(engine):
    """Without a scheduler: ``admit`` beside a live row steps that row with
    its first chunk (one token, settled), later chunks of the same call carry
    nobody, and ``step()`` steps everybody again."""
    gen = PagedGenerator(engine, n_slots=2)
    a = Request(rid=1, prompt_ids=_prompt(40, 1), max_tokens=20, stop_on_eos=False)
    b = Request(rid=2, prompt_ids=_prompt(100, 2), max_tokens=20, stop_on_eos=False)
    gen.admit(a, 0)
    assert (gen._n_chunks, gen._n_chunks_rows) == (2, 0) and not gen.take_rows_rode()
    gen.step()
    assert len(a.tokens) == 1
    gen.admit(b, 1)                      # 99 tokens to prefill: chunks of 64, 32 and a padded 32
    assert len(a.tokens) == 2 and len(b.tokens) == 0
    assert (gen._n_chunks, gen._n_chunks_rows) == (5, 1)
    assert not gen._chunks_pending[:-2] and len(gen._chunks_pending) == 2     # the carried one is settled
    assert gen.take_rows_rode() and not gen.take_rows_rode()
    gen.step()
    assert len(a.tokens) == 3 and len(b.tokens) == 1
    while gen.n_active:
        gen.step()
    solo_tokens = []
    for r in (a, b):
        assert r.error is None and len(r.tokens) == 20
        solo_tokens.append(r.tokens)
    assert solo_tokens[0] != solo_tokens[1]


def test_a_sampled_row_beside_a_chunk_draws_the_two_program_ticks_tokens(engine):
    """Rows that sample ride a chunk too: the tick program's argmax is set
    aside and the sampler runs over the logits it hands back, with the coins
    the step would have had. Same seeds through a generator that keeps two
    programs a tick: the same tokens, greedy bystander included."""
    def run(two_programs):
        gen = PagedGenerator(engine, n_slots=3)
        if two_programs:
            gen._tick = None             # what spec_lookup, a plan or another architecture leave it at
        reqs = [Request(rid=i, prompt_ids=_prompt(n, seed=50 + i), max_tokens=14, stop_on_eos=False,
                        temperature=t, topp=0.9, seed=77 + i)
                for i, (n, t) in enumerate([(40, 0.8), (70, 0.0), (100, 1.1)])]
        gen.admit(reqs[0], 0)
        gen.step()
        gen.admit(reqs[1], 1)            # 69 tokens beside a sampled row
        gen.step()
        gen.admit(reqs[2], 2)            # 99 beside a sampled and a greedy one
        while gen.n_active:
            gen.step()
        assert all(r.error is None and len(r.tokens) == 14 for r in reqs)
        return [r.tokens for r in reqs], gen._n_chunks_rows

    carried, rode = run(False)
    plain, none = run(True)
    assert carried == plain and rode > 0 and none == 0
    programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                if e["scope"] == engine.introspection_scope}
    assert "sample_rows" in programs


# -- the counter, the span totals, the phases, the attribution -------------------


def test_chunk_totals_ride_step_wait_and_the_phases_tile_a_carried_tick(engine, tmp_path):
    """Under a profiler every ``step_wait`` (a plain step's and a carried
    chunk's) carries the two running totals; the registry's two labels add up
    to the chunks dispatched; a tick that carried a chunk is tiled by the
    closed vocabulary, ``prefill_dispatch`` in front of the step's phases and
    ``admit_commit`` behind them."""
    from jax.profiler import ProfileData

    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    gen = sched.gen
    chunks = tm.registry().counter(tm.PREFILL_CHUNKS)
    c0 = chunks.total()
    n0, r0 = gen._n_chunks, gen._n_chunks_rows
    try:
        _drive(sched, [sched.submit(_prompt(50, 9), 4, stop_on_eos=False)])       # warm
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            reqs = [sched.submit(_prompt(70, 10), 10, stop_on_eos=False)]
            for n in (120, 45):
                for _ in range(2):
                    sched._tick()
                reqs.append(sched.submit(_prompt(n, n), 10, stop_on_eos=False))
            _drive(sched, reqs)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.close()
    assert chunks.total() - c0 == gen._n_chunks - n0
    assert chunks.total(rows="live") >= gen._n_chunks_rows - r0 > 0
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    waits = sorted(((ev.start_ns, dict(ev.stats)) for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for ev in line.events
                    if ev.name == tm.TICK_SPAN + ".step_wait"), key=lambda w: w[0])
    assert len(waits) >= 10 and all("chunks" in st and "chunks_with_rows" in st for _t, st in waits)
    seen = [(int(st["chunks"]), int(st["chunks_with_rows"])) for _t, st in waits]
    assert seen == sorted(seen) and seen[-1] == (gen._n_chunks, gen._n_chunks_rows)
    assert seen[-1][1] - seen[0][1] > 0 and all(c >= r for c, r in seen)
    # the benchmark's reader over the same spans: last less first
    added = [b - a for a, b in zip(seen[0], seen[-1])]
    assert 0 < 100.0 * added[1] / added[0] <= 100.0
    carried = _carried_ticks()
    assert carried
    commits = 0
    for t, names in carried:
        assert set(t["phases"]) <= set(tm.TICK_PHASES)
        at = names.index("step_prepare")
        assert names[at - 1] == "prefill_dispatch" and names.count("step_prepare") == 1
        assert names[at:at + 6] == ["step_prepare", "step_upload", "step_dispatch", "step_wait", "emit",
                                    "bookkeeping"], names
        commits += names[at + 6] == "admit_commit"
        wall = (t["t_end_ns"] - t["t_start_ns"]) / 1e6
        assert sum(t["phases"].values()) <= wall + 1e-6
        spans = t["phase_spans"]
        assert all(a[1] + a[2] <= b[1] + 1e-6 for a, b in zip(spans, spans[1:]))     # in order, no overlap
    assert commits >= 1                                                             # a prompt ended on a carried chunk


def test_a_carried_chunks_shares_sum_to_the_wall(engine):
    """``_settle_prefill`` over a chunk whose program stepped the rows: the
    admission is charged the whole wall from the enqueue to the end of the
    wait (no step's own wait lies in it), the bystander what the wall holds
    beyond a step of its own; a chunk a plain step waited for keeps the
    baseline taken out."""
    gen = PagedGenerator(engine, n_slots=2)
    own = Request(rid=1, prompt_ids=[1, 2], max_tokens=1)
    other = Request(rid=2, prompt_ids=[1, 2], max_tokens=1)
    gen.slots[1] = other
    gen._step_waits.extend([4.0, 6.0, 5.0])       # the running median of chunk-free steps: 5 ms
    hist = tm.registry().histogram(tm.PREFILL_CHUNK_MS)
    ms = 1_000_000
    from dllama_tpu.runtime.serving import _PendingChunk

    for rode, want_own, want_other in ((True, 30.0, 25.0), (False, 25.0, 25.0)):
        own.ms_prefill = other.ms_preempt = 0.0
        s0, n0 = hist.sum(), hist.count()
        gen._chunks_pending = [_PendingChunk(own, 0, 64, 60, 100 * ms), _PendingChunk(own, 0, 32, 10, 110 * ms)]
        gen._settle_prefill(120 * ms, 130 * ms, rode=rode)
        assert own.ms_prefill == pytest.approx(want_own) and other.ms_preempt == pytest.approx(want_other)
        assert hist.count() - n0 == 2 and hist.sum() - s0 == pytest.approx(want_own)
        assert not gen._chunks_pending and list(gen._step_waits) == [4.0, 6.0, 5.0]
    gen.slots[1] = None
