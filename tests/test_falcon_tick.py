"""A tick that carries a prefill chunk as ONE program in the family with an SSD
mixer beside attention (PR 52): ``models.falcon_h1.forward_and_step`` against
``forward`` followed by ``paged_sampled_step_guarded`` on the same inputs (same
tokens, column, block pool and state pool; padding behind ``n_valid`` leaves
state and tail alone), then the paged generator that dispatches it: every plain
chunk goes through it (one executable a bucket), the tick's live rows ride the
tick's first chunk, and every request's tokens are those of a generator that
keeps its two programs. CPU, the benchmark's tiny configuration (hidden 64, 4
layers, float32); nothing here is a timing claim. The tolerances are
tests/test_forward_and_step.py's: the joined rows' matmuls sum in the order the
parts' do, a row at a time."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import falcon_h1, llama
from dllama_tpu.ops import sampling
from dllama_tpu.runtime import flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.kvblocks import PagedKVCache, StateColumn, StatePool
from dllama_tpu.runtime.serving import BatchScheduler, PagedGenerator, Request

from test_falcon_h1 import FH1, TINY, _bench, _engine
from test_forward_and_step import _drive

R, BS, M = 4, 16, 8          # slots, block size, table width (positions under 128)


@pytest.fixture(autouse=True)
def _fresh_recorder_and_loader():
    import dllama_tpu.runtime.engine as engine_mod

    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()
    engine_mod.load_params_from_mfile = llama.load_params_from_mfile       # the weights module's seam


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    eng = _engine(_bench(FH1, TINY, "fh1_tick"), tmp_path_factory.mktemp("fh1_tick"))
    yield eng
    eng.close()


# -- the program ---------------------------------------------------------------


@pytest.fixture(scope="module")
def programs(engine):
    cfg = engine.cfg
    # functions of this file's own: a jit of llama.forward itself would share its trace cache with the worker's
    return (jax.jit(lambda p, *a: llama.forward(p, cfg, *a)),
            jax.jit(lambda p, *a: llama.paged_sampled_step_guarded(p, cfg, *a)),
            jax.jit(lambda p, *a: falcon_h1.forward_and_step(p, cfg, *a)),
            jax.jit(sampling.sampled_token))


def _inputs(cfg, T, live, sampled=False, seed=0):
    """A column, a block pool and a state pool of noise (what is not written
    must come back as it went in), ``live`` rows with tables of their own at
    positions inside them, the others dead (null tables, a stale position)."""
    rng = np.random.default_rng([seed, T, len(live)])
    noise = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pkv = PagedKVCache(*(noise((cfg.n_layers, R * M + 1, cfg.n_kv_heads, BS, cfg.head_dim)) for _ in "kv"))
    spool = StatePool(s=noise(cfg.state_shape(R + 1)), conv=noise(cfg.conv_shape(R + 1)))
    col = StateColumn(*(noise((cfg.n_layers, 1, cfg.n_kv_heads, 128, cfg.head_dim)) for _ in "kv"),
                      s=noise(cfg.state_shape(1)), conv=noise(cfg.conv_shape(1)))
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
    return col, (pkv, spool), tables, pos, tokens, chunk, (temps, topps, coins)


def _both(engine, programs, col, pools, tables, pos, tokens, chunk, knobs, chunk_pos, n_valid, poison=0.0):
    fwd, step, tick, sample = programs
    params, poison = engine.params, np.float32(poison)
    _logits, col_a = fwd(params, chunk, jnp.int32(chunk_pos), col, jnp.int32(n_valid))
    (tok_a, nf_a), pools_a = step(params, tokens, pos, pools, tables, *knobs, poison)
    (tok_b, nf_b, logits), (col_b, pools_b) = tick(params, tokens, pos, (col, pools), tables, chunk,
                                                   jnp.int32(chunk_pos), jnp.int32(n_valid), poison)
    np.testing.assert_array_equal(np.asarray(tok_b), np.argmax(np.asarray(logits), axis=-1))
    if (knobs[0] > 0).any():         # a row samples: the generator runs the sampler over the rows' logits
        tok_b = sample(logits, *knobs)
    return (tok_a, nf_a, col_a, pools_a), (tok_b, nf_b, col_b, pools_b)


def _same(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("live", [[], [2], [0, 1, 2, 3]], ids=["no-row", "one-row", "every-row"])
@pytest.mark.parametrize("T,n_valid", [(32, 32), (32, 5), (64, 64), (64, 41)])
def test_the_tick_program_is_forward_then_the_step(engine, programs, T, n_valid, live, sampled):
    """Tokens and non-finite counts of the LIVE rows, the whole column (K/V,
    state and tail) and both pools equal what the two programs give on the
    same inputs: greedy from the program's own argmax, and with the same coins
    from the sampler over the logits it hands back; a dead row writes the null
    block and the null row alone."""
    cfg = engine.cfg
    inputs = _inputs(cfg, T, live, sampled)
    (tok_a, nf_a, col_a, pools_a), (tok_b, nf_b, col_b, pools_b) = _both(engine, programs, *inputs, chunk_pos=16,
                                                                         n_valid=n_valid)
    np.testing.assert_array_equal(np.asarray(tok_a)[live], np.asarray(tok_b)[live])
    np.testing.assert_array_equal(np.asarray(nf_a), np.asarray(nf_b))
    assert not np.asarray(nf_b).any()
    _same(col_a, col_b)
    _same(pools_a, pools_b)
    # ... and what neither wrote is what went in: the rest of the column, the last row's last block, and the state
    # rows of the dead slots (row b + 1 is slot b's)
    col0, (pkv0, spool0) = inputs[0], inputs[1]
    np.testing.assert_array_equal(np.asarray(col_b.k)[:, :, :, 16 + T:], np.asarray(col0.k)[:, :, :, 16 + T:])
    assert np.any(np.asarray(col_b.k)[:, :, :, 16:16 + T] != np.asarray(col0.k)[:, :, :, 16:16 + T])
    np.testing.assert_array_equal(np.asarray(pools_b[0].k)[:, R * M], np.asarray(pkv0.k)[:, R * M])
    dead = [1 + i for i in range(R) if i not in live]
    for got, was in ((pools_b[1].s, spool0.s), (pools_b[1].conv, spool0.conv)):
        np.testing.assert_array_equal(np.asarray(got)[:, dead], np.asarray(was)[:, dead])
        assert all(np.any(np.asarray(got)[:, 1 + i] != np.asarray(was)[:, 1 + i]) for i in live)


@pytest.mark.parametrize("T,n_valid", [(32, 5), (32, 29), (64, 33)])
def test_padding_behind_n_valid_leaves_state_and_tail_alone(engine, programs, T, n_valid):
    """The padded positions never enter the recurrence or the convolution's
    tail: with other tokens behind ``n_valid`` the column's state and tail and
    every decode row's token come out bit for bit the same."""
    col, pools, tables, pos, tokens, chunk, knobs = _inputs(engine.cfg, T, [0, 3], seed=3)
    other = np.array(chunk)
    other[0, n_valid:] = (other[0, n_valid:] + 1 + np.arange(T - n_valid)) % engine.cfg.vocab_size
    tick = programs[2]
    run = lambda c: tick(engine.params, tokens, pos, (col, pools), tables, c, jnp.int32(16), jnp.int32(n_valid),
                         np.float32(0))
    (tok_a, _nf, logits_a), (col_a, pools_a) = run(chunk)
    (tok_b, _nf, logits_b), (col_b, pools_b) = run(other)
    for a, b in ((col_a.s, col_b.s), (col_a.conv, col_b.conv), (tok_a, tok_b), (logits_a, logits_b),
                 (pools_a[1].s, pools_b[1].s), (pools_a[0].k, pools_b[0].k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the valid positions' K/V rows are the same; the padded ones' differ (and are overwritten by the next chunk)
    np.testing.assert_array_equal(np.asarray(col_a.k)[:, :, :, :16 + n_valid], np.asarray(col_b.k)[:, :, :, :16 + n_valid])
    assert np.any(np.asarray(col_a.k)[:, :, :, 16 + n_valid:16 + T] != np.asarray(col_b.k)[:, :, :, 16 + n_valid:16 + T])
    assert np.any(np.asarray(col_a.s) != np.asarray(col.s))


def test_a_poisoned_row_fails_alone(engine, programs):
    """A non-finite value in ONE row's state reaches that row's logits and no
    other's, nor the chunk's column; the failpoint's selector poisons every
    row's logits, as the step's does."""
    cfg = engine.cfg
    col, (pkv, spool), tables, pos, tokens, chunk, knobs = _inputs(cfg, 32, [0, 1, 2, 3])
    spool = spool._replace(s=spool.s.at[:, 1 + 1, 0].set(jnp.nan))               # slot 1's row is 2
    (tok_a, nf_a, col_a, _), (tok_b, nf_b, col_b, _) = _both(engine, programs, col, (pkv, spool), tables, pos,
                                                             tokens, chunk, knobs, 0, 32)
    nf_b = np.asarray(nf_b)
    assert nf_b[1] > 0 and not nf_b[[0, 2, 3]].any()
    np.testing.assert_array_equal(np.asarray(nf_a), nf_b)
    np.testing.assert_array_equal(np.asarray(tok_a)[[0, 2, 3]], np.asarray(tok_b)[[0, 2, 3]])
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in jax.tree.leaves(col_b))
    _same(col_a, col_b)
    col, pools, *rest = _inputs(cfg, 32, [0, 1, 2, 3])
    _, (_tok, nf, _col, _pools) = _both(engine, programs, col, pools, *rest, 0, 32, poison=1.0)
    assert (np.asarray(nf) == cfg.vocab_size).all()


def test_the_module_is_named_for_the_chunk_and_is_the_familys_tick():
    """The benchmark tells a chunk's program from a step's by the XLA module's
    name (``prefill_chunk_device_ms`` matches ``jit_forward``): this one is
    ``jit_forward_and_step``, as the dense decoders' is."""
    from dllama_tpu.runtime import steppack

    assert falcon_h1.FAMILY.tick is falcon_h1.forward_and_step
    assert "jit_" + steppack.packed_program(falcon_h1.FAMILY.tick).__name__ == "jit_forward_and_step"


def test_one_read_of_every_plane_a_layer(engine, monkeypatch):
    """What the program is for: the traced layer body asks ``linear`` ONCE for
    each of the nine Q40 planes, over the joined ``T + R`` rows, and once for
    the head, over the R rows alone (``forward`` then the step ask eighteen
    times and twice, the first head over all ``T`` rows of the chunk)."""
    from dllama_tpu.models import ssd_mixer

    cfg = engine.cfg
    col, pools, tables, pos, tokens, chunk, _knobs = _inputs(cfg, 32, [1])
    seen = []
    real = falcon_h1.linear
    for mod in (falcon_h1, ssd_mixer):
        monkeypatch.setattr(mod, "linear", lambda x, w, **kw: seen.append(x.shape) or real(x, w, **kw))
    jax.eval_shape(lambda p, *a: falcon_h1.forward_and_step(p, cfg, *a), engine.params, tokens, pos, (col, pools),
                   tables, chunk, jnp.int32(16), jnp.int32(32), np.float32(0))
    assert len(seen) == 9 + 1
    assert all(shape[:2] == (1, 32 + R) for shape in seen[:9]) and seen[9][:2] == (R, 1)


# -- through the generator and the scheduler ------------------------------------


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 127, size=n).tolist()


def _staggered(engine, two_programs, temps=(0.0,) * 6):
    prompts = [_prompt(n, seed=n) for n in (70, 33, 130, 97, 40, 161)]
    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    gen = sched.gen
    assert isinstance(gen, PagedGenerator) and gen._tick is not None
    if two_programs:
        gen._tick = None                 # what spec_lookup, a plan or a family without a tick leave it at
    try:
        kw = lambda i: dict(stop_on_eos=False, temperature=temps[i], topp=0.9, seed=90 + i)
        reqs = [sched.submit(prompts[0], 12, **kw(0))]
        for i, p in enumerate(prompts[1:], 1):
            for _ in range(3):
                sched._tick()
            reqs.append(sched.submit(p, 12, **kw(i)))
        _drive(sched, reqs)
    finally:
        sched.close()
    assert all(r.error is None and len(r.tokens) == 12 for r in reqs)
    return [r.tokens for r in reqs], gen


@pytest.mark.parametrize("temps", [(0.0,) * 6, (0.8, 0.0, 1.1, 0.0, 0.7, 0.0)], ids=["greedy", "some-sample"])
def test_staggered_arrivals_emit_the_two_program_generators_tokens(engine, temps):
    """Requests admitted while others decode, prompts of one to three chunks,
    padded last chunks among them: every request's tokens are those of the
    generator that dispatches ``forward`` and the step apart (a sampling row's
    with the same coins); the chunks with live rows were counted, and no plain
    ``forward`` was dispatched at all."""
    chunks = tm.registry().counter(tm.PREFILL_CHUNKS)
    live0, none0 = chunks.total(rows="live"), chunks.total(rows="none")
    seen0 = {e["program"] for e in introspection.ledger().snapshot()["events"]
             if e["scope"] == engine.introspection_scope}
    carried, gen = _staggered(engine, False, temps)
    live, none = chunks.total(rows="live") - live0, chunks.total(rows="none") - none0
    assert live > 0 and none > 0            # the first prompt's chunks had nobody beside them
    assert (gen._n_chunks, gen._n_chunks_rows) == (live + none, live)
    programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                if e["scope"] == engine.introspection_scope}
    assert "forward_and_step" in programs and ("forward" in seen0 or "forward" not in programs)
    plain, gen = _staggered(engine, True, temps)
    assert carried == plain
    assert gen._n_chunks == live + none and gen._n_chunks_rows == 0


def test_the_first_token_is_the_references_argmax(engine):
    """Held against the plain reference, not only against the other
    generator: a request prefilled by carried chunks and decoded beside
    others emits the reference's greedy continuation (gap 0)."""
    bench = _bench(FH1, TINY, "fh1_tick_ref")
    sched = BatchScheduler(engine, n_slots=2, _start_thread=False)
    try:
        a, b = _prompt(150, 1), _prompt(37, 2)
        reqs = [sched.submit(a, 20, stop_on_eos=False)]
        for _ in range(4):
            sched._tick()
        reqs.append(sched.submit(b, 10, stop_on_eos=False))
        _drive(sched, reqs)
        assert sched.gen._n_chunks_rows > 0
    finally:
        sched.close()
    for prompt, req in zip((a, b), reqs):
        gaps = bench["reference"].reference_gaps(bench["model"], engine.params, prompt, list(req.tokens))
        assert float(gaps["gap"].max()) == 0.0


def test_one_tick_executable_a_bucket_and_none_from_churn(engine):
    """Admit / retire churn over every bucket compiles the tick program once a
    bucket and then nothing: live rows or none, first chunk or later, padded
    or full, the executable is the bucket's."""
    ledger = introspection.ledger()
    scope = engine.introspection_scope
    of_scope = lambda: [e for e in ledger.snapshot()["events"] if e["scope"] == scope]
    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    n0 = len(of_scope())                 # a generator's programs are its own: an earlier test's are not these
    seen0 = set(engine.seen_buckets)
    engine.seen_buckets.clear()
    lengths = (33, 65, 129, 257, 97, 40)

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
    assert len(ticks) == len(buckets) and buckets == {32, 64, 128, 256}
    assert sum(e["program"] == "paged_sampled_step" for e in events) <= 1
    assert not any(e["program"] == "forward" for e in events)


def test_the_cells_engine_options_take_the_tick():
    """The conditions under which a generator takes ``family.tick``, read off
    the cell's own file: widest bucket 256 and 16 slots are 272 rows, inside
    the kernel's chunk regime; no speculative verify, no plan."""
    from dllama_tpu.ops.quant_matmul import CHUNK_MAX_M

    with open(os.path.join(os.path.dirname(FH1), "configs", "falcon-h1-34b.json"), encoding="utf-8") as f:
        eng = json.load(f)["engine"]
    assert eng["slots"] == 16 and 256 + eng["slots"] <= CHUNK_MAX_M
    assert not eng.get("spec_lookup") and eng.get("tp", 1) == 1
