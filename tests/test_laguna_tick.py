"""A tick that carries a prefill chunk as ONE program in the family of window
and full attention layers with an expert share (PR 57):
``models.laguna.forward_and_step`` against ``forward`` followed by
``paged_sampled_step_guarded`` on the same inputs (same tokens, column, BOTH
block pools through both tables; a row past its window; padding behind
``n_valid`` is not routed and its K/V rows are overwritten), then the paged
generator that dispatches it: every plain chunk goes through it (one executable
a bucket), the tick's live rows ride the tick's first chunk, and every request's
tokens are those of a generator that keeps its two programs. And the routing
counters: the joined dispatch is a chunk-form one and counts on the totals'
chunk row alone, each pair once. CPU, the cell's selftest configuration (hidden
64, two periods of [full, sliding x 3], window 32, 8 of 16 experts held of which
a token takes 4, float32); nothing here is a timing claim. The tolerances stand
above ``COLUMN_TOL``, each with what was seen."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import laguna, llama, share
from dllama_tpu.models.share import N_COUNTS, zero_totals
from dllama_tpu.ops import sampling
from dllama_tpu.runtime import flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.kvblocks import PagedKVCache
from dllama_tpu.runtime.serving import BatchScheduler, PagedGenerator

from test_forward_and_step import _drive
from test_laguna import BENCH, _reference_logits
from test_laguna import bench, engine  # noqa: F401  (module-scoped fixtures: this file gets an engine of its own)

R, BS, M = 4, 16, 8          # slots, block size, table width (positions under 128)
K, ROUTED, HELD = 4, 7, 8    # experts a token, routed layers, experts held (the selftest configuration's)
WINDOW, WB = 32, 3           # the sliding window, and the blocks of 16 it can span
S = 512                      # a column's positions (``LagunaColumn.zeros`` at the engine's seq_len)
BUCKETS = (32, 64, 128, 256)
# The chunk's rows are computed as ``forward`` computes them and come out bit for bit here; held to 1e-6 of a leaf's
# largest value. The decode rows' routed half is the PAIR form in the step (a GEMV a pair, summed back a token) and the
# chunk form in the tick (every chosen expert over every row, weighted): the same float32 products in another order, and
# this model's router rows carry a common direction of gain 800 (benchmark/laguna/weights.py) that turns a rounding of
# its input into 1e-4 of a weight. Of a leaf's largest value the pools differ by up to 5.8e-5 (bucket 256); with the step
# traced in the chunk form too (``share.step_form`` patched) they are bit for bit the tick's at bucket 32 and within
# 5.3e-5 at the wider ones, where XLA:CPU blocks a dot by its rows: the difference is the form's, not the program's.
COLUMN_TOL, POOL_TOL = 1e-6, 2e-4
REAL = os.path.join(BENCH, "configs", "laguna-s-2.1.json")


@pytest.fixture(autouse=True)
def _fresh_recorder_and_loader():
    import dllama_tpu.runtime.engine as engine_mod

    flightrec.recorder().reset()
    yield
    flightrec.recorder().reset()
    engine_mod.load_params_from_mfile = llama.load_params_from_mfile       # the weights module's seam


# -- the program ---------------------------------------------------------------


@pytest.fixture(scope="module")
def programs(engine):
    cfg = engine.cfg
    # functions of this file's own: a jit of llama.forward itself would share its trace cache with the worker's
    return (jax.jit(lambda p, *a: llama.forward(p, cfg, *a)),
            jax.jit(lambda p, *a: llama.paged_sampled_step_guarded(p, cfg, *a)),
            jax.jit(lambda p, *a: laguna.forward_and_step(p, cfg, *a)),
            jax.jit(sampling.sampled_token))


def _inputs(cfg, T, live, sampled=False, seed=0):
    """A column and two block pools of noise and running totals that are not
    zero (what is not written must come back as it went in); ``live`` rows with
    a full table of their own down to their position and a window table whose
    entries behind the window are null, the others dead (null in BOTH tables, a
    stale position). Row 2 stands past its window (position 77: its window
    table starts at entry 2), row 0 inside it."""
    rng = np.random.default_rng([seed, T, len(live)])
    noise = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pkv = PagedKVCache(*(noise((cfg.n_kv_layers, R * M + 1, cfg.n_kv_heads, BS, cfg.head_dim)) for _ in "kv"))
    wkv = PagedKVCache(*(noise((cfg.n_window_layers, R * WB + 1, cfg.n_kv_heads, BS, cfg.head_dim)) for _ in "kv"))
    totals = jnp.asarray(rng.integers(1, 1000, size=zero_totals(cfg).shape), jnp.int32)
    # the full layers dense at the slot's length, the sliding layers' buffer (the window and the widest chunk:
    # 384 rows here) at position 0
    # (drawn over all eight layers at the slot's length, as when the column was dense, and cut from that)
    k, v = (noise((cfg.n_layers, 1, cfg.n_kv_heads, S, cfg.head_dim)) for _ in "kv")
    full = np.arange(cfg.full_layer_at, cfg.n_layers, cfg.layer_period)
    slide = np.setdiff1d(np.arange(cfg.n_layers), full)
    col = laguna.LagunaColumn(k[full], v[full], k[slide][:, :, :, :cfg.window_column_rows],
                              v[slide][:, :, :, :cfg.window_column_rows], base=jnp.int32(0),
                              stats=jnp.asarray(rng.integers(1, 1000, size=totals.shape[1:]), jnp.int32))
    tables = np.zeros((2, R, M), np.int32)
    pos = rng.integers(0, 100, size=R).astype(np.int32)
    pos[0], pos[2] = 9, 77
    for i in live:
        last = int(pos[i]) // BS
        tables[0, i, :last + 1] = 1 + i * M + np.arange(last + 1)
        for b in range(max(0, int(pos[i]) - WINDOW + 1) // BS, last + 1):
            tables[1, i, b] = 1 + i * WB + b % WB
    temps, topps, coins = np.zeros(R, np.float32), np.zeros(R, np.float32), np.zeros(R, np.float32)
    if sampled:
        for i in live[::2] or [0]:
            temps[i], topps[i], coins[i] = 0.8, 0.9, rng.random()
    tokens = rng.integers(0, cfg.vocab_size, size=(R, 1)).astype(np.int32)
    chunk = rng.integers(0, cfg.vocab_size, size=(1, T)).astype(np.int32)
    return col, (pkv, wkv, totals), tables, pos, tokens, chunk, (temps, topps, coins)


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


def _same(a, b, tol):
    """Leaf by leaf within ``tol`` of the leaf's largest value."""
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, rtol=0, atol=tol * float(np.abs(y).max()))


ADDITIVE = np.r_[0, 1, N_COUNTS:N_COUNTS + HELD]      # held pairs, absent pairs, tokens a held expert: sums over dispatches


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("live", [[], [2], [0, 1, 2, 3]], ids=["no-row", "one-row", "every-row"])
@pytest.mark.parametrize("T,n_valid", [(32, 32), (32, 5), (64, 41), (128, 128), (256, 201)])
def test_the_tick_program_is_forward_then_the_step(engine, programs, T, n_valid, live, sampled):
    """Tokens and non-finite counts of the LIVE rows, the column's K/V over
    all eight layers and BOTH pools equal what the two programs give on the
    same inputs: greedy from the program's own argmax, and with the same coins
    from the sampler over the logits it hands back; a dead row writes the two
    null blocks alone. The counters: row 0 of the totals is what it was,
    ``col.stats`` goes back as it came, and the chunk row grew by what
    ``forward`` put on ``col.stats`` PLUS what the step put on row 0 (pairs
    and tokens an expert; the planes are the union's, at most the sum)."""
    cfg = engine.cfg
    inputs = _inputs(cfg, T, live, sampled)
    (tok_a, nf_a, col_a, pools_a), (tok_b, nf_b, col_b, pools_b) = _both(engine, programs, *inputs, chunk_pos=16,
                                                                         n_valid=n_valid)
    np.testing.assert_array_equal(np.asarray(tok_a)[live], np.asarray(tok_b)[live])
    np.testing.assert_array_equal(np.asarray(nf_a), np.asarray(nf_b))
    assert not np.asarray(nf_b).any()
    _same(col_a._replace(stats=None), col_b._replace(stats=None), COLUMN_TOL)
    _same(pools_a[:2], pools_b[:2], POOL_TOL)
    col0, (pkv0, wkv0, totals0), tables, pos = inputs[0], inputs[1], inputs[2], inputs[3]
    totals0, stats0 = np.asarray(totals0), np.asarray(col0.stats)
    chunk_added, step_added = np.asarray(col_a.stats) - stats0, np.asarray(pools_a[2])[0] - totals0[0]
    tick_added = np.asarray(pools_b[2]) - totals0
    np.testing.assert_array_equal(np.asarray(col_b.stats), stats0)
    assert not tick_added[0].any()
    np.testing.assert_array_equal(tick_added[1][ADDITIVE], (chunk_added + step_added)[ADDITIVE])
    assert tick_added[1][0] + tick_added[1][1] == K * ROUTED * (n_valid + len(live))
    assert max(chunk_added[3], step_added[3]) <= tick_added[1][3] <= chunk_added[3] + step_added[3]
    assert tick_added[1][2] >= (T + R) * tick_added[1][3] // ROUTED       # the every-row form feeds every row a plane
    # ... and what neither wrote is what went in: the rest of the column, and of each pool every block but the live
    # rows' newest and the null one (a dead row's write lands there)
    np.testing.assert_array_equal(np.asarray(col_b.k)[:, :, :, 16 + T:], np.asarray(col0.k)[:, :, :, 16 + T:])
    assert np.any(np.asarray(col_b.k)[:, :, :, 16:16 + T] != np.asarray(col0.k)[:, :, :, 16:16 + T])
    for pool_b, pool0, table in ((pools_b[0], pkv0, tables[0]), (pools_b[1], wkv0, tables[1])):
        written = {0} | {int(table[i, int(pos[i]) // BS]) for i in live}
        kept = [b for b in range(pool0.k.shape[1]) if b not in written]
        np.testing.assert_array_equal(np.asarray(pool_b.k)[:, kept], np.asarray(pool0.k)[:, kept])
        assert all(np.any(np.asarray(pool_b.v)[:, b] != np.asarray(pool0.v)[:, b]) for b in written - {0})


@pytest.mark.parametrize("T,n_valid", [(32, 5), (32, 29), (64, 33)])
def test_padding_behind_n_valid_is_not_routed_and_its_rows_are_overwritten(engine, programs, T, n_valid):
    """The padded positions are not routed and reach no decode row: with other
    tokens behind ``n_valid`` the counters, every decode row's token and logits
    and both pools come out bit for bit the same. Their K/V rows in the column
    do differ, and the next chunk (at ``chunk_pos + n_valid``) writes over
    every one of them: the two columns are the same again."""
    col, pools, tables, pos, tokens, chunk, knobs = _inputs(engine.cfg, T, [0, 2], seed=3)
    other = np.array(chunk)
    other[0, n_valid:] = (other[0, n_valid:] + 1 + np.arange(T - n_valid)) % engine.cfg.vocab_size
    tick = programs[2]
    run = lambda c, col, at, n: tick(engine.params, tokens, pos, (col, pools), tables, c, jnp.int32(at), jnp.int32(n),
                                     np.float32(0))
    (tok_a, _nf, logits_a), (col_a, pools_a) = run(chunk, col, 16, n_valid)
    (tok_b, _nf, logits_b), (col_b, pools_b) = run(other, col, 16, n_valid)
    for a, b in ((tok_a, tok_b), (logits_a, logits_b), (pools_a[0].k, pools_b[0].k), (pools_a[1].v, pools_b[1].v),
                 (pools_a[2], pools_b[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    added = np.asarray(pools_a[2])[1] - np.asarray(pools[2])[1]
    assert added[0] + added[1] == K * ROUTED * (n_valid + 2)
    np.testing.assert_array_equal(np.asarray(col_a.k)[:, :, :, :16 + n_valid], np.asarray(col_b.k)[:, :, :, :16 + n_valid])
    assert np.any(np.asarray(col_a.k)[:, :, :, 16 + n_valid:16 + T] != np.asarray(col_b.k)[:, :, :, 16 + n_valid:16 + T])
    (_t, _n, _l), (next_a, _p) = run(chunk, col_a, 16 + n_valid, T)
    (_t, _n, _l), (next_b, _p) = run(chunk, col_b, 16 + n_valid, T)
    for a, b in ((next_a.k, next_b.k), (next_a.v, next_b.v), (next_a.wk, next_b.wk), (next_a.wv, next_b.wv)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_poisoned_row_fails_alone(engine, programs):
    """A non-finite value in ONE row's window blocks reaches that row's logits
    and no other's, nor the chunk's column; the failpoint's selector poisons
    every row's logits, as the step's does."""
    cfg = engine.cfg
    col, (pkv, wkv, totals), tables, pos, tokens, chunk, knobs = _inputs(cfg, 32, [0, 1, 2, 3])
    own = tables[1, 1][tables[1, 1] != 0]
    wkv = wkv._replace(v=wkv.v.at[:, own].set(jnp.nan))                       # slot 1's window blocks
    (tok_a, nf_a, col_a, _), (tok_b, nf_b, col_b, _) = _both(engine, programs, col, (pkv, wkv, totals), tables, pos,
                                                             tokens, chunk, knobs, 0, 32)
    nf_b = np.asarray(nf_b)
    assert nf_b[1] > 0 and not nf_b[[0, 2, 3]].any()
    np.testing.assert_array_equal(np.asarray(nf_a), nf_b)
    np.testing.assert_array_equal(np.asarray(tok_a)[[0, 2, 3]], np.asarray(tok_b)[[0, 2, 3]])
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in jax.tree.leaves(col_b))
    _same(col_a._replace(stats=None), col_b._replace(stats=None), COLUMN_TOL)
    col, pools, *rest = _inputs(cfg, 32, [0, 1, 2, 3])
    _, (_tok, nf, _col, _pools) = _both(engine, programs, col, pools, *rest, 0, 32, poison=1.0)
    assert (np.asarray(nf) == cfg.vocab_size).all()


def test_the_module_is_named_for_the_chunk_and_is_the_familys_tick(engine):
    """The benchmark tells a chunk's program from a step's by the XLA module's
    name (``prefill_chunk_device_ms`` matches ``jit_forward``): this one is
    ``jit_forward_and_step``, as the dense decoders', falcon's and lfm2's are."""
    from dllama_tpu.models.family import family_of
    from dllama_tpu.runtime import steppack

    assert family_of(engine.cfg).tick is laguna.forward_and_step is laguna.FAMILY.tick
    assert "jit_" + steppack.packed_program(laguna.FAMILY.tick).__name__ == "jit_forward_and_step"


def test_one_read_of_every_plane_a_layer_and_one_grouped_dispatch(engine, monkeypatch):
    """What the program is for: each traced layer body asks ``linear`` ONCE a
    dense plane over the joined ``T + R`` rows (four an attention half),
    ``routed_ffn`` meets the joined rows as ONE dispatch of the chunk form a
    routed body (the step form is not traced at all), each body's attention is
    the chunk's over the column beside the rows' walk into THEIR pool (the
    sliding body's with the window), and the head runs over the R rows alone."""
    cfg = engine.cfg
    col, pools, tables, pos, tokens, chunk, _knobs = _inputs(cfg, 32, [1])
    seen, forms, walks = [], [], []
    real = laguna.linear
    monkeypatch.setattr(laguna, "linear", lambda x, w, **kw: seen.append(x.shape) or real(x, w, **kw))
    chunk_form, step_form = share._experts_chunk, share._experts_step
    monkeypatch.setattr(share, "_experts_chunk", lambda cfg, x, *a: forms.append(("chunk", x.shape[0]))
                        or chunk_form(cfg, x, *a))
    monkeypatch.setattr(share, "_experts_step", lambda cfg, x, *a: forms.append(("step", x.shape[0]))
                        or step_form(cfg, x, *a))
    paged, dense, window = laguna._attend_paged, laguna._attend_dense, laguna._attend_window_buffer
    monkeypatch.setattr(laguna, "_attend_paged", lambda cfg, q, k, v, kp, *a, **kw: walks.append(
        ("paged", q.shape[:2], kp.shape[0], kw.get("window", 0))) or paged(cfg, q, k, v, kp, *a, **kw))
    monkeypatch.setattr(laguna, "_attend_dense", lambda cfg, q, *a: walks.append(("dense", q.shape[:2]))
                        or dense(cfg, q, *a))
    monkeypatch.setattr(laguna, "_attend_window_buffer", lambda cfg, q, *a: walks.append(("window", q.shape[:2]))
                        or window(cfg, q, *a))
    jax.eval_shape(lambda p, *a: laguna.forward_and_step(p, cfg, *a), engine.params, tokens, pos, (col, pools),
                   tables, chunk, jnp.int32(16), jnp.int32(32), np.float32(0))
    # traced bodies: a period's full layer and its sliding layer (q k v wo each), then the head
    assert len(seen) == 4 + 4 + 1
    assert all(shape[:2] == (1, 32 + R) for shape in seen[:-1]) and seen[-1][:2] == (R, 1)
    assert forms == [("chunk", 32 + R)] * 2
    assert walks == [("dense", (1, 32)), ("paged", (R, 1), cfg.n_kv_layers, 0),
                     ("window", (1, 32)), ("paged", (R, 1), cfg.n_window_layers, WINDOW)]


@pytest.mark.parametrize("T", [48, 80])
def test_no_chunk_logits_in_the_lowered_program(engine, T):
    """The head runs for the rows alone: no array with a vocabulary axis in the
    tick program's lowered text has ``T`` or ``T + R`` rows, the logits have
    ``R`` (``forward``'s has the chunk's logits as its result). Lowered from
    shapes with a head and an embedding of 160 rows and chunks of 48 and 80,
    numbers nothing else in the tiny model has."""
    import re

    cfg, V = engine.cfg, 160
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    wide = lambda a: jax.ShapeDtypeStruct(tuple(V if d == cfg.vocab_size else d for d in a.shape), a.dtype)
    col, pools, tables, pos, tokens, _chunk, _knobs = _inputs(cfg, 32, [1])
    params = jax.tree.map(shape, engine.params)
    params = params._replace(embedding=wide(params.embedding), logits=jax.tree.map(wide, params.logits))
    chunk = jax.ShapeDtypeStruct((1, T), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    text = jax.jit(lambda p, *a: laguna.forward_and_step(p, cfg, *a)).lower(
        params, tokens, pos, (col, pools), tables, chunk, scalar, scalar, jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    shapes = set(re.findall(rf"tensor<([0-9x]+)x{V}x[a-z0-9]+>", text))
    assert {f"{R}x1", str(R)} <= shapes, shapes
    assert not any(str(n) in s.split("x") for s in shapes for n in (T, T + R)), shapes
    text = jax.jit(lambda p, *a: llama.forward(p, cfg, *a)).lower(params, chunk, scalar, col, scalar).as_text()
    assert f"tensor<1x{T}x{V}xf32>" in text


# -- through the generator and the scheduler ------------------------------------


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 250, size=n).tolist()


def _staggered(engine, two_programs, temps=(0.0,) * 6):
    prompts = [_prompt(n, seed=n) for n in (70, 33, 130, 97, 40, 161)]
    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    gen = sched.gen
    assert isinstance(gen, PagedGenerator) and gen._tick is not None
    if two_programs:
        gen._tick = None                 # what spec_lookup, a plan or a family without a tick leave it at
    gen.dispatched = {"step": 0, "tick": 0}             # which program stepped the rows, counted at the one door
    run_rows = gen._run_rows

    def counted(active, rows, chunk=None):
        gen.dispatched["step" if chunk is None else "tick"] += 1
        return run_rows(active, rows, chunk=chunk)

    gen._run_rows = counted
    try:
        kw = lambda i: dict(stop_on_eos=False, temperature=temps[i], topp=0.9, seed=90 + i)
        reqs = [sched.submit(prompts[0], 40, **kw(0))]          # 70 + 40 positions: its window moves while others arrive
        for i, p in enumerate(prompts[1:], 1):
            for _ in range(3):
                sched._tick()
            reqs.append(sched.submit(p, 12, **kw(i)))
        _drive(sched, reqs)
        totals = np.asarray(gen.moe_stats)
        assert gen.wpool.used_blocks() == 0 and gen.pool.used_blocks() == 0
    finally:
        sched.close()
    assert all(r.error is None and len(r.tokens) == (40 if i == 0 else 12) for i, r in enumerate(reqs))
    return [r.tokens for r in reqs], gen, totals, sum(len(p) - 1 for p in prompts)


@pytest.mark.parametrize("temps", [(0.0,) * 6, (0.8, 0.0, 1.1, 0.0, 0.7, 0.0)], ids=["greedy", "some-sample"])
def test_staggered_arrivals_emit_the_two_program_generators_tokens(engine, temps):
    """Requests admitted while others decode, prompts of one to three chunks,
    padded last chunks among them, the first row's window moving all the
    while: every request's tokens are those of the generator that dispatches
    ``forward`` and the step apart (a sampling row's with the same coins); the
    chunks with live rows were counted, and no plain ``forward`` was dispatched
    at all. **The counters over the run**: held + absent pairs of both rows sum
    to ``k`` x (live decode rows + valid chunk tokens) x routed layers in
    either generator, each pair counted once; with the tick the carried rows'
    pairs moved from row 0 to the chunk row, and the plane slots grew a STEP
    program's dispatch alone."""
    chunks = tm.registry().counter(tm.PREFILL_CHUNKS)
    live0, none0 = chunks.total(rows="live"), chunks.total(rows="none")
    seen0 = {e["program"] for e in introspection.ledger().snapshot()["events"]
             if e["scope"] == engine.introspection_scope}
    carried, gen, totals, prefilled = _staggered(engine, False, temps)
    live, none = chunks.total(rows="live") - live0, chunks.total(rows="none") - none0
    assert live > 0 and none > 0            # the first prompt's chunks had nobody beside them
    assert (gen._n_chunks, gen._n_chunks_rows) == (live + none, live)
    programs = {e["program"] for e in introspection.ledger().snapshot()["events"]
                if e["scope"] == engine.introspection_scope}
    assert "forward_and_step" in programs and ("forward" in seen0 or "forward" not in programs)
    plain, gen2, totals2, _ = _staggered(engine, True, temps)
    assert carried == plain
    assert gen2._n_chunks == live + none and gen2._n_chunks_rows == 0
    # a request's first token comes from the commit's step, so its every token is a decode row
    every = K * ROUTED * (40 + 5 * 12 + prefilled)
    assert totals[:, :2].sum() == totals2[:, :2].sum() == every
    assert totals2[1, :2].sum() == K * ROUTED * prefilled
    rode = (totals[1, :2].sum() - totals2[1, :2].sum()) // (K * ROUTED)      # decode rows a carried tick stepped
    assert rode >= live and totals[0, :2].sum() == totals2[0, :2].sum() - rode * K * ROUTED
    slots_a_step = ROUTED * gen.cfg.n_experts
    assert gen.dispatched["tick"] == live and gen2.dispatched["tick"] == 0
    assert gen._moe_plane_slots == gen.dispatched["step"] * slots_a_step       # a carried tick ran no step program
    assert gen2._moe_plane_slots == gen2.dispatched["step"] * slots_a_step


def test_a_carried_ticks_span_carries_nothing_of_the_step_programs(engine, tmp_path):
    """While a profiler listens: the ``step_wait`` span of a tick whose rows
    the STEP program stepped carries ``kv_walk_blocks`` and its own
    ``moe_pairs``; a carried tick's carries no walk and ``moe_pairs`` 0, and
    the running totals the step readers take (``moe_step_held``,
    ``moe_planes``, ``moe_plane_slots``) stand still across it while
    ``moe_chunk_held`` grows: what ``expert_gemv_hbm_share`` divides by is
    the step program's kernel time, which holds none of a carried tick."""
    import program_spans        # benchmark/program_spans.py (test_laguna put benchmark/ on sys.path)

    sched = BatchScheduler(engine, n_slots=3, _start_thread=False)
    try:
        first = sched.submit(_prompt(40, 7), 40, stop_on_eos=False)
        for _ in range(4):
            sched._tick()
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            for _ in range(2):
                sched._tick()
            second = sched.submit(_prompt(130, 8), 6, stop_on_eos=False)
            _drive(sched, [first, second])
        assert sched.gen._n_chunks_rows >= 1
    finally:
        sched.close()
    spans = program_spans.load(program_spans.newest_trace(trace_dir))
    waits = [st for t in spans["ticks"] for name, _s, _e, st in t["children"] if name == "step_wait"]
    carried = [i for i, st in enumerate(waits) if "kv_walk_blocks" not in st]
    assert carried and len(carried) < len(waits) and carried[0] > 0
    for i in carried:
        before, at = waits[i - 1], waits[i]
        assert int(at["moe_pairs"]) == 0 and int(at["moe_chunk_held"]) > int(before["moe_chunk_held"])
        assert all(int(at[key]) == int(before[key]) for key in ("moe_step_held", "moe_planes", "moe_plane_slots"))
        assert int(at["chunks_with_rows"]) == int(before["chunks_with_rows"]) + 1
    stepped = [st for st in waits if "kv_walk_blocks" in st]
    assert all(int(st["moe_pairs"]) > 0 for st in stepped)
    assert int(stepped[-1]["moe_plane_slots"]) - int(stepped[0]["moe_plane_slots"]) == (len(stepped) - 1) * ROUTED * HELD


def test_the_first_token_is_the_references_argmax(bench, engine):
    """Held against the plain reference, not only against the other
    generator: a request prefilled by carried chunks and decoded beside
    others, past its window, emits the reference's greedy continuation."""
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
        want = _reference_logits(bench, engine.params, prompt + list(req.tokens))
        assert [int(r.argmax()) for r in want[len(prompt) - 1:-1]] == list(req.tokens)


def test_one_tick_executable_a_bucket_and_none_from_churn(engine):
    """Admit / retire churn over every bucket compiles the tick program once a
    bucket and then nothing: live rows or none (the chunk nobody rides hands
    the program null tables in the step's own shape, both pools'), first chunk
    or later, padded or full, the executable is the bucket's."""
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
    assert len(ticks) == len(buckets) and buckets == set(BUCKETS)
    assert sum(e["program"] == "paged_sampled_step" for e in events) <= 1
    assert not any(e["program"] == "forward" for e in events)


# -- the cell's own numbers -------------------------------------------------------


@pytest.fixture(scope="module")
def real():
    with open(REAL, encoding="utf-8") as f:
        return json.load(f)


def test_the_cells_engine_options_take_the_tick(real):
    """The conditions under which a generator takes ``family.tick``, read off
    the cell's own file: widest bucket 256 and 16 slots are 272 rows, inside
    the kernel's chunk regime; no speculative verify, no plan; and the
    narrowest tick (bucket 32 + 16 slots) is past the routed step form by
    either of its limits, so every tick's routed half is one chunk-form
    dispatch while the cell's 16-row step keeps the pair form."""
    from dllama_tpu.ops.quant_matmul import CHUNK_MAX_M

    eng = real["engine"]
    assert eng["slots"] == 16 and 256 + eng["slots"] <= CHUNK_MAX_M
    assert not eng.get("spec_lookup") and eng.get("tp", 1) == 1
    cfg = types.SimpleNamespace(n_active_experts=real["num_experts_per_tok"],
                                moe_router_width=real["program"]["router_width"])
    assert share.step_form(cfg, eng["slots"]) and not share.step_form(cfg, 32 + eng["slots"])
    assert 32 + eng["slots"] > share.STEP_FORM_MAX_ROWS


@pytest.mark.parametrize("bucket", BUCKETS)
def test_the_joined_rows_go_through_the_grouped_kernel_whole(real, bucket, monkeypatch):
    """The issue's arithmetic as a test: at laguna-s-2.1's planes (32 held
    experts, ten a token, 3072 x 1024 gather and 1024 x 3072 scatter, bfloat16
    scales) ``share._chunk_pieces`` gives ONE piece at every ``bucket + 16``
    rows, the fed layout's bound 1504-3744 rows, and the kernel's stripe is
    the plane's full width either way: a held plane is fetched once a run over
    the union of what the chunk and the rows chose, never once a piece."""
    from dllama_tpu.ops import expert_chunk as ec
    from dllama_tpu.ops import quant_matmul as qm
    from dllama_tpu.ops.linear import QuantizedWeight

    monkeypatch.setattr(qm, "on_tpu", lambda: True)            # the gate's answer for a chip, asked here
    E, k = real["num_experts"], real["num_experts_per_tok"]
    d, hid, NM = real["hidden_size"], real["moe_intermediate_size"], real["num_hidden_layers"] - 1
    N = bucket + real["engine"]["slots"]
    stack = lambda i, o: QuantizedWeight(scales=jax.ShapeDtypeStruct((NM, E, i // 32, o), jnp.bfloat16),
                                         codes=jax.ShapeDtypeStruct((NM, E, i, o), jnp.int8))
    lp = types.SimpleNamespace(we1=stack(d, hid), we2=stack(hid, d))
    x = jax.ShapeDtypeStruct((N, d), jnp.bfloat16)
    rows, kw = share._chunk_pieces(types.SimpleNamespace(n_experts=E), x, N, k, lp)
    assert rows == N and kw == {"interpret": False, "fast": True}
    fed = ec.fed_rows(N * k, E)
    assert fed == {32: 1504, 64: 1824, 128: 2464, 256: 3744}[bucket]
    assert ec.stripe(N, fed, d, hid, True, False) == hid and ec.stripe(N, fed, hid, d, True, True) == d
