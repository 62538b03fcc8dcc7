"""A tick that carries a prefill chunk as ONE program in the gated-delta hybrid
(PR 55): ``models.hybrid.forward_and_step`` against ``forward`` followed by
``paged_sampled_step_guarded`` on the same inputs (same tokens, column, block
pool and state pool; padding behind ``n_valid`` leaves state and tail alone),
then the paged generator that dispatches it: every plain chunk goes through it
(one executable a bucket), the tick's live rows ride the tick's first chunk, and
every request's tokens are those of a generator that keeps its two programs.
CPU, the cell's selftest configuration (hidden 64, 8 layers = two periods of
three linear layers and a full one, float32); nothing here is a timing claim.

``TOL``, 5e-5 of a leaf's largest value: the joined rows' matmuls are the parts'
a row at a time, but a matmul of another width sums in another order, and the
chunk rule's triangular solve carries that rounding further than falcon's scan
does (tests/test_falcon_tick.py holds 1e-5). The yardstick is ``forward``
against ITSELF on the parent: the same 32 tokens as a full 32-bucket and as a
64-bucket with ``n_valid`` 32 differ by 2.1e-4 in a state whose largest value is
13.4 (1.5e-5 of it), 6.9e-5 of 11.8 in the tail; the tick against the two
programs reads at most 4.3e-4 of 16.1 (2.7e-5). A state carried wrongly, or a
padded position that entered it, reads 0.1 of the largest value and more."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.models import hybrid, llama
from dllama_tpu.ops import sampling
from dllama_tpu.runtime import flightrec, introspection
from dllama_tpu.runtime import telemetry as tm
from dllama_tpu.runtime.kvblocks import PagedKVCache, StateColumn, StatePool
from dllama_tpu.runtime.serving import BatchScheduler, PagedGenerator, Request

from test_forward_and_step import _drive
from test_olmo_hybrid import BENCH
from test_olmo_hybrid import bench, engine  # noqa: F401  (module-scoped fixtures: this file gets an engine of its own)

R, BS, M = 4, 16, 8          # slots, block size, table width (positions under 128)
TOL = 5e-5
REAL = os.path.join(BENCH, "configs", "olmo-hybrid-7b.json")


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
            jax.jit(lambda p, *a: hybrid.forward_and_step(p, cfg, *a)),
            jax.jit(sampling.sampled_token))


def _inputs(cfg, T, live, sampled=False, seed=0):
    """A column, a block pool and a state pool of noise (what is not written
    must come back as it went in), ``live`` rows with tables of their own at
    positions inside them, the others dead (null tables, a stale position).
    K/V belongs to the FULL layers alone, one a period; state and tail to the
    linear ones."""
    rng = np.random.default_rng([seed, T, len(live)])
    noise = lambda shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    pkv = PagedKVCache(*(noise((cfg.n_kv_layers, R * M + 1, cfg.n_kv_heads, BS, cfg.head_dim)) for _ in "kv"))
    spool = StatePool(s=noise(cfg.state_shape(R + 1)), conv=noise(cfg.conv_shape(R + 1)))
    col = StateColumn(*(noise((cfg.n_kv_layers, 1, cfg.n_kv_heads, 128, cfg.head_dim)) for _ in "kv"),
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
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_allclose(x, y, rtol=0, atol=TOL * max(1.0, float(np.abs(y).max())))


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
    """The padded positions never enter the rule or the convolution's tail:
    with other tokens behind ``n_valid`` the column's state and tail and every
    decode row's token come out bit for bit the same."""
    col, pools, tables, pos, tokens, chunk, knobs = _inputs(engine.cfg, T, [0, 3], seed=3)
    other = np.array(chunk)
    other[0, n_valid:] = (other[0, n_valid:] + 1 + np.arange(T - n_valid)) % engine.cfg.vocab_size
    tick = programs[2]
    run = lambda c: tick(engine.params, tokens, pos, (col, pools), tables, c, jnp.int32(16), jnp.int32(n_valid),
                         np.float32(0))
    (tok_a, _nf, logits_a), (col_a, pools_a) = run(chunk)
    (tok_b, _nf, logits_b), (col_b, pools_b) = run(other)
    for a, b in ((col_a.s, col_b.s), (col_a.conv, col_b.conv), (tok_a, tok_b), (logits_a, logits_b),
                 (pools_a[1].s, pools_b[1].s), (pools_a[1].conv, pools_b[1].conv), (pools_a[0].k, pools_b[0].k)):
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
    ``jit_forward_and_step``, as the dense decoders', falcon's and lfm2's are."""
    from dllama_tpu.runtime import steppack

    assert hybrid.FAMILY.tick is hybrid.forward_and_step
    assert "jit_" + steppack.packed_program(hybrid.FAMILY.tick).__name__ == "jit_forward_and_step"


def _tick_shapes(engine, T):
    col, pools, tables, pos, tokens, chunk, _knobs = _inputs(engine.cfg, T, [1])
    return (engine.params, tokens, pos, (col, pools), tables, chunk, jnp.int32(16), jnp.int32(T), np.float32(0))


def test_one_read_of_every_plane_of_both_kinds_of_layer(engine, monkeypatch):
    """What the program is for: the traced bodies ask ``linear`` ONCE for each
    Q40 plane, over the joined ``T + R`` rows: five a linear layer (the packed
    ``w_in``, 17280 wide at the published sizes, first; ``w_out``; the
    feed-forward's three), seven a full one (q k v o and its feed-forward),
    and once for the head, over the R rows alone (``forward`` then the step ask
    twenty-four times and twice, the first head over all ``T`` rows of the
    chunk)."""
    cfg = engine.cfg
    seen = []
    real = hybrid.linear
    monkeypatch.setattr(hybrid, "linear", lambda x, w, **kw: seen.append((x.shape, real(x, w, **kw))) or seen[-1][1])
    jax.eval_shape(lambda p, *a: hybrid.forward_and_step(p, cfg, *a), *_tick_shapes(engine, 32))
    assert len(seen) == 5 + 7 + 1
    assert all(x[:2] == (1, 32 + R) for x, _y in seen[:12]) and seen[12][0][:2] == (R, 1)
    assert seen[0][1].shape == (1, 32 + R, cfg.lin_in_dim) and seen[12][1].shape == (R, 1, cfg.vocab_size)


@pytest.mark.parametrize("T", [48, 80])
def test_no_chunk_logits_in_the_lowered_program(engine, T):
    """The head runs for the rows alone: no array with a vocabulary axis in the
    tick program's lowered text has ``T`` or ``T + R`` rows, the logits have
    ``R`` (``forward``'s has the chunk's logits as its result). Lowered from
    shapes with a head and an embedding of 160 rows and chunks of 48 and 80,
    numbers nothing else in the tiny model has (its feed-forward is 128 wide, as
    its vocabulary is; a Q40 block is 32)."""
    import re

    cfg, V = engine.cfg, 160
    wide = lambda a: jax.ShapeDtypeStruct(tuple(V if d == cfg.vocab_size else d for d in a.shape), a.dtype)
    params, *args = _tick_shapes(engine, T)
    params = params._replace(embedding=wide(params.embedding), logits=jax.tree.map(wide, params.logits))
    text = jax.jit(lambda p, *a: hybrid.forward_and_step(p, cfg, *a)).lower(params, *args).as_text()
    shapes = set(re.findall(rf"tensor<([0-9x]+)x{V}x[a-z0-9]+>", text))
    assert {f"{R}x1", str(R)} <= shapes, shapes
    assert not any(str(n) in shape.split("x") for shape in shapes for n in (T, T + R)), shapes
    _tokens, _pos, (col, _pools), _tables, chunk, chunk_pos, n_valid, _poison = args
    text = jax.jit(lambda p, *a: llama.forward(p, cfg, *a)).lower(params, chunk, chunk_pos, col, n_valid).as_text()
    assert f"tensor<1x{T}x{V}xf32>" in text


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


def test_a_row_rides_a_direct_admission_beside_it(engine):
    """Without a scheduler (``gen.admit`` beside a live row): the row steps
    with the admission's first chunk, ``take_rows_rode`` says so once, and both
    requests finish; what stood here before the hybrid had a tick program
    (``test_olmo_hybrid.py::test_a_carried_chunk_stays_two_programs_here``)
    counted no rider."""
    gen = PagedGenerator(engine, n_slots=2)
    assert gen._tick is not None
    a = Request(rid=1, prompt_ids=_prompt(20, seed=4), max_tokens=3, stop_on_eos=False)
    b = Request(rid=2, prompt_ids=_prompt(40, seed=5), max_tokens=3, stop_on_eos=False)
    gen.admit(a, 0)
    gen.step()
    gen.admit(b, 1)
    assert len(a.tokens) == 2 and gen.take_rows_rode() and not gen.take_rows_rode()
    while gen.n_active:
        gen.step()
    assert a.error is None and b.error is None and len(a.tokens) == len(b.tokens) == 3
    assert gen._n_chunks >= 2 and gen._n_chunks_rows == 1


def test_the_first_token_is_the_references_argmax(bench, engine):
    """Held against the plain reference, not only against the other
    generator: a request prefilled by carried chunks and decoded beside
    others emits the reference's greedy continuation (gap 0)."""
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
    the cell's own file: widest bucket 256 and 4 slots are 260 rows, inside
    the kernel's chunk regime; no speculative verify, no plan."""
    from dllama_tpu.ops.quant_matmul import CHUNK_MAX_M

    with open(REAL, encoding="utf-8") as f:
        eng = json.load(f)["engine"]
    assert eng["slots"] == 4 and 256 + eng["slots"] <= CHUNK_MAX_M
    assert not eng.get("spec_lookup") and eng.get("tp", 1) == 1


# -- the chunk rule's kernel under the gate -------------------------------------


def _rule_notes(trace, plan=None):
    """What a trace of ``trace()`` notes of the delta rule's paths (``{"chunk:pallas": n, ..}``), under ``plan``."""
    from dllama_tpu.parallel import use_plan

    with introspection._thread_window() as notes, use_plan(plan):
        trace()
    return notes["gdn"]


def _two_programs(engine, T=32):
    cfg = engine.cfg
    params, *args = _tick_shapes(engine, T)
    _tokens, _pos, (col, _pools), _tables, chunk, chunk_pos, n_valid, _poison = args
    return {"forward": lambda: jax.eval_shape(lambda p, *a: hybrid.forward(p, cfg, *a), params, chunk, chunk_pos, col, n_valid),
            "forward_and_step": lambda: jax.eval_shape(lambda p, *a: hybrid.forward_and_step(p, cfg, *a), params, *args)}


@pytest.mark.parametrize("program", ["forward", "forward_and_step"])
def test_the_chunk_rule_takes_its_kernel_under_the_gate(engine, monkeypatch, program):
    """Traced with the kernels forced (interpret mode off a TPU: what ``auto``
    resolves to on one), ``forward`` and the tick program note ``chunk:pallas``
    and ask for ONE ``gated_delta_chunk``, the linear layer's body's (a
    period's three linear layers are one scan), over the chunk's rows alone;
    the tick's decode rows go through the step kernel beside it."""
    from dllama_tpu.ops import gated_delta as gd

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    calls = []
    entry = gd.gated_delta_chunk
    monkeypatch.setattr(gd, "gated_delta_chunk", lambda *a, **kw: calls.append((a[0].shape, kw)) or entry(*a, **kw))
    notes = _rule_notes(_two_programs(engine)[program])
    cfg = engine.cfg
    assert calls == [((1, 32, cfg.lin_heads, cfg.lin_key_dim), {"interpret": True})]
    assert notes == ({"chunk:pallas": 1} if program == "forward" else {"chunk:pallas": 1, "step:pallas": 1})


@pytest.mark.parametrize("program", ["forward", "forward_and_step", "the rule under a plan"])
def test_the_chunk_rule_keeps_its_xla_form_off_a_tpu_and_under_a_plan(engine, monkeypatch, program):
    """On the plain CPU path (``auto`` off a TPU: no kernel) both programs note
    ``chunk:xla`` and never ask for the kernel; nor does the rule under a mesh
    plan with the kernels forced (the auto-sharder cannot partition a
    ``pallas_call``; the period scan itself refuses a plan, so the rule is
    traced alone there)."""
    from dllama_tpu.ops import gated_delta as gd
    from dllama_tpu.parallel.api import make_tp_mesh

    monkeypatch.setattr(gd, "gated_delta_chunk", lambda *a, **kw: pytest.fail("the kernel was asked for"))
    if program in ("forward", "forward_and_step"):
        notes = _rule_notes(_two_programs(engine)[program])
        assert notes == ({"chunk:xla": 1} if program == "forward" else {"chunk:xla": 1, "step:xla": 1})
        return
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    rule = lambda: jax.eval_shape(hybrid._rule_chunk, f(1, 32, 2, 8), f(1, 32, 2, 8), f(1, 32, 2, 16), f(1, 32, 2), f(1, 32, 2),
                                  f(1, 2, 8, 16), jnp.int32(20))
    assert gd.chunk_kernel_choice(32) == {"interpret": True} and gd.chunk_kernel_choice(20) is None    # 20: sub-chunks of 4
    assert _rule_notes(rule, make_tp_mesh(1)) == {"chunk:xla": 1}
