"""What every family's tick program shares, alone and at a tiny size
(``models/llama.py``, "A tick that carries a chunk"): the joined rows and the
dead-row rule against a case written out by hand, ``_by_row`` / ``_join``
against each other, ONE layer's attention over both kinds of row against the
two forms it is made of, and the epilogue against
``paged_sampled_step_guarded``'s on the same logits. The programs made of
them are held to their lowered text by ``tests/test_program_digests.py``;
``parallel.multihost.replicated``, the other thing PR 63 said once, is held
here to the program it wraps and end to end by ``tests/test_multihost.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats.mfile import ArchType, RopeType
from dllama_tpu.models import llama
from dllama_tpu.models.config import ModelConfig
from dllama_tpu.runtime.kvblocks import PagedKVCache, StatePool
from dllama_tpu.runtime.kvcache import KVCache

T, R, BS, M = 5, 3, 4, 4


def _cfg():
    return ModelConfig(arch=ArchType.LLAMA, dim=32, hidden_dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=8,
                       vocab_size=64, seq_len=16, norm_epsilon=1e-5, rope_theta=10000.0, rope_type=RopeType.LLAMA)


def test_the_joined_rows_by_hand():
    """The chunk's ids and positions first, then one a slot; a row whose
    table starts with the null block is dead and takes the pool's null row."""
    chunk = jnp.asarray([[11, 12, 13, 14, 15]], jnp.int32)
    tokens = jnp.asarray([[7], [8], [9]], jnp.int32)
    np.testing.assert_array_equal(np.asarray(llama._join_tokens(chunk, tokens)), [11, 12, 13, 14, 15, 7, 8, 9])
    cpos, rpos, positions = llama._join_positions(jnp.int32(20), np.asarray([3, 0, 9], np.int64), T)
    np.testing.assert_array_equal(np.asarray(cpos), [[20, 21, 22, 23, 24]])
    np.testing.assert_array_equal(np.asarray(rpos), [[3], [0], [9]])
    np.testing.assert_array_equal(np.asarray(positions), [[20, 21, 22, 23, 24, 3, 0, 9]])
    assert cpos.dtype == rpos.dtype == positions.dtype == jnp.int32
    tables = jnp.asarray([[5, 6, 0, 0], [0, 0, 0, 0], [2, 0, 0, 0]], jnp.int32)
    live = llama._live_rows(tables)
    np.testing.assert_array_equal(np.asarray(live), [True, False, True])
    np.testing.assert_array_equal(np.asarray(llama._state_rows(live)), [1, StatePool.NULL, 3])
    # a table whose FIRST entry is null is dead whatever stands behind it (a window layer's may be null in front)
    assert not bool(llama._live_rows(jnp.asarray([[0, 4, 4, 4]], jnp.int32))[0])


@pytest.mark.parametrize("tail", [(), (3,), (2, 4)], ids=["rows", "vectors", "heads"])
def test_by_row_and_join_undo_each_other(tail):
    rng = np.random.default_rng(len(tail))
    a = jnp.asarray(rng.normal(size=(1, T + R, *tail)), jnp.float32)
    rows = llama._by_row(a, T)
    assert rows.shape == (R, 1, *tail)
    np.testing.assert_array_equal(np.asarray(rows[:, 0]), np.asarray(a[0, T:]))
    np.testing.assert_array_equal(np.asarray(llama._join(a[:, :T], rows)), np.asarray(a))
    c, r = jnp.asarray(rng.normal(size=(1, T, *tail)), jnp.float32), jnp.asarray(rng.normal(size=(R, 1, *tail)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(llama._by_row(llama._join(c, r), T)), np.asarray(r))


def test_at_and_put_undo_each_other():
    a = jnp.arange(24, dtype=jnp.float32).reshape(3, 2, 4)
    np.testing.assert_array_equal(np.asarray(llama._at(a, jnp.int32(2))), np.asarray(a[2]))
    b = llama._put(a, jnp.zeros((2, 4)), jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(llama._at(b, 1)), np.zeros((2, 4)))
    np.testing.assert_array_equal(np.asarray(llama._put(b, a[1], 1)), np.asarray(a))


def test_the_attend_split_is_the_two_forms_side_by_side():
    """The chunk's rows through ``_attend_dense`` into the column's layer,
    the decode rows through ``_attend_paged`` into the pools, bit for bit
    what each gives alone; the column's layer is fetched by a CALL, once."""
    cfg = _cfg()
    rng = np.random.default_rng(3)
    noise = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = noise(1, T + R, cfg.n_heads, cfg.head_dim)
    k, v = noise(1, T + R, cfg.n_kv_heads, cfg.head_dim), noise(1, T + R, cfg.n_kv_heads, cfg.head_dim)
    col = KVCache(*(noise(1, cfg.n_kv_heads, cfg.seq_len, cfg.head_dim) for _ in "kv"))       # ONE layer's
    pool = PagedKVCache(*(noise(cfg.n_layers, R * M + 1, cfg.n_kv_heads, BS, cfg.head_dim) for _ in "kv"))
    tables = np.zeros((R, M), np.int32)
    pos = np.asarray([6, 0, 2], np.int32)
    for i in (0, 2):                                                                              # row 1 is dead
        tables[i, :pos[i] // BS + 1] = 1 + i * M + np.arange(pos[i] // BS + 1)
    chunk_pos, l = jnp.int32(4), jnp.int32(1)
    cpos, rpos, _ = llama._join_positions(chunk_pos, pos, T)
    fetched = []

    def column():
        fetched.append(1)
        return col.k, col.v

    att, k_l, v_l, k_pool, v_pool = llama._attend_split(cfg, q, k, v, T, column, pool.k, pool.v, l, chunk_pos, cpos,
                                                        rpos, jnp.asarray(tables))
    assert fetched == [1]
    att_c, k_c, v_c = llama._attend_dense(cfg, q[:, :T], k[:, :T], v[:, :T], col.k, col.v, chunk_pos, cpos)
    rows = lambda a: jnp.swapaxes(a[:, T:], 0, 1)
    att_r, k_p, v_p = llama._attend_paged(cfg, rows(q), rows(k), rows(v), pool.k, pool.v, l, rpos, jnp.asarray(tables))
    np.testing.assert_array_equal(np.asarray(att[:, :T]), np.asarray(att_c))
    np.testing.assert_array_equal(np.asarray(att[0, T:]), np.asarray(att_r[:, 0]))
    for got, want in ((k_l, k_c), (v_l, v_c), (k_pool, k_p), (v_pool, v_p)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the other layer of the pool, and every block but the null one and the live rows' own, came back as they went in
    np.testing.assert_array_equal(np.asarray(k_pool[0]), np.asarray(pool.k[0]))
    touched = {0, int(tables[0, pos[0] // BS]), int(tables[2, pos[2] // BS])}
    for b in set(range(R * M + 1)) - touched:
        np.testing.assert_array_equal(np.asarray(k_pool[1, b]), np.asarray(pool.k[1, b]))


@pytest.mark.parametrize("code", [0.0, 1.0, 2.0, 3.0], ids=["clean", "nan", "inf", "wire-code-passes-clean"])
def test_the_epilogue_is_the_steps_on_the_same_logits(code, monkeypatch):
    """``_pick_rows`` over hidden rows whose head is a fixed table of logits
    against ``paged_sampled_step_guarded`` over a ``paged_forward`` that
    gives the same table: the poison, the argmax (what the step's sampler
    gives rows that do not sample) and the non-finite count, code by code."""
    cfg = _cfg()
    V = cfg.vocab_size
    table = jnp.asarray(np.random.default_rng(11).normal(size=(R, V)), jnp.float32)
    x = jnp.zeros((1, T + R, cfg.dim), jnp.float32).at[0, T:, 0].set(jnp.arange(R, dtype=jnp.float32))

    def head(params, cfg_, rows):
        assert params is None and cfg_ is cfg and rows.shape == (R, 1, cfg.dim)        # the R decode rows ALONE
        return table[rows[:, :, 0].astype(jnp.int32)]                                  # [R, 1, V]

    greedy, nonfinite, last = llama._pick_rows(head, None, cfg, x, T, jnp.float32(code))
    monkeypatch.setattr(llama, "paged_forward", lambda params, cfg_, tokens, pos, pkv, tables: (table[:, None], pkv))
    zeros = jnp.zeros((R,), jnp.float32)
    (tok, nf), _ = llama.paged_sampled_step_guarded(None, cfg, jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
                                                    None, None, zeros, zeros, zeros, jnp.float32(code))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(tok))
    np.testing.assert_array_equal(np.asarray(nonfinite), np.asarray(nf))
    hit = code in (1.0, 2.0)
    assert (np.asarray(nonfinite) == (V if hit else 0)).all()
    if hit:
        assert (np.isnan(np.asarray(last)) if code == 1.0 else np.isposinf(np.asarray(last))).all()
    else:
        np.testing.assert_array_equal(np.asarray(last), np.asarray(table))
        np.testing.assert_array_equal(np.asarray(greedy), np.argmax(np.asarray(table), axis=-1))
    assert greedy.dtype == jnp.int32 and last.dtype == jnp.float32


@pytest.mark.parametrize("name", ["greedy_step_guarded", "sampled_step_guarded", "greedy_steps_guarded",
                                  "sampled_steps_guarded", "verify_step_guarded", "ragged_verify_step_guarded"])
def test_a_replicated_program_is_the_program(name):
    """``parallel.multihost.replicated`` gives the SAME program (same picks,
    same counts, same cache) with its logits and outputs constrained, which
    without a mesh plan constrains nothing; it bears the program's name
    behind ``replicated_`` and takes the program's positional arguments."""
    from dllama_tpu.parallel.multihost import replicated

    cfg = _cfg()
    params = llama.init_random_params(cfg, seed=2)
    program = getattr(llama, name)
    B, K = 2, 3
    wide = "verify" in name
    steps = "steps" in name
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab_size, (B, K + 1 if wide else 1)), jnp.int32)
    pos = jnp.asarray([2, 5], jnp.int32)
    knobs = ()
    if "sampled" in name or "ragged" in name:
        coins = jnp.full((K, B), 0.3, jnp.float32) if steps else jnp.full((B,), 0.3, jnp.float32)
        knobs = (jnp.asarray([0.0, 0.9], jnp.float32), jnp.full((B,), 0.9, jnp.float32), coins)
    args = (tokens[:, 0] if steps else tokens, pos, KVCache.create(cfg, batch_size=B), *knobs, *((K,) if steps else ()),
            jnp.float32(0.0))
    static = (1, len(args)) if steps else (1,)              # cfg, and a chunk's n_steps (in front of the poison)
    wrapped = replicated(program)
    assert wrapped.__name__ == "replicated_" + name
    want = jax.jit(program, static_argnums=static)(params, cfg, *args)
    got = jax.jit(wrapped, static_argnums=static)(params, cfg, *args)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(want[0][-1]) == 0).all()              # no row counts a non-finite logit


def test_a_replicated_programs_outputs_are_whole_on_every_device():
    """Under a tensor-parallel plan the wrapped program's picks and counts
    are fully replicated (every process of a multihost run reads them on its
    host) and are the picks of the program itself."""
    from dllama_tpu.parallel.api import make_tp_mesh, plan_scoped_jit, use_plan
    from dllama_tpu.parallel.multihost import replicated
    from dllama_tpu.parallel.sharding import kv_cache_sharding, shard_params

    cfg = _cfg()
    plan = make_tp_mesh(2)
    params = shard_params(plan, llama.init_random_params(cfg, seed=2))
    B = 2
    fresh = lambda: jax.device_put(KVCache.create(cfg, batch_size=B), kv_cache_sharding(plan, KVCache.create(cfg, batch_size=B)))
    args = (jnp.asarray([[3], [9]], jnp.int32), jnp.asarray([2, 5], jnp.int32))
    knobs = (jnp.asarray([0.0, 0.9], jnp.float32), jnp.full((B,), 0.9, jnp.float32), jnp.full((B,), 0.3, jnp.float32),
             jnp.float32(0.0))
    with use_plan(plan):
        (tok, nf), _ = plan_scoped_jit(replicated(llama.sampled_step_guarded), static_argnums=1)(
            params, cfg, *args, fresh(), *knobs)
        (want, _), _ = plan_scoped_jit(llama.sampled_step_guarded, static_argnums=1)(params, cfg, *args, fresh(), *knobs)
    assert tok.sharding.is_fully_replicated and nf.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(want))
    assert (np.asarray(nf) == 0).all()
