"""Ragged paged attention kernel vs the gather+oracle reference,
adversarially (the parity methodology of nn-vulkan-test.cpp, escalated:
the paged kernel replaces the PR6 ``pool[tables]`` gather, so every table
shape continuous batching can produce must reproduce the dense path's
floats). The kernel keeps the oracle's arithmetic and changes the ORDER
of the reductions over the cache axis (a running softmax over fetch
groups, bounded by each row's length), so the claim is a tolerance of a
few float32 ulps (``TOL``), not bit equality; every structural fault
these cases hunt (a wrong block, a leaked null block, a mask off by one
row, a group dropped at the bound) is wrong by order 1. A row whose table
starts with the null block is DEAD: the kernel writes zeros there
whatever its stale position says, and the oracle's row (attention over
the null block's garbage) is nobody's to read.

The reference side is the JITTED gather+oracle composition — the program
the seam in models/llama.py actually swaps out (eager op-by-op execution
rounds differently than a fused jaxpr; the claim is program-vs-program)."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.ops import paged_attention as pa
from dllama_tpu.ops.attention import attention
from dllama_tpu.ops.paged_attention import (
    kernel_choice,
    paged_ragged_attention,
    supports,
)

TOL = 2e-6  # interpret mode, outputs of order 1; 2e-5 compiled on the chip


def _reference(q, k_pool, v_pool, tables, positions, head_dim):
    """The gather+oracle pair, jitted — exactly what _paged_layer_step's
    fallback branch traces."""
    B, M = tables.shape
    n_kv, bs, hd = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]

    @jax.jit
    def ref(q, k_pool, v_pool, tables, positions):
        def view(pool):
            gathered = pool[tables]              # [B, M, n_kv, bs, hd]
            return jnp.moveaxis(gathered, 2, 1).reshape(
                B, n_kv, M * bs, hd)

        return attention(q, view(k_pool), view(v_pool), positions, head_dim)

    return ref(q, k_pool, v_pool, tables, positions)


def _kernel_1(q, k_pool, v_pool, tables, positions, hd, **kw):
    """The kernel over a one-layer pool: it takes the whole pool ``[L, ..]``
    and a layer index, these cases hold one layer's ``[n_blocks, ..]``."""
    kw.setdefault("interpret", True)
    return paged_ragged_attention(q, k_pool[None], v_pool[None], 0, tables,
                                  positions, hd, **kw)


def _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb, dtype=jnp.float32):
    k_pool = jnp.asarray(rng.standard_normal((nb, n_kv, bs, hd)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((nb, n_kv, bs, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((B, T, n_heads, hd)), jnp.float32)
    return q, k_pool, v_pool


def _assert_close(q, k_pool, v_pool, tables, positions, hd):
    """Kernel vs reference on every live row, to a few ulps (the blocked
    reference einsum and the kernel's per-group dots already differed in
    the last bit under jaxlib 0.9, max abs 2.4e-7, PR 22; the running
    softmax reassociates the sums over the cache axis besides); zeros on
    every dead row. Whether the COMPILED kernel holds its 2e-5 is the
    chip's to say (test_paged_kernel_compiled_parity_on_hw,
    tools/paged_attn_sweep.py)."""
    got = np.asarray(_kernel_1(q, k_pool, v_pool, tables, positions, hd))
    want = np.asarray(_reference(q, k_pool, v_pool, tables, positions, hd))
    live = np.asarray(tables)[:, 0] != 0
    np.testing.assert_allclose(got[live], want[live], rtol=TOL, atol=TOL)
    assert np.all(got[~live] == 0)
    return got


def test_scrambled_block_table_bitwise():
    """Arbitrary physical placement: every row's blocks land at scrambled
    pool ids (the steady-state of a churning allocator)."""
    rng = np.random.default_rng(0)
    B, T, n_heads, n_kv, hd, bs, M, nb = 3, 1, 8, 2, 16, 16, 4, 14
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32))
    positions = jnp.asarray([[37], [5], [63]], jnp.int32)
    _assert_close(q, kp, vp, tables, positions, hd)


def test_partial_tail_block_and_ragged_rows():
    """Each row mid-block at its own depth: the newest block is partially
    valid and masked per position, never per block."""
    rng = np.random.default_rng(1)
    B, T, n_heads, n_kv, hd, bs, M, nb = 4, 1, 8, 4, 32, 16, 8, 40
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    # depths chosen to hit block offsets 0, 1, bs-1 and a mid-block point
    positions = jnp.asarray([[0], [bs - 1], [bs], [3 * bs + 7]], jnp.int32)
    _assert_close(q, kp, vp, tables, positions, hd)


def test_shared_and_null_redirected_blocks():
    """Block-level sharing (two rows aliasing one physical prefix block —
    the prefix-reuse steady state) and CoW-retired tails redirected to the
    null block 0: the garbage behind null entries is position-masked on
    both paths identically."""
    rng = np.random.default_rng(2)
    B, T, n_heads, n_kv, hd, bs, M, nb = 3, 1, 4, 2, 16, 16, 6, 10
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = np.zeros((B, M), np.int32)        # all-null tails
    tables[0, :3] = [5, 6, 7]
    tables[1, :3] = [5, 6, 8]                  # shares blocks 5, 6 with row 0
    tables[2, :2] = [9, 3]
    tables = jnp.asarray(tables)
    positions = jnp.asarray([[2 * bs + 3], [2 * bs + 9], [bs + 1]], jnp.int32)
    _assert_close(q, kp, vp, tables, positions, hd)


@pytest.mark.parametrize("t", [1, 16])
def test_query_width_edges(t):
    """T=1 (decode) and T=16 (chunked-prefill tail / verify width)."""
    rng = np.random.default_rng(3 + t)
    B, n_heads, n_kv, hd, bs, M, nb = 2, 8, 2, 16, 16, 6, 20
    q, kp, vp = _mk(rng, B, t, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    positions = (jnp.asarray([3, 2 * bs + 1], jnp.int32)[:, None]
                 + jnp.arange(t)[None, :])
    _assert_close(q, kp, vp, tables, positions, hd)


@pytest.mark.parametrize("hd", [40, 72])
def test_non_128_aligned_head_dims(hd):
    rng = np.random.default_rng(11)
    B, T, n_heads, n_kv, bs, M, nb = 2, 2, 4, 4, 8, 4, 9
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(0, nb, (B, M)).astype(np.int32))
    positions = (jnp.asarray([7, 19], jnp.int32)[:, None]
                 + jnp.arange(T)[None, :])
    _assert_close(q, kp, vp, tables, positions, hd)


def test_bf16_pool_bitwise():
    """The serving pool dtype: both paths cast pool rows to f32 the same
    way, so bf16 storage holds the same tolerance."""
    rng = np.random.default_rng(21)
    B, T, n_heads, n_kv, hd, bs, M, nb = 2, 1, 4, 2, 16, 16, 4, 8
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb,
                    dtype=jnp.bfloat16)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    positions = jnp.asarray([[9], [3 * bs - 1]], jnp.int32)
    _assert_close(q, kp, vp, tables, positions, hd)


# (B, T, n_heads, n_kv, hd, M) and each row's length (0 = a dead row: an
# all-null table under a stale depth). Blocks of 16 and ``_GROUP_TOKENS``
# 128 make a fetch group 8 blocks = 128 positions, the table 20 entries.
_BS, _M = 16, 20
_WALKS = {
    "length-1": ((2, 1, 8, 2, 16, _M), [1, 200]),
    "length-bs-1": ((2, 1, 8, 2, 16, _M), [_BS - 1, 200]),
    "length-bs": ((2, 1, 8, 2, 16, _M), [_BS, 200]),
    "length-bs+1": ((2, 1, 8, 2, 16, _M), [_BS + 1, 200]),
    "one-short-of-a-group": ((2, 1, 8, 2, 16, _M), [127, 200]),
    "exactly-a-group": ((2, 1, 8, 2, 16, _M), [128, 200]),
    "one-past-a-group": ((2, 1, 8, 2, 16, _M), [129, 200]),
    "two-groups-exactly": ((2, 1, 8, 2, 16, _M), [256, 3]),
    "whole-table": ((2, 1, 8, 2, 16, _M), [_BS * _M, _BS * _M]),
    "very-different-lengths": ((5, 1, 8, 2, 16, _M), [1, 320, 17, 250, 130]),
    "dead-row-between-live": ((4, 1, 8, 2, 16, _M), [70, 0, 300, 0]),
    "dead-first-and-last": ((4, 1, 8, 2, 16, _M), [0, 33, 129, 0]),
    "all-dead": ((3, 1, 8, 2, 16, _M), [0, 0, 0]),
    "mha-30-heads": ((2, 1, 30, 30, 16, _M), [41, 290]),
    "t16-over-a-group-edge": ((2, 16, 8, 2, 16, _M), [120 + 16, 16]),
}


@pytest.mark.parametrize("case", list(_WALKS))
def test_walk_bounded_by_each_rows_length(case):
    """The walk and the running softmax stop at each row's own bound:
    lengths around a block's and a fetch group's edges, rows of very
    different lengths in one call, dead rows (zero and finite whatever
    their stale depth; the live rows beside them unchanged BITWISE when
    that depth changes), 30:30 heads, a 16-wide query over a group edge."""
    (B, T, n_heads, n_kv, hd, M), lengths = _WALKS[case]
    assert pa._plan(n_kv, T * n_heads // n_kv, hd, M, _BS, 4)[1] * _BS == 128
    rng = np.random.default_rng(sum(map(ord, case)))
    nb = B * M + 1
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, _BS, M, nb)
    tables = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
    pos0 = np.zeros(B, np.int32)
    for b, length in enumerate(lengths):
        if length == 0:
            tables[b] = 0                       # retired: nothing resets pos
            pos0[b] = rng.integers(1, M * _BS)
        else:
            tables[b, -(-length // _BS):] = 0   # the allocator's null tail
            pos0[b] = length - T
    positions = jnp.asarray(pos0[:, None] + np.arange(T)[None, :], jnp.int32)
    got = _assert_close(q, kp, vp, jnp.asarray(tables), positions, hd)
    assert np.all(np.isfinite(got))
    dead = np.asarray(lengths) == 0
    if dead.any():
        other = np.where(dead, (pos0 + 77) % (M * _BS), pos0).astype(np.int32)
        again = _kernel_1(
            q, kp, vp, jnp.asarray(tables),
            jnp.asarray(other[:, None] + np.arange(T)[None, :], jnp.int32),
            hd)
        np.testing.assert_array_equal(np.asarray(again), got)


def test_a_row_never_reads_past_its_bound():
    """Blocks the table names past a row's bound hold NaN (a neighbour's
    poisoned cache, a block freed mid-flight): the walk must not fetch
    them, so the output stays finite and equal to the clean run's. The
    last fetch group re-reads the row's own newest block instead."""
    rng = np.random.default_rng(77)
    B, T, n_heads, n_kv, hd, M = 2, 1, 8, 2, 16, _M
    nb = B * M + 1
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, _BS, M, nb)
    tables = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
    positions = jnp.asarray([[40], [150]], jnp.int32)
    clean = _kernel_1(q, kp, vp, jnp.asarray(tables), positions, hd)
    past = np.concatenate([tables[0, 3:], tables[1, 10:]])
    poisoned = _kernel_1(
        q, kp.at[past].set(jnp.nan), vp.at[past].set(jnp.nan),
        jnp.asarray(tables), positions, hd)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


@pytest.mark.parametrize("n_layers,layer", [(3, 0), (3, 1), (4, 3)])
def test_the_kernel_reads_its_layer_and_no_other(n_layers, layer):
    """The whole pool ``[L, n_blocks, ..]`` and a layer index: every OTHER
    layer holds NaN, so one byte fetched from a neighbour shows; the output
    is the one-layer pool's, bit for bit (the same bytes in the same
    order), under jit with the index traced."""
    rng = np.random.default_rng(90 + layer)
    B, T, n_heads, n_kv, hd, M = 3, 1, 8, 2, 16, _M
    nb = B * M + 1
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, _BS, M, nb)
    tables = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
    tables[1] = 0                                   # a dead row rides along
    tables = jnp.asarray(tables)
    positions = jnp.asarray([[40], [9], [150]], jnp.int32)
    alone = _assert_close(q, kp, vp, tables, positions, hd)

    def whole(pool):
        return jnp.full((n_layers,) + pool.shape, jnp.nan,
                        pool.dtype).at[layer].set(pool)

    got = jax.jit(lambda l: paged_ragged_attention(
        q, whole(kp), whole(vp), l, tables, positions, hd,
        interpret=True))(jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), alone)


def test_verify_width_with_write_lens_through_the_seam(monkeypatch):
    """``paged_verify_step``'s shape through ``_attend_paged``: T = 16
    lanes a row, ``write_lens`` redirecting the lanes past a row's draft to
    the null block. The kernel's bound is ``pos0 + T`` whatever the draft's
    length, its mask per query row; the pools are written before either
    path attends, so they stay bit-equal. The pool is whole (three layers,
    the middle one written and read): the others come back untouched."""
    from dllama_tpu.models.llama import _attend_paged

    cfg = _tiny_cfg()
    rng = np.random.default_rng(41)
    B, T, M, nb = 3, 16, 6, 19
    n_kv, hd = cfg.n_kv_heads, cfg.head_dim
    q = jnp.asarray(rng.standard_normal((B, T, cfg.n_heads, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, n_kv, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((3, nb, n_kv, 16, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((3, nb, n_kv, 16, hd)), jnp.float32)
    tables = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
    pos0 = np.asarray([5, 30, 70], np.int32)
    write_lens = jnp.asarray([15, 0, 6], jnp.int32)
    for b in range(B):   # blocks only as far as the row's real lanes reach
        tables[b, (pos0[b] + int(write_lens[b])) // 16 + 1:] = 0
    positions = jnp.asarray(pos0[:, None] + np.arange(T)[None, :], jnp.int32)

    def run():
        return jax.jit(lambda *a: _attend_paged(cfg, *a))(
            q, k, v, kp, vp, jnp.int32(1), positions, jnp.asarray(tables),
            write_lens)

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    ax, kx, vx = run()
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    ap, kpp, vpp = run()
    np.testing.assert_allclose(np.asarray(ap), np.asarray(ax),
                               rtol=TOL, atol=TOL)
    np.testing.assert_array_equal(np.asarray(kpp), np.asarray(kx))
    np.testing.assert_array_equal(np.asarray(vpp), np.asarray(vx))
    for got, was in ((kpp, kp), (vpp, vp)):
        np.testing.assert_array_equal(np.asarray(got)[[0, 2]],
                                      np.asarray(was)[[0, 2]])
        assert not np.array_equal(np.asarray(got)[1], np.asarray(was)[1])


def test_supports_predicate():
    assert supports((2, 1, 8, 128), 2, 8, 16)
    assert supports((2, 16, 8, 40), 2, 8, 16)
    assert not supports((2, 1, 8, 129), 2, 8, 16)   # head dim not 8-aligned
    assert not supports((2, 1, 8, 128), 2, 8, 4)    # block_size below a tile
    assert not supports((2, 1, 8, 128), 3, 8, 16)   # irregular GQA split
    # the old epilogue staged the whole logical context in VMEM and
    # refused a 1M-row one; the walk's resident set is a fetch group's,
    # whatever the table's length
    assert supports((1, 1, 8, 128), 1, 8192, 128)
    # VMEM bound: what one K/V head keeps beside MAX_TQ folded query rows
    # of a very wide head does not fit, at any head grouping
    assert not supports((1, 512, 1, 4096), 1, 8, 16)
    assert not supports((1, 513, 1, 128), 1, 8, 16)  # over MAX_TQ
    # compiled, a manual DMA cannot slice an HBM ref whose minor dim is
    # not lane-aligned: such heads keep the gather + oracle on the chip
    assert supports((2, 1, 8, 128), 2, 8, 16, compiled=True)
    assert not supports((2, 1, 8, 64), 2, 8, 16, compiled=True)
    assert supports((2, 1, 8, 64), 2, 8, 16)


def test_kernel_choice_routes_through_the_one_gate(monkeypatch):
    """Mode selection is quant_matmul.pallas_mode_gate — xla kills the
    kernel, pallas forces it (interpret off-TPU), and an active mesh plan
    falls back (the auto-sharder can't partition a pallas_call)."""
    from dllama_tpu.parallel.api import make_tp_mesh, use_plan

    shape = ((2, 1, 8, 16), 2, 4, 16)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    assert kernel_choice(*shape) is None
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    kw = kernel_choice(*shape)
    assert kw is not None and kw["interpret"] is True  # off-TPU test path
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "fused")
    assert kernel_choice(*shape) is not None
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    with use_plan(make_tp_mesh(2)):
        assert kernel_choice(*shape) is None


# ---------------------------------------------------------------------------
# program-level: the paged forward family through the seam
# ---------------------------------------------------------------------------


def _tiny_cfg():
    from dllama_tpu.formats import mfile
    from dllama_tpu.models import ModelConfig

    return ModelConfig(
        arch=mfile.ArchType.LLAMA, dim=64, hidden_dim=96, n_layers=2,
        n_heads=8, n_kv_heads=2, head_dim=8, vocab_size=128, seq_len=64,
        norm_epsilon=1e-5, rope_theta=10000.0, rope_type=mfile.RopeType.LLAMA)


def test_paged_forward_bitwise_through_scrambled_tables(monkeypatch):
    """The full paged decode program between the gather+oracle trace and
    the kernel trace, through a scrambled block table — the acceptance bar
    for the seam swap: the written pools bit-identical (the writes do not
    pass through the kernel; layer 1's rows come after layer 0's attention,
    so a few ulps of slack there), the logits to ``1e-5`` (two layers of
    reduction-order noise on logits of order 1)."""
    from dllama_tpu.models import init_random_params
    from dllama_tpu.models.llama import paged_forward
    from dllama_tpu.runtime.kvblocks import PagedKVCache

    cfg = _tiny_cfg()
    params = init_random_params(cfg, seed=7)
    pkv = PagedKVCache.create(cfg, n_blocks=14, block_size=16)
    rng = np.random.default_rng(3)
    B, M = 3, 4
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32))
    pos = jnp.asarray([5, 0, 33], jnp.int32)
    toks = jnp.asarray(rng.integers(1, 127, (B, 1)).astype(np.int32))

    # fresh lambdas per mode: jit wrappers around the SAME function object
    # share the pjit executable cache, which would reuse the oracle program
    # for the kernel run and make this test vacuous
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "xla")
    lx, px = jax.jit(lambda p, c, t, s, kv, tb: paged_forward(p, c, t, s, kv, tb),
                     static_argnums=1)(params, cfg, toks, pos, pkv, tables)
    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    lp, pp = jax.jit(lambda p, c, t, s, kv, tb: paged_forward(p, c, t, s, kv, tb),
                     static_argnums=1)(params, cfg, toks, pos, pkv, tables)

    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(px.k[0]), np.asarray(pp.k[0]))
    np.testing.assert_array_equal(np.asarray(px.v[0]), np.asarray(pp.v[0]))
    np.testing.assert_allclose(np.asarray(px.k), np.asarray(pp.k),
                               rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(px.v), np.asarray(pp.v),
                               rtol=TOL, atol=TOL)


def test_paged_kernel_steady_state_never_retraces(monkeypatch):
    """Zero post-steady compiles with the kernel enabled: table contents,
    positions, and tokens all vary dispatch to dispatch without a retrace
    (the continuous-batching requirement, ledger-asserted at the engine
    level by test_kvblocks — this is the kernel-path twin)."""
    from dllama_tpu.models import init_random_params
    from dllama_tpu.models.llama import paged_forward
    from dllama_tpu.runtime.kvblocks import PagedKVCache

    monkeypatch.setenv("DLLAMA_TPU_QUANT_KERNEL", "pallas")
    cfg = _tiny_cfg()
    params = init_random_params(cfg, seed=8)
    pkv = PagedKVCache.create(cfg, n_blocks=14, block_size=16)
    rng = np.random.default_rng(5)
    fwd = jax.jit(paged_forward, static_argnums=1)
    n_compiles = []
    for step in range(4):
        tables = jnp.asarray(rng.integers(0, 14, (3, 4)).astype(np.int32))
        pos = jnp.asarray(rng.integers(0, 40, 3).astype(np.int32))
        toks = jnp.asarray(rng.integers(1, 127, (3, 1)).astype(np.int32))
        logits, pkv = fwd(params, cfg, toks, pos, pkv, tables)
        jax.block_until_ready(logits)
        n_compiles.append(fwd._cache_size())
    assert n_compiles[0] == 1 and n_compiles[-1] == 1, n_compiles


# ---------------------------------------------------------------------------
# real-chip tier (the capability-probe skip idiom: compiled kernels only
# ever run under DLLAMA_TESTS_TPU=1 on a real backend — tier-1 stays
# deterministic off-TPU)
# ---------------------------------------------------------------------------


@pytest.mark.tpu
def test_paged_kernel_compiled_parity_on_hw():
    if jax.default_backend() != "tpu":  # the repo's one rule: parallel.api.on_tpu
        pytest.skip(f"no TPU backend (devices: {jax.devices()})")
    rng = np.random.default_rng(31)
    B, T, n_heads, n_kv, hd, bs, M, nb = 4, 1, 8, 2, 128, 16, 12, 49
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = rng.permutation(np.arange(1, nb)).reshape(B, M).astype(np.int32)
    tables[2] = 0                                   # a dead row, stale depth
    positions = jnp.asarray([[17], [3], [99], [M * bs - 1]], jnp.int32)
    # under the default precision the MXU rounds float32 operands to
    # bfloat16 on both sides and the two softmax orders round different
    # probabilities (1e-3 apart, each as far from the truth); ``highest``
    # is where Mosaic compiled vs XLA is accumulation-order noise only
    # (tools/paged_attn_sweep.py reads both, at the benchmark's geometries)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(_kernel_1(q, kp, vp, jnp.asarray(tables), positions,
                                   hd, interpret=False))
        want = np.asarray(_reference(q, kp, vp, jnp.asarray(tables),
                                     positions, hd))
    live = tables[:, 0] != 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert np.all(got[~live] == 0)


# -- a sliding-window layer: a lower bound on the walk, the window in the mask --


def _window_reference(q, k_pool, v_pool, tables, positions, head_dim, window):
    B, M = tables.shape
    n_kv, bs, hd = k_pool.shape[1], k_pool.shape[2], k_pool.shape[3]

    @jax.jit
    def ref(q, k_pool, v_pool, tables, positions):
        def view(pool):
            return jnp.moveaxis(pool[tables], 2, 1).reshape(B, n_kv, M * bs, hd)

        return attention(q, view(k_pool), view(v_pool), positions, head_dim,
                         window=window)

    return ref(q, k_pool, v_pool, tables, positions)


@pytest.mark.parametrize("n_heads,n_kv", [(6, 1), (9, 1), (8, 2)])
@pytest.mark.parametrize("window", [32, 48, 200])
def test_window_walk_starts_at_the_first_live_block(n_heads, n_kv, window):
    """A sliding-window layer's rows: the query sees its newest ``window``
    keys, the walk starts at the block that holds the oldest of them, and
    every table entry behind it is the NULL block (the allocator took those
    blocks back), so a kernel that read one would read garbage: the pool's
    block 0 is poisoned with NaN here. Query groups of 6 and 9 over ONE K/V
    head are what one chip's share of a layer holds; depths sit under the
    window, on its edge, and blocks past it; one row is dead."""
    rng = np.random.default_rng(window + n_heads)
    B, T, hd, bs, M, nb = 6, 1, 16, 16, 16, 60
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    depths = [5, window - 1, window, window + bs + 3, 5 * bs + 9, 77]
    tables = np.zeros((B, M), np.int32)
    ids = iter(rng.permutation(np.arange(1, nb)))
    for b, pos in enumerate(depths[:-1]):
        first = max(0, pos - window + 1) // bs
        for idx in range(first, pos // bs + 1):
            tables[b, idx] = next(ids)          # entries behind `first` stay null
    positions = jnp.asarray([[p] for p in depths], jnp.int32)
    got = np.asarray(_kernel_1(q, kp, vp, jnp.asarray(tables), positions, hd,
                               window=window))
    # the oracle gathers the null block too: give it a finite one to mask
    want = np.asarray(_window_reference(q, kp.at[0].set(0.0), vp.at[0].set(0.0),
                                        jnp.asarray(tables), positions, hd, window))
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=TOL, atol=TOL)
    assert np.all(got[-1] == 0)                 # the dead row: zeros, no NaN
    # and the window really cuts: a full-attention read of the same row differs
    full = np.asarray(_window_reference(q, kp.at[0].set(0.0), vp.at[0].set(0.0),
                                        jnp.asarray(tables), positions, hd, 0))
    assert np.abs(full[3] - want[3]).max() > 1e-2      # row 3 is a block and more past its window


def test_window_walk_takes_one_token_a_row():
    rng = np.random.default_rng(3)
    q, kp, vp = _mk(rng, 1, 2, 4, 2, 16, 16, 4, 6)
    with pytest.raises(ValueError, match="one token a row"):
        _kernel_1(q, kp, vp, jnp.ones((1, 4), jnp.int32),
                  jnp.asarray([[3, 4]], jnp.int32), 16, window=8)
