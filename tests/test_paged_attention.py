"""Ragged paged attention kernel vs the gather+oracle reference —
BITWISE, adversarially (the parity methodology of nn-vulkan-test.cpp,
escalated: the paged kernel replaces the PR6 ``pool[tables]`` gather
bit-for-bit, so every table shape continuous batching can produce must
reproduce the dense path's exact float pattern).

The reference side is the JITTED gather+oracle composition — the program
the seam in models/llama.py actually swaps out (eager op-by-op execution
rounds differently than a fused jaxpr; the claim is program-vs-program)."""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.ops.attention import attention
from dllama_tpu.ops.paged_attention import (
    kernel_choice,
    paged_ragged_attention,
    supports,
)


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


def _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb, dtype=jnp.float32):
    k_pool = jnp.asarray(rng.standard_normal((nb, n_kv, bs, hd)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((nb, n_kv, bs, hd)), dtype)
    q = jnp.asarray(rng.standard_normal((B, T, n_heads, hd)), jnp.float32)
    return q, k_pool, v_pool


def _assert_bitwise(q, k_pool, v_pool, tables, positions, hd):
    """Kernel vs reference, to the last few bits. Written as bitwise
    equality, which held on the jaxlib this was written against. Under
    jaxlib 0.9 the CPU backend blocks the reference's batched einsum and the
    interpret-mode kernel's per-(b, h) dots differently, and the two differ
    in the last bit (max abs 2.4e-7 on outputs of order 1, PR 22). Every
    structural fault these cases hunt (a wrong block, a leaked null block, a
    mask off by one row) is wrong by order 1, so a few ulps of slack loses
    nothing; whether the COMPILED kernel is bit-identical is the chip's to
    say (test_paged_kernel_compiled_parity_on_hw)."""
    got = paged_ragged_attention(q, k_pool, v_pool, tables, positions, hd,
                                 interpret=True)
    want = _reference(q, k_pool, v_pool, tables, positions, hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_scrambled_block_table_bitwise():
    """Arbitrary physical placement: every row's blocks land at scrambled
    pool ids (the steady-state of a churning allocator)."""
    rng = np.random.default_rng(0)
    B, T, n_heads, n_kv, hd, bs, M, nb = 3, 1, 8, 2, 16, 16, 4, 14
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M).astype(np.int32))
    positions = jnp.asarray([[37], [5], [63]], jnp.int32)
    _assert_bitwise(q, kp, vp, tables, positions, hd)


def test_partial_tail_block_and_ragged_rows():
    """Each row mid-block at its own depth: the newest block is partially
    valid and masked per position, never per block."""
    rng = np.random.default_rng(1)
    B, T, n_heads, n_kv, hd, bs, M, nb = 4, 1, 8, 4, 32, 16, 8, 40
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    # depths chosen to hit block offsets 0, 1, bs-1 and a mid-block point
    positions = jnp.asarray([[0], [bs - 1], [bs], [3 * bs + 7]], jnp.int32)
    _assert_bitwise(q, kp, vp, tables, positions, hd)


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
    _assert_bitwise(q, kp, vp, tables, positions, hd)


@pytest.mark.parametrize("t", [1, 16])
def test_query_width_edges(t):
    """T=1 (decode) and T=16 (chunked-prefill tail / verify width)."""
    rng = np.random.default_rng(3 + t)
    B, n_heads, n_kv, hd, bs, M, nb = 2, 8, 2, 16, 16, 6, 20
    q, kp, vp = _mk(rng, B, t, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    positions = (jnp.asarray([3, 2 * bs + 1], jnp.int32)[:, None]
                 + jnp.arange(t)[None, :])
    _assert_bitwise(q, kp, vp, tables, positions, hd)


@pytest.mark.parametrize("hd", [40, 72])
def test_non_128_aligned_head_dims(hd):
    rng = np.random.default_rng(11)
    B, T, n_heads, n_kv, bs, M, nb = 2, 2, 4, 4, 8, 4, 9
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(0, nb, (B, M)).astype(np.int32))
    positions = (jnp.asarray([7, 19], jnp.int32)[:, None]
                 + jnp.arange(T)[None, :])
    _assert_bitwise(q, kp, vp, tables, positions, hd)


def test_bf16_pool_bitwise():
    """The serving pool dtype: both paths cast pool rows to f32 the same
    way, so bf16 storage stays bit-identical too."""
    rng = np.random.default_rng(21)
    B, T, n_heads, n_kv, hd, bs, M, nb = 2, 1, 4, 2, 16, 16, 4, 8
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb,
                    dtype=jnp.bfloat16)
    tables = jnp.asarray(rng.integers(1, nb, (B, M)).astype(np.int32))
    positions = jnp.asarray([[9], [3 * bs - 1]], jnp.int32)
    _assert_bitwise(q, kp, vp, tables, positions, hd)


def test_supports_predicate():
    assert supports((2, 1, 8, 128), 2, 8, 16)
    assert supports((2, 16, 8, 40), 2, 8, 16)
    assert not supports((2, 1, 8, 129), 2, 8, 16)   # head dim not 8-aligned
    assert not supports((2, 1, 8, 128), 2, 8, 4)    # block_size below a tile
    assert not supports((2, 1, 8, 128), 3, 8, 16)   # irregular GQA split
    # VMEM bound: a 1M-row logical context can't stage
    assert not supports((1, 1, 8, 128), 1, 8192, 128)


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
    """The full paged decode program (logits AND written pool) is
    bit-identical between the gather+oracle trace and the kernel trace,
    through a scrambled block table — the acceptance bar for the seam
    swap."""
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

    np.testing.assert_array_equal(np.asarray(lx), np.asarray(lp))
    np.testing.assert_array_equal(np.asarray(px.k), np.asarray(pp.k))
    np.testing.assert_array_equal(np.asarray(px.v), np.asarray(pp.v))


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
    B, T, n_heads, n_kv, hd, bs, M, nb = 2, 1, 8, 2, 128, 16, 4, 10
    q, kp, vp = _mk(rng, B, T, n_heads, n_kv, hd, bs, M, nb)
    tables = jnp.asarray(rng.integers(0, nb, (B, M)).astype(np.int32))
    positions = jnp.asarray([[17], [3]], jnp.int32)
    got = paged_ragged_attention(q, kp, vp, tables, positions, hd)
    want = _reference(q, kp, vp, tables, positions, hd)
    # Mosaic compiled vs XLA: accumulation-order noise at f32 scale only
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
