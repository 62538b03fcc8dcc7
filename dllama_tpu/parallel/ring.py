"""Ring attention / sequence-parallel attention over an ``sp`` mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5
"Long-context / sequence parallelism: Absent" — its KV cache is a dense
``seq_len × kv_dim0`` buffer per node and attention is a serial loop,
src/nn/nn-cpu-ops.cpp:751-786). Here the KV cache's *sequence* dim is sharded
across the ``sp`` mesh axis so context length scales with the number of
chips, and attention runs as manual-SPMD (``shard_map``) with XLA collectives
riding ICI:

* **Prefill (queries seq-sharded):** classic ring attention — each device
  computes block attention against its local KV shard while rotating the
  K/V blocks around the ring with ``lax.ppermute``, folding each block into
  an online-softmax accumulator ``(m, l, acc)``. ``n_sp`` steps; compute and
  the permute of the next block overlap inside XLA's async collectives.
* **Decode (queries replicated, T not divisible by sp):** flash-decoding
  style — one block pass over the local KV shard, then a log-sum-exp merge
  across the ring (``pmax`` of maxima, ``psum`` of rescaled ``l``/``acc``).

Both paths share the same block/combine math, are causal via *global*
position ids (each shard knows which absolute positions it holds), support
GQA, and compose with ``tp`` (kv-heads sharded) and ``dp`` (batch sharded)
inside the same shard_map.

The KV-cache append (reference OP_SHIFT) happens inside the same shard_map:
new K/V rows are all-gathered over ``sp`` (tiny: T rows vs S cache) and each
device scatters the rows whose absolute position falls inside its shard.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .api import on_tpu, shard_map

if TYPE_CHECKING:
    from .api import MeshPlan

AXIS = "sp"
NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# Block math (shared by ring and merge paths). All in float32.
# ---------------------------------------------------------------------------


def _block_attn(qg: jax.Array, k: jax.Array, v: jax.Array,
                mask: jax.Array, head_dim: int):
    """Unnormalized block attention.

    ``qg: [B, T, n_kv, kv_mul, hd]`` grouped queries, ``k/v: [B, n_kv, S, hd]``
    (head-major cache block), ``mask: [B, T, S]`` True where visible.
    Returns ``(acc [B,T,n_kv,kv_mul,hd], m [B,T,n_kv,kv_mul], l [same])`` such
    that the true softmax-attention over this block is ``acc * exp(m') / l'``
    terms under the usual online-softmax algebra.
    """
    scores = jnp.einsum("btkmh,bksh->btkms", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(head_dim))
    mask_b = mask[:, :, None, None, :]
    scores = jnp.where(mask_b, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                      # [B,T,k,mul]; may be -inf
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(mask_b, jnp.exp(scores - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("btkms,bksh->btkmh", p, v.astype(jnp.float32))
    return acc, m, l


def _combine(m, l, acc, bm, bl, bacc):
    """Fold block stats ``(bm, bl, bacc)`` into the running ``(m, l, acc)``.

    Safe for fully-masked blocks (all stats stay 0 / -inf, no NaNs)."""
    m_new = jnp.maximum(m, bm)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(m - m_safe)       # -inf - 0 → 0, never NaN
    beta = jnp.exp(bm - m_safe)
    l_new = l * alpha + bl * beta
    acc_new = acc * alpha[..., None] + bacc * beta[..., None]
    return m_new, l_new, acc_new


def _finish(acc, l, dtype):
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(dtype)


# ---------------------------------------------------------------------------
# In-shard KV cache append (reference OP_SHIFT, sequence-sharded)
# ---------------------------------------------------------------------------


def _scatter_rows(cache: jax.Array, rows: jax.Array, local_idx: jax.Array) -> jax.Array:
    """Write ``rows: [..., n_kv, T, hd]`` into ``cache: [..., n_kv, Sl, hd]``
    at per-row indices ``local_idx: [T]``; out-of-range rows are dropped
    (they belong to another shard). Rank-agnostic on the leading axes so the
    ragged path can vmap it over the batch."""
    s_local = cache.shape[-2]
    in_range = (local_idx >= 0) & (local_idx < s_local)
    # map out-of-range to an OOB index so mode="drop" discards them
    safe_idx = jnp.where(in_range, local_idx, s_local)
    return cache.at[..., safe_idx, :].set(rows.astype(cache.dtype), mode="drop")


def _append_kv(k_shard, v_shard, new_k, new_v, start_pos, t_global,
               q_sharded: bool, n_sp: int):
    """Inside shard_map: append the step's K/V rows into the seq-sharded cache.

    ``new_k/new_v: [B, T_local, n_kv_local, hd]`` time-major (T_local =
    T_global/n_sp when queries are sharded, else T_global replicated).
    ``start_pos`` is a scalar, or a ``[B]`` vector for ragged batched
    serving (each slot appends at its own depth)."""
    idx = lax.axis_index(AXIS)
    s_local = k_shard.shape[2]
    if q_sharded and n_sp > 1:
        new_k = lax.all_gather(new_k, AXIS, axis=1, tiled=True)
        new_v = lax.all_gather(new_v, AXIS, axis=1, tiled=True)
    k_rows = jnp.swapaxes(new_k, 1, 2)   # [B, n_kv, T, hd]
    v_rows = jnp.swapaxes(new_v, 1, 2)
    steps = jnp.arange(t_global, dtype=jnp.int32)
    if jnp.asarray(start_pos).ndim:      # ragged: per-batch-row depths
        local_idx = (start_pos[:, None] + steps[None, :]) - idx * s_local
        scat = jax.vmap(_scatter_rows, in_axes=(0, 0, 0))
        return scat(k_shard, k_rows, local_idx), scat(v_shard, v_rows, local_idx)
    local_idx = (start_pos + steps) - idx * s_local   # [T_global]
    return (_scatter_rows(k_shard, k_rows, local_idx),
            _scatter_rows(v_shard, v_rows, local_idx))


# ---------------------------------------------------------------------------
# The two attention paths (run inside shard_map)
# ---------------------------------------------------------------------------


def _kernel_block_stats(qg, k, v, q_pos0, kv_pos0, head_dim: int,
                        interpret: bool):
    """One KV block through the Pallas flash kernel, results in ring layout.

    ``qg: [B, Tl, n_kv, kv_mul, hd]`` → fold GQA into kernel query rows
    (``[B, n_kv, Tl*kv_mul, hd]``, row = t*kv_mul + m — the same layout
    ops.flash_attention uses), call the stats-mode kernel, unfold."""
    from ..ops.flash_attention import flash_block_stats

    B, Tl, n_kv, kv_mul, hd = qg.shape
    q_hm = qg.transpose(0, 2, 1, 3, 4).reshape(B, n_kv, Tl * kv_mul, hd)
    acc, m, l = flash_block_stats(q_hm, k, v, q_pos0, kv_pos0, head_dim, Tl,
                                  interpret=interpret)
    acc = acc.reshape(B, n_kv, Tl, kv_mul, hd).transpose(0, 2, 1, 3, 4)
    m = m.reshape(B, n_kv, Tl, kv_mul).transpose(0, 2, 1, 3)
    l = l.reshape(B, n_kv, Tl, kv_mul).transpose(0, 2, 1, 3)
    return acc, m, l


def _ring_attention_local(qg, k_shard, v_shard, q_positions, head_dim: int,
                          n_sp: int, use_kernel: bool = False,
                          interpret: bool = False):
    """Ring pass: rotate KV blocks, accumulate online softmax.

    ``qg: [B, Tl, n_kv, kv_mul, hd]`` local queries, ``q_positions: [B, Tl]``
    absolute positions, ``k/v_shard: [B, n_kv, Sl, hd]`` local cache block.
    With ``use_kernel`` each block runs the Pallas flash kernel (VMEM-blocked
    MXU attention) instead of the XLA einsum; the cross-block combine is
    identical.
    """
    B, Tl, n_kv, kv_mul, hd = qg.shape
    s_local = k_shard.shape[2]
    idx = lax.axis_index(AXIS)
    perm = [(j, (j + 1) % n_sp) for j in range(n_sp)]

    m0 = jnp.full((B, Tl, n_kv, kv_mul), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, Tl, n_kv, kv_mul), dtype=jnp.float32)
    acc0 = jnp.zeros((B, Tl, n_kv, kv_mul, hd), dtype=jnp.float32)

    def fold_block(r, m, l, acc, k, v):
        # after r forward rotations this block originated on rank (idx - r)
        src = jnp.mod(idx - r, n_sp)
        if use_kernel:
            # positions are affine WITHIN each batch row (start + t), so the
            # per-row first position fully determines the causal mask inside
            # the kernel (its pos table is per batch row — ragged serving's
            # per-slot depths ride the same table)
            bacc, bm, bl = _kernel_block_stats(
                qg, k, v, q_positions[:, 0], src * s_local, head_dim, interpret)
        else:
            kv_pos = src * s_local + jnp.arange(s_local, dtype=jnp.int32)
            mask = kv_pos[None, None, :] <= q_positions[:, :, None]
            bacc, bm, bl = _block_attn(qg, k, v, mask, head_dim)
        return _combine(m, l, acc, bm, bl, bacc)

    def step(r, carry):
        m, l, acc, k, v = carry
        m, l, acc = fold_block(r, m, l, acc, k, v)
        k = lax.ppermute(k, AXIS, perm)
        v = lax.ppermute(v, AXIS, perm)
        return m, l, acc, k, v

    # n_sp - 1 rotations; the last block is folded without the (wasted) final
    # permute — n_sp-1 ICI rotations total per layer
    m, l, acc, k, v = lax.fori_loop(
        0, n_sp - 1, step, (m0, l0, acc0, k_shard, v_shard))
    m, l, acc = fold_block(n_sp - 1, m, l, acc, k, v)
    return acc, l


def _merge_attention_local(qg, k_shard, v_shard, q_positions, head_dim: int,
                           use_kernel: bool = False, interpret: bool = False):
    """Flash-decoding pass: one local block + LSE merge over the ring.

    Queries (and their positions) are replicated across ``sp``."""
    s_local = k_shard.shape[2]
    idx = lax.axis_index(AXIS)
    if use_kernel:
        acc, m, l = _kernel_block_stats(qg, k_shard, v_shard,
                                        q_positions[:, 0], idx * s_local,
                                        head_dim, interpret)
    else:
        kv_pos = idx * s_local + jnp.arange(s_local, dtype=jnp.int32)
        mask = kv_pos[None, None, :] <= q_positions[:, :, None]
        acc, m, l = _block_attn(qg, k_shard, v_shard, mask, head_dim)

    gm = lax.pmax(m, AXIS)
    gm_safe = jnp.where(jnp.isfinite(gm), gm, 0.0)
    scale = jnp.exp(m - gm_safe)            # 0 for -inf locals, no NaN
    l = lax.psum(l * scale, AXIS)
    acc = lax.psum(acc * scale[..., None], AXIS)
    return acc, l


# ---------------------------------------------------------------------------
# Public wrapper
# ---------------------------------------------------------------------------


def sp_supported(plan: "MeshPlan", q_shape, kv_shape) -> bool:
    """Whether the fused sequence-parallel attention path applies."""
    sp = plan.axis_size("sp")
    if sp <= 1:
        return False
    B, T, H, hd = q_shape
    n_kv, S = kv_shape[1], kv_shape[2]
    if S % sp != 0:
        return False
    tp = plan.axis_size("tp")
    if tp > 1 and (H % tp != 0 or n_kv % tp != 0):
        return False  # kv replication groups don't compose with manual sp yet
    dp = plan.axis_size("dp")
    if B % dp != 0:
        return False
    return True


def _kernel_eligible(plan: "MeshPlan", q_shape, kv_shape,
                     attn_impl: str) -> tuple[bool, bool]:
    """Whether the per-block Pallas kernel applies inside the sp shard_map;
    returns (use_kernel, interpret). 'flash' forces it (interpret mode off
    TPU, the test path); 'auto' enables it on TPU backends."""
    from ..ops import flash_attention as _fa

    if attn_impl == "xla":
        return False, False
    n_sp = plan.axis_size("sp")
    tp = max(1, plan.axis_size("tp"))
    dp = max(1, plan.axis_size("dp"))
    B, T, H, hd = q_shape
    n_kv, S = kv_shape[1], kv_shape[2]
    q_sharded = T % n_sp == 0 and T > 1
    t_local = T // n_sp if q_sharded else T
    shapes_ok = _fa.supports((B // dp, t_local, H // tp, hd), n_kv // tp,
                             S // n_sp)
    if not shapes_ok:
        if attn_impl == "flash":
            raise ValueError(
                f"attn_impl='flash' with sp={n_sp}: kernel unsupported for "
                f"q={q_shape}, S_local={S // n_sp} (needs S/sp % 128 == 0)")
        return False, False
    if attn_impl == "flash":
        return True, not on_tpu()
    return on_tpu(), False


def sp_attention(plan: "MeshPlan", q: jax.Array, k_cache: jax.Array,
                 v_cache: jax.Array, new_k: jax.Array, new_v: jax.Array,
                 positions: jax.Array, start_pos: jax.Array, head_dim: int,
                 attn_impl: str = "auto"):
    """Fused sequence-parallel KV append + causal GQA attention.

    Args (global, auto-sharded views):
      q:        [B, T, n_heads, hd]   (post-rope)
      k_cache:  [B, n_kv, S, hd]      sequence-sharded over ``sp``
      new_k/v:  [B, T, n_kv, hd]      this step's rows (post-rope, time-major)
      positions:[B, T]                absolute position of each query row
      start_pos: scalar               absolute position of row 0
      attn_impl: per-block compute — 'auto' (Pallas flash kernel on TPU, XLA
                 einsum elsewhere), 'flash' (force kernel; interpret mode off
                 TPU), 'xla' (force einsum)

    Returns ``(att [B, T, n_heads, hd], k_cache, v_cache)`` or ``None`` when
    the path doesn't apply (caller falls back to the dense path).
    """
    if not sp_supported(plan, q.shape, k_cache.shape):
        return None

    mesh = plan.mesh
    n_sp = plan.axis_size("sp")
    B, T, H, hd = q.shape
    n_kv = k_cache.shape[1]
    q_sharded = T % n_sp == 0 and T > 1
    use_kernel, interpret = _kernel_eligible(plan, q.shape, k_cache.shape,
                                             attn_impl)

    dp_ax = plan.resolve("batch") if B % plan.axis_size("dp") == 0 else None
    tp_ax = plan.resolve("heads") if H % plan.axis_size("tp") == 0 else None
    seq_ax = AXIS if q_sharded else None

    q_spec = P(dp_ax, seq_ax, tp_ax, None)
    new_spec = P(dp_ax, seq_ax, tp_ax, None)
    cache_spec = P(dp_ax, tp_ax, AXIS, None)
    pos_spec = P(dp_ax, seq_ax)

    def local_fn(q_l, k_l, v_l, nk_l, nv_l, pos_l, sp0):
        k_l, v_l = _append_kv(k_l, v_l, nk_l, nv_l, sp0, T, q_sharded, n_sp)
        Bl, Tl, Hl, _ = q_l.shape
        n_kv_l = k_l.shape[1]
        kv_mul = Hl // n_kv_l
        qg = q_l.reshape(Bl, Tl, n_kv_l, kv_mul, hd).astype(jnp.float32)
        if q_sharded:
            acc, l = _ring_attention_local(qg, k_l, v_l, pos_l, head_dim, n_sp,
                                           use_kernel, interpret)
        else:
            acc, l = _merge_attention_local(qg, k_l, v_l, pos_l, head_dim,
                                            use_kernel, interpret)
        out = _finish(acc, l, q_l.dtype).reshape(Bl, Tl, Hl, hd)
        return out, k_l, v_l

    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    # scalar start_pos replicates; a [B] vector (ragged batched serving:
    # per-slot depths) shards with the batch rows
    sp0_spec = P(dp_ax) if start_pos.ndim else P()
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, new_spec, new_spec,
                  pos_spec, sp0_spec),
        out_specs=(q_spec, cache_spec, cache_spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, new_k, new_v, positions, start_pos)
