"""Pallas TPU kernel: blockwise online-softmax attention over the KV cache.

The TPU replacement for the reference's serial per-head attention loop
(reference: multiheadAtt_F32, src/nn/nn-cpu-ops.cpp:751-786): instead of
walking positions ``0..pos`` one dot product at a time, KV blocks stream from
HBM through VMEM and the softmax is computed online (running max / running
sum), so the full ``[T, S]`` score matrix never materializes and both dots
land on the MXU.

Layouts (chosen together with :mod:`dllama_tpu.runtime.kvcache`):

* cache is head-major ``[B, n_kv_heads, S, head_dim]`` — KV blocks are
  directly tileable ``(S, head_dim)`` slabs, no transpose on the hot path;
* queries fold the GQA group into rows: ``[B, n_kv_heads, T*kv_mul, D]`` —
  one kernel instance per (batch, kv-head) attends the whole query group, so
  GQA widens the MXU tile instead of shrinking it.

Causality follows the reference's affine position rule: query row ``r``
(source position ``start_pos + r // kv_mul``) sees cache slots
``s <= start_pos + r // kv_mul``; positions are derived in-kernel from a
per-batch-row ``(q_pos0, kv_pos0)`` table in SMEM — a scalar ``start_pos``
broadcasts, a ``[B]`` vector gives every sequence its own depth (ragged
batched serving) — so no mask tensor is built.

The XLA oracle in :mod:`dllama_tpu.ops.attention` is the semantics reference;
parity is tested in tests/test_flash_attention.py (the way
nn-vulkan-test.cpp checks GPU ops against CPU expectations).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..parallel.api import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # VPU lane width; scratch vectors are stored lane-broadcast


def _kernel(pos_ref, q_ref, k_ref, v_ref, out_ref, *rest,
            bs: int, kv_mul: int, t: int, scale: float, stats: bool):
    if stats:
        m_out_ref, l_out_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    s_idx = pl.program_id(2)
    ns = pl.num_programs(2)
    # query row r sits at absolute position q_pos0 + r // kv_mul; cache slot c
    # of this call covers absolute position kv_pos0 + c (kv_pos0 != 0 when the
    # caller holds a mid-sequence block, e.g. a ring-attention KV shard).
    # The whole [B, 2] table rides in SMEM (Mosaic rejects a (1, 2) block of a
    # (B, 2) array for B not in {1, 8k}); each instance reads its batch row by
    # program id, so ragged batches (each sequence at its own depth — batched
    # serving) still get their own q_pos0.
    b_idx = pl.program_id(0)
    q_pos0 = pos_ref[b_idx, 0]
    kv_pos0 = pos_ref[b_idx, 1]

    @pl.when(s_idx == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks past the newest position are entirely masked: skip their DMA'd
    # compute (their loads still stream, matching the oracle's byte traffic).
    @pl.when(kv_pos0 + s_idx * bs <= q_pos0 + (t - 1))
    def _():
        q = q_ref[0, 0].astype(jnp.float32)  # (TQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)

        scores = jax.lax.dot_general(  # (TQ, BS) = q @ k.T
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        tq = scores.shape[0]
        row_t = jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 0) // kv_mul
        col = kv_pos0 + s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (tq, bs), 1)
        scores = jnp.where(col <= q_pos0 + row_t, scores, -jnp.inf)

        # online softmax update; m/l live lane-broadcast in (TQ, 128) scratch.
        # A row can be fully masked so far when kv_pos0 > 0 (mid-sequence
        # block): clamp m to keep exp() NaN-free (-inf rows stay acc=0, l=0).
        m_prev = jnp.max(m_ref[:], axis=-1, keepdims=True)  # (TQ, 1)
        l_prev = jnp.max(l_ref[:], axis=-1, keepdims=True)
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe)  # scores=-inf → 0, never NaN
        corr = jnp.exp(m_prev - m_safe)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

        pv = jax.lax.dot_general(  # (TQ, D)
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s_idx == ns - 1)
    def _():
        if stats:
            # unnormalized block results for cross-block online-softmax
            # combining (ring attention / flash-decoding LSE merge)
            out_ref[0, 0] = acc_ref[:]
            m_out_ref[0, 0] = m_ref[:]
            l_out_ref[0, 0] = l_ref[:]
        else:
            l = jnp.max(l_ref[:], axis=-1, keepdims=True)
            l = jnp.where(l == 0.0, 1.0, l)  # kv_pos0=0 ⇒ l>=1; belt anyway
            out_ref[0, 0] = acc_ref[:] / l


def _pick_bs(s: int) -> int | None:
    for c in (512, 256, 128):
        if s % c == 0:
            return c
    return None


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "t", "interpret", "stats"))
def _call(q_g: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
          start_pos: jax.Array, head_dim: int, t: int, interpret: bool,
          kv_pos0: jax.Array | int = 0, stats: bool = False):
    B, n_kv, TQ, D = q_g.shape
    S = k_cache.shape[2]
    bs = _pick_bs(S)
    kv_mul = TQ // t
    # per-batch-row position table [B, 2]: scalar start_pos broadcasts, a
    # [B] vector (ragged batched serving) lands one row per sequence
    q_pos = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(start_pos, jnp.int32)), (B,))
    kv_pos = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(kv_pos0, jnp.int32)), (B,))
    pos = jnp.stack([q_pos, kv_pos], axis=1)

    kernel = functools.partial(_kernel, bs=bs, kv_mul=kv_mul, t=t,
                               scale=1.0 / (head_dim ** 0.5), stats=stats)
    out_shape = [jax.ShapeDtypeStruct((B, n_kv, TQ, D), jnp.float32)]
    out_specs = [pl.BlockSpec((1, 1, TQ, D), lambda b, h, s: (b, h, 0, 0),
                              memory_space=pltpu.VMEM)]
    if stats:
        # lane-broadcast running max / sum, one (TQ, 128) slab per (b, h)
        stat_spec = pl.BlockSpec((1, 1, TQ, _LANES), lambda b, h, s: (b, h, 0, 0),
                                 memory_space=pltpu.VMEM)
        out_shape += [jax.ShapeDtypeStruct((B, n_kv, TQ, _LANES), jnp.float32)] * 2
        out_specs += [stat_spec, stat_spec]
    res = pl.pallas_call(
        kernel,
        grid=(B, n_kv, S // bs),
        in_specs=[
            pl.BlockSpec((B, 2), lambda b, h, s: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, TQ, D), lambda b, h, s: (b, h, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bs, D), lambda b, h, s: (b, h, s, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs if stats else out_specs[0],
        out_shape=out_shape if stats else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((TQ, _LANES), jnp.float32),  # running max
            pltpu.VMEM((TQ, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((TQ, D), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(pos, q_g, k_cache, v_cache)
    if stats:
        acc, m, l = res
        return acc, m[..., 0], l[..., 0]  # de-broadcast the lane dim
    return res


def flash_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    start_pos: jax.Array, head_dim: int, *,
                    interpret: bool = False) -> jax.Array:
    """Causal GQA attention: ``q [B, T, n_heads, D]`` over head-major caches
    ``k/v [B, n_kv, S, D]``; query row positions are ``start_pos + t``.

    Drop-in for :func:`dllama_tpu.ops.attention.attention` whenever positions
    are the affine ``start_pos + arange(T)`` the model always uses.
    """
    B, T, n_heads, D = q.shape
    n_kv = k_cache.shape[1]
    kv_mul = n_heads // n_kv

    # fold GQA groups into query rows: [B, n_kv, T*kv_mul, D], row r=(t, m)
    q_g = (q.reshape(B, T, n_kv, kv_mul, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, n_kv, T * kv_mul, D)
            .astype(jnp.float32))
    out = _call(q_g, k_cache, v_cache, start_pos, head_dim, T, interpret)
    return (out.reshape(B, n_kv, T, kv_mul, D)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, n_heads, D)
               .astype(q.dtype))


def flash_block_stats(q_g: jax.Array, k_block: jax.Array, v_block: jax.Array,
                      q_pos0: jax.Array, kv_pos0: jax.Array, head_dim: int,
                      t: int, *, interpret: bool = False):
    """Unnormalized blockwise attention over a mid-sequence KV block — the
    Pallas building block for ring attention / flash-decoding merges
    (parallel/ring.py).

    ``q_g: [B, n_kv, T*kv_mul, D]`` GQA-folded queries whose row ``r`` sits at
    absolute position ``q_pos0 + r // kv_mul``; ``k/v_block: [B, n_kv, Sb, D]``
    covering absolute positions ``[kv_pos0, kv_pos0 + Sb)``. Returns
    ``(acc [B,n_kv,TQ,D], m [B,n_kv,TQ], l [B,n_kv,TQ])`` in the usual
    online-softmax algebra (fully-masked rows: acc=0, l=0, m=-inf), ready for
    cross-block combining.
    """
    return _call(q_g.astype(jnp.float32), k_block, v_block, q_pos0, head_dim,
                 t, interpret, kv_pos0=kv_pos0, stats=True)


MAX_TQ = 2048  # scores tile (TQ, bs) + acc must fit VMEM comfortably


def supports(q_shape: tuple[int, ...], n_kv: int, s: int) -> bool:
    """Whether the kernel's tile grid covers these shapes."""
    B, T, n_heads, D = q_shape
    kv_mul = n_heads // n_kv
    return (_pick_bs(s) is not None
            and D % 8 == 0
            and T * kv_mul <= MAX_TQ)


def flash_attention_sharded(plan, q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, start_pos: jax.Array,
                            head_dim: int, *, interpret: bool = False):
    """Tensor-parallel flash attention: the Pallas kernel inside a shard_map.

    The auto-sharder cannot partition a ``pallas_call``, so under a mesh plan
    the kernel runs manual-SPMD: q sharded on heads, head-major caches sharded
    on kv-heads — the reference's per-node head shards (sliceMultiHeadAtt,
    nn-core.cpp:265-272) — with zero collectives inside (attention is
    embarrassingly parallel across heads). Composes with ``dp`` on the batch
    dim. Returns ``None`` when the layout doesn't apply (caller falls back to
    the XLA oracle); the ``sp`` path has its own kernels (parallel/ring.py).
    """
    from jax.sharding import PartitionSpec as P

    B, T, H, D = q.shape
    n_kv, S = k_cache.shape[1], k_cache.shape[2]
    tp = plan.axis_size("tp")
    if plan.axis_size("sp") > 1 or tp <= 1:
        return None
    if H % tp != 0:
        return None
    # kv replication groups (tp > n_kv_heads — the v5e-16 70B shape): the
    # cache stays replicated across tp (kv_cache_sharding's divisibility
    # fallback) and each device slices out the ONE kv head its q-head shard
    # maps to. Requires tp % n_kv == 0 so every device's q heads land in a
    # single group; an irregular split keeps the oracle.
    repl = n_kv % tp != 0
    if repl and tp % n_kv != 0:
        return None
    n_kv_l = 1 if repl else n_kv // tp
    if not supports((B, T, H // tp, D), n_kv_l, S):
        return None
    dp_ax = plan.resolve("batch") if B % plan.axis_size("dp") == 0 else None

    if repl:
        grp = H // n_kv   # q heads per kv head
        h_loc = H // tp

        def local(q_l, k_l, v_l, sp0):
            g = (jax.lax.axis_index("tp") * h_loc) // grp
            k_s = jax.lax.dynamic_slice_in_dim(k_l, g, 1, axis=1)
            v_s = jax.lax.dynamic_slice_in_dim(v_l, g, 1, axis=1)
            return flash_attention(q_l, k_s, v_s, sp0, head_dim,
                                   interpret=interpret)

        kv_spec = P(dp_ax, None, None, None)
    else:
        def local(q_l, k_l, v_l, sp0):
            return flash_attention(q_l, k_l, v_l, sp0, head_dim,
                                   interpret=interpret)

        kv_spec = P(dp_ax, "tp", None, None)

    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    # scalar start_pos replicates; a [B] vector (ragged batched serving)
    # shards with the batch rows
    pos_spec = P(dp_ax) if start_pos.ndim else P()
    fn = shard_map(
        local, mesh=plan.mesh,
        in_specs=(P(dp_ax, None, "tp", None), kv_spec, kv_spec, pos_spec),
        out_specs=P(dp_ax, None, "tp", None),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, start_pos)
