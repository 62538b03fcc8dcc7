"""Pallas TPU kernel: ragged paged attention over the block-table KV pool.

The TPU-native replacement for ``_attend_paged``'s gather+oracle pair
(models/llama.py): the XLA path materializes each row's full dense logical
cache per layer per step (``pool[l, tables]`` writes ``[B, M, n_kv, bs, hd]``
to HBM, then the oracle reads it straight back), so the paged program
family pays the KV bytes twice plus a scatter's worth of write bandwidth.
This kernel is the "Ragged Paged Attention" shape (PAPERS.md, arxiv
2604.15464): the pools stay in HBM, the block table and each row's bound
ride in as scalar-prefetch operands, and the kernel fetches the physical
blocks a row OWNS straight into VMEM, so the dense logical cache never
exists in HBM at all.

**It is handed the whole pool.** ``k_pool / v_pool`` are ``[L, n_blocks,
n_kv, bs, hd]``, every layer's blocks, and the layer is a traced scalar
that rides in as the fourth scalar-prefetch operand and picks each DMA's
source (``pool.at[layer, blk, heads]``). A caller that cut the layer's
slice out first would have XLA materialize that slice in front of the
custom call, a whole layer's pool copied a call; the decode step carries
the pool through its layer scan instead and writes it in place
(models/llama.paged_forward). One signature: a one-layer pool is ``L = 1``.

**It walks what is live.** The walk and the softmax are bounded per row
by what the inputs show:

* **the bound** — ``len[b] = pos0[b] + T`` where the row's first table
  entry is a real block, 0 where it is the null block (a retired slot
  keeps a stale ``pos`` and an all-null table, a slot under admission has
  a null table until commit: liveness is the table's to say). The walk
  covers ``ceil(len[b] / bs)`` table entries; both are traced values, so
  table contents and depths vary dispatch to dispatch without a retrace;
* **dead rows** — a row of length 0 fetches nothing and writes zeros
  (finite: its logits still pass ``_nonfinite_rows``);
* **whole blocks a fetch** — one ``make_async_copy`` moves a physical
  block for every K/V head of the grid step's head group (a block is
  ``[n_kv, bs, hd]``: contiguous over heads), ``G`` blocks a
  loop iteration, double-buffered, the next row's first group started
  under this row's last; the grid is rows x head groups and the loop's
  trip count is the row's. ``_plan`` picks the head group and ``G`` from
  the static shapes against ``_VMEM_BUDGET``: no knob, no model's name.

Semantics are the gather+oracle pair's on every live row:

* **ragged rows** — query row ``r`` (GQA-folded, source position
  ``pos0[b] + r // kv_mul``) sees cache columns ``s <= pos0[b] + r //
  kv_mul``, the oracle's position mask;
* **partial tail block** — the row's newest block is masked per position,
  not per block, so a mid-block write point behaves identically;
* **null block 0** — a null entry INSIDE the walk (a verify lane's
  padding) is fetched and position-masked like the oracle's; entries
  past the walk are never read. The last fetch group re-reads the row's
  own newest block in place of entries past its bound, so a row only
  ever sees bytes of blocks it owns (a neighbour's NaN cannot leak in
  through a masked column's ``0 * NaN``).

The arithmetic is the oracle's (float32 scores, statistics, accumulator
and probabilities; dots at the oracle's operand widths and the ambient
``precision``); what differs is the ORDER of the reductions over the
cache axis: a running maximum, running sum and accumulator over fetch
groups (online softmax) instead of one softmax over the whole table. So
parity is to a tolerance, not bitwise: ``2e-6`` in interpret mode
(tests/test_paged_attention.py: scrambled tables, CoW-redirects, dead
rows, ragged lengths around block and group edges, T=1/T=16, 30:30
heads, non-128-aligned head dims), ``2e-5`` compiled on the chip under
``highest`` (tools/paged_attn_sweep.py; PERF.md section 6, PR 31).

Mode selection routes through :func:`quant_matmul.pallas_mode_gate` — the
ONE kernel gate (dlint rule ``pallas-gate``): ``auto`` enables the kernel
on TPU backends, ``DLLAMA_TPU_QUANT_KERNEL=pallas``/``fused`` force it
(interpret mode off-TPU, the test path), ``xla`` is the kill switch back
to the gather+oracle path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tbl_ref, pos_ref, nblk_ref, lo_ref, layer_ref, q_ref, k_hbm,
            v_hbm, out_ref, kbuf, vbuf, sems, m_ref, l_ref, slot_ref, *,
            bs: int, kv_mul: int, hd: int, group: int, heads: int,
            n_entries: int, window: int):
    """One (row, head group) grid step: walk the row's table entries
    ``lo .. nblk - 1`` in fetch groups of ``group`` blocks (``lo`` is 0 but
    in a sliding-window layer, whose walk starts at the row's first live
    block: the entries before it were returned and read null).

    ``kbuf`` / ``vbuf [2, heads, group * bs, D]`` are the double-buffered
    landing zones (pool dtype, head-major so a head's keys are one 2-D
    slab); ``out_ref`` doubles as the float32 accumulator, ``m_ref`` /
    ``l_ref`` hold the running maximum and sum. ``slot_ref[0]`` is the
    buffer half this step's first group lands in: whoever ran before
    started that fetch (the grid is sequential), so a row's first DMA is
    hidden under its predecessor's last group."""
    b, g = pl.program_id(0), pl.program_id(1)
    n_rows, n_groups = pl.num_programs(0), pl.num_programs(1)
    layer = layer_ref[0]
    gt = group * bs
    tq = q_ref.shape[2]
    n = nblk_ref[b]
    lo = lo_ref[b]
    trips = pl.cdiv(n - lo, group)

    def fetch(row, hgrp, j, slot):
        """The 2 * group copies of fetch group ``j`` of (row, hgrp)."""
        last = nblk_ref[row] - 1
        first = lo_ref[row]
        copies = []
        for i in range(group):
            # entries past the row's bound re-read its own newest block
            blk = tbl_ref[row * n_entries
                          + jnp.minimum(first + j * group + i, last)]
            for w, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                copies.append(pltpu.make_async_copy(
                    pool.at[layer, blk, pl.ds(hgrp * heads, heads)],
                    buf.at[slot, :, pl.ds(i * bs, bs), :],
                    sems.at[w, slot]))
        return copies

    # the grid step after this one, and whether it will walk anything
    wraps = g + 1 == n_groups
    nxt_row = jnp.where(wraps, b + 1, b)
    nxt_grp = jnp.where(wraps, 0, g + 1)
    nxt_live = jnp.logical_and(
        nxt_row < n_rows, nblk_ref[jnp.minimum(nxt_row, n_rows - 1)] > 0)
    nxt_row = jnp.minimum(nxt_row, n_rows - 1)

    first = jnp.logical_and(b == 0, g == 0)

    @pl.when(first)
    def _():
        slot_ref[0] = 0

        @pl.when(n > 0)
        def _():
            for c in fetch(b, g, 0, 0):
                c.start()

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(nxt_live)
        def _():
            for c in fetch(nxt_row, nxt_grp, 0, slot_ref[0]):
                c.start()

    @pl.when(n > 0)
    def _():
        slot0 = slot_ref[0]
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        out_ref[...] = jnp.zeros_like(out_ref)
        pos0 = pos_ref[b]
        row_t = jax.lax.broadcasted_iota(jnp.int32, (tq, gt), 0) // kv_mul
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, gt), 1)
        # the oracle's position mask, less the group's first column:
        # column s visible to query row r iff s <= pos0 + r // kv_mul
        # (ragged depths, partial tail blocks, re-read blocks past the
        # bound and null-block garbage all handled by this one rule)
        reach = pos0 + row_t - col - lo * bs

        def body(j, _):
            slot = (slot0 + j) % 2
            more = j + 1 < trips

            @pl.when(jnp.logical_or(more, nxt_live))
            def _():
                for c in fetch(jnp.where(more, b, nxt_row),
                               jnp.where(more, g, nxt_grp),
                               jnp.where(more, j + 1, 0), 1 - slot):
                    c.start()

            for c in fetch(b, g, j, slot):
                c.wait()
            visible = reach >= j * gt
            if window:
                # a sliding layer: keys more than window - 1 behind the
                # query are out of sight (the walk's first block holds the
                # oldest visible key, so the running maximum stays finite)
                visible = jnp.logical_and(visible, reach - j * gt < window)
            for h in range(heads):
                scores = jax.lax.dot_general(
                    q_ref[0, h], kbuf[slot, h].astype(jnp.float32),
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)   # (TQ, gt)
                scores = scores / jnp.sqrt(jnp.float32(hd))
                scores = jnp.where(visible, scores, -jnp.inf)
                m_prev = m_ref[h]
                # column 0 is visible to every query row, so from the
                # first group on the running maximum is finite
                m_next = jnp.maximum(
                    m_prev, jnp.max(scores, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                probs = jnp.exp(scores - m_next)
                l_ref[h] = alpha * l_ref[h] + jnp.sum(probs, axis=-1,
                                                      keepdims=True)
                m_ref[h] = m_next
                out_ref[0, h] = alpha * out_ref[0, h] + jax.lax.dot_general(
                    probs, vbuf[slot, h].astype(jnp.float32),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # (TQ, D)

        jax.lax.fori_loop(0, trips, body, None)
        out_ref[0] = out_ref[0] / l_ref[...]
        slot_ref[0] = (slot0 + trips) % 2


# VMEM budget for one grid step's resident set (:func:`vmem_bytes`): the
# double-buffered landing zones, the pipelined q and out blocks, the
# softmax statistics and one head's float32 temporaries. Under Mosaic's
# 16 MiB default scoped limit, with room for what the compiler spills.
_VMEM_BUDGET = 12 * 1024 * 1024

MAX_TQ = 512  # folded query rows per (b, h) instance

# cache positions a fetch group covers: the running softmax's step. 128
# is one lane tile of scores for every query row.
_GROUP_TOKENS = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(heads: int, group: int, tq: int, D: int, block_size: int,
               itemsize: int) -> int:  # dlint: static-fn
    """What one grid step keeps in VMEM at ``heads`` K/V heads and
    ``group`` blocks a fetch, padded as Mosaic tiles it (8 sublanes x 128
    lanes of 32 bits)."""
    gt, d, rows = group * block_size, _round_up(D, 128), _round_up(tq, 8)
    landing = 2 * 2 * heads * gt * d * itemsize          # K and V, two halves
    q_out = 2 * 2 * heads * rows * d * 4                 # pipelined blocks
    stats = 2 * heads * rows * 128 * 4                   # m, l: one lane used
    temps = 2 * gt * d * 4 + 4 * rows * _round_up(gt, 128) * 4
    return landing + q_out + stats + temps


def _plan(n_kv: int, tq: int, D: int, n_blocks_seq: int, block_size: int,
          itemsize: int) -> tuple[int, int] | None:  # dlint: static-fn
    """(K/V heads a grid step, blocks a fetch group) for a geometry, or
    None where not even one head fits: the most heads (whole blocks are
    contiguous over heads, so the widest DMA) whose resident set stays
    under ``_VMEM_BUDGET`` at a group of ``_GROUP_TOKENS`` positions."""
    group = max(1, min(n_blocks_seq, _GROUP_TOKENS // block_size))
    for heads in range(n_kv, 0, -1):
        if n_kv % heads == 0 and vmem_bytes(
                heads, group, tq, D, block_size, itemsize) <= _VMEM_BUDGET:
            return heads, group
    return None


def supports(q_shape: tuple[int, ...], n_kv: int, n_blocks_seq: int,
             block_size: int, *, compiled: bool = False) -> bool:  # dlint: static-fn
    """Whether the kernel covers this paged geometry (caller falls back to
    the gather+oracle path otherwise), priced at the widest pool there is
    (float32). ``compiled``: for Mosaic, where a manual DMA cannot slice
    an HBM ref whose minor dim is not lane-aligned (it is padded there);
    interpret mode takes any head dim of whole sublanes."""
    B, T, n_heads, D = q_shape
    if n_heads % n_kv or D % (128 if compiled else 8):
        return False
    tq = T * (n_heads // n_kv)
    return (block_size % 8 == 0 and 0 < tq <= MAX_TQ
            and _plan(n_kv, tq, D, n_blocks_seq, block_size, 4) is not None)


def kernel_choice(q_shape: tuple[int, ...], n_kv: int, n_blocks_seq: int,
                  block_size: int) -> dict | None:  # dlint: static-fn
    """The paged-attention kernel gate: mode selection routes through
    :func:`quant_matmul.pallas_mode_gate` (the ONE gate; fast=False — the
    kernel keeps the oracle's arithmetic, so there is no fast/exact
    numerics split to pick), plus the shape predicate and the plan-free
    requirement (the paged forward auto-shards under a mesh plan, and the
    auto-sharder cannot partition a ``pallas_call``). Returns
    :func:`paged_ragged_attention` kwargs or None (gather+oracle)."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    kw = pallas_mode_gate(False)
    if kw is None or current_plan() is not None:
        return None
    if not supports(q_shape, n_kv, n_blocks_seq, block_size,
                    compiled=not kw["interpret"]):
        return None
    return {"interpret": kw["interpret"]}


def walk_bounds(tables: jax.Array, pos0: jax.Array, T: int, bs: int,
                window: int = 0) -> tuple[jax.Array, jax.Array]:
    """``(first, end)`` of each row's walk over its ``tables [B, M]`` row:
    entries ``first .. end - 1``. ``end = ceil((pos0 + T) / bs)``; ``first``
    is 0, or in a sliding-window layer the block that holds the oldest key
    the row's first query still sees (``pos0 - window + 1``). A row is live
    where the table entry at ``first`` is a real block, else its walk is
    empty (``end`` = 0). Traced: no retrace as depths and tables vary."""
    M = tables.shape[1]
    first = (jnp.maximum(pos0 - window + 1, 0) // bs if window
             else jnp.zeros_like(pos0))
    first = jnp.minimum(first, M - 1)
    live = jnp.take_along_axis(tables, first[:, None], axis=1)[:, 0] != 0
    end = jnp.where(live, jnp.clip(-(-(pos0 + T) // bs), 1, M), 0)
    return jnp.where(live, first, 0), end


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "interpret", "window"))
def paged_ragged_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, layer: jax.Array,
                           tables: jax.Array, positions: jax.Array,
                           head_dim: int, *, interpret: bool = False,
                           window: int = 0) -> jax.Array:
    """Causal GQA attention of ``q [B, T, n_heads, hd]`` over layer
    ``layer`` (a traced scalar) of the WHOLE paged pool ``k/v_pool [L,
    n_blocks, n_kv, bs, hd]`` through block ``tables [B, M]`` (0 = null
    block), with per-row absolute positions ``positions [B, T]`` (affine
    per row, the model's invariant). The pool is never sliced: the layer
    rides in as a scalar-prefetch operand and picks the DMA's source.

    On every row whose first table entry is a real block, equal (to
    float32 reduction-order noise) to::

        gathered = pool[layer, tables]    # the dense logical cache
        view = moveaxis(gathered, 2, 1).reshape(B, n_kv, M*bs, hd)
        attention(q, view_k, view_v, positions, head_dim)

    and zero on a row whose table starts with the null block (a dead
    slot, whatever its stale ``positions`` say).

    ``window`` > 0 (static) is a sliding-window layer: the query at
    position ``i`` sees keys ``i - window + 1 .. i``, the walk starts at the
    block that holds the oldest of them (:func:`walk_bounds`; earlier
    entries are never read, so a returned block's entry may be null), and a
    row is live where THAT entry is a real block. One token a row: with
    ``T`` > 1 a later query's window could begin past the first fetch
    group, which the running maximum does not carry."""
    B, T, n_heads, D = q.shape
    if window and T != 1:
        raise ValueError("a sliding-window walk takes one token a row")
    n_kv, bs = k_pool.shape[2], k_pool.shape[3]
    M = tables.shape[1]
    kv_mul = n_heads // n_kv
    tq = T * kv_mul
    heads, group = _plan(n_kv, tq, D, M, bs, k_pool.dtype.itemsize)

    q_g = (q.reshape(B, T, n_kv, kv_mul, D)
            .transpose(0, 2, 1, 3, 4)
            .reshape(B, n_kv, tq, D)
            .astype(jnp.float32))
    tables = jnp.asarray(tables, jnp.int32)
    pos0 = jnp.asarray(positions, jnp.int32)[:, 0]
    # the walk's bounds, from the table and the depth (traced: no retrace)
    n_first, n_walk = walk_bounds(tables, pos0, T, bs, window)

    q_spec = pl.BlockSpec((1, heads, tq, D),
                          lambda b, g, *_: (b, g, 0, 0),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # tables (flat), pos0, n_walk, n_first, layer
        grid=(B, n_kv // heads),
        in_specs=[q_spec,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, heads, group * bs, D), k_pool.dtype),
            pltpu.VMEM((2, heads, group * bs, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (K | V, half)
            pltpu.VMEM((heads, tq, 1), jnp.float32),  # running maximum
            pltpu.VMEM((heads, tq, 1), jnp.float32),  # running sum
            pltpu.SMEM((1,), jnp.int32),              # next landing half
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, kv_mul=kv_mul, hd=head_dim,
                          group=group, heads=heads, n_entries=M,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_kv, tq, D), jnp.float32),
        interpret=interpret,
    )(tables.reshape(-1), pos0, n_walk, n_first,
      jnp.asarray(layer, jnp.int32).reshape(1), q_g, k_pool, v_pool)

    return (out.reshape(B, n_kv, T, kv_mul, D)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, T, n_heads, D)
               .astype(q.dtype))
