"""Latent attention (MLA) over a cache of ONE compressed row a token: two
forms and their oracle (models/axk1.py).

**What is cached.** Per token and layer one row ``[c | k_r | 0]``: the
normed latent ``c`` (``kv_lora`` lanes), the rotated key ``k_r`` every head
shares (``rope`` lanes) and zeros up to whole lane tiles (``row`` lanes:
576 useful of 640 at the published sizes). Per-head keys and values are
never written anywhere.

**The oracle** (:func:`latent_attention_oracle`) is the unabsorbed per-token
form: ``[k_n | v]_j = W_ukv c_j`` for every head, ``s_ij = (q_n,i . k_n,j +
q_r,i . k_r,j) scale``, a dense causal softmax, ``o_i = sum_j p_ij v_j``.

**Both served forms are absorbed**, the same numbers in another order:
``q'_i = W_uk^T q_n,i`` (:func:`absorb_q`, ``kv_lora`` wide a head), ``s_ij =
(q'_i . c_j + q_r,i . k_r,j) scale``, ``o'_i = sum_j p_ij c_j``, ``o_i = W_uv
o'_i`` (:func:`unabsorb_o`). With ``q'' = [q' | q_r | 0]`` a head's score is
ONE dot with the cached row, and the value is the row's first ``kv_lora``
lanes: multi-query attention in which every head reads the same row.

* **The step form** (:func:`mla_paged_step`, a Pallas kernel; its table walk
  is :mod:`paged_attention`'s: ``walk_bounds``, the scalar-prefetched layer,
  the null block, the newest block re-read past a row's bound) is handed the
  WHOLE pool ``[L, n_blocks, 1, bs, row]`` and the layer index; a grid step
  is one row of the batch: all its heads against fetch groups of
  ``_GROUP_TOKENS`` cached tokens, ONE DMA a block (a block is ``bs x row``
  contiguous), double-buffered, under a running softmax. The dots take the
  pool's dtype (bfloat16 operands, float32 accumulation, in serving): at
  ``2 H (row + kv_lora)`` FLOP a ``2 * latent_dim`` useful bytes it sits near
  the chip's ridge, where float32 passes would put it over. Off a TPU, and
  where the gate says no, :func:`mla_paged_step_xla` gathers the row's
  blocks and runs the same arithmetic in one softmax.
* **The chunk form** (:func:`mla_chunk`) walks the admission's dense latent
  column in blocks of keys under a running softmax (``ops/flash_attention.py``'s
  scheme), as far as each tile of queries sees, so the ``[T H, S]`` score
  matrix (1.1 GB in float32 at 256 rows, 64 heads and 17k keys) never exists.
  It too is a Pallas kernel on a TPU (:func:`mla_chunk_kernel`: the ``T H``
  absorbed query rows in tiles of ``_CHUNK_TQ``, each against key blocks of
  ``_CHUNK_TK`` of the WHOLE column ``[L, 1, 1, S, row]`` at a
  scalar-prefetched layer, the block index clamped to the tile's last
  visible block so that what lies behind it is neither fetched nor
  computed; score tile, probabilities and accumulator stay in VMEM): XLA's
  form (:func:`mla_chunk_xla`, a ``fori_loop`` over blocks of
  ``CHUNK_BLOCK`` keys, the form every other backend runs) writes each
  block's score tile to HBM between its two dots, 270 MB a block a layer at
  the published sizes, and a chunk spent four fifths of its time there.
  Absorbed, a chunk pays ``2 T H (row + kv_lora)`` FLOP a cached token;
  expanding a block's K/V first would pay ``2 kv_lora H (nope + v)`` a cached
  token before any score (more, below 390 rows a chunk), and write them out.

Mode selection routes through :func:`quant_matmul.pallas_mode_gate` (the ONE
gate): the kernels on a TPU (interpret mode where forced off one), the XLA
forms otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import walk_bounds

_HIGHEST = jax.lax.Precision.HIGHEST

# cached tokens a fetch group covers (the running softmax's step): four
# lane tiles of scores for every head, 32 blocks of 16 a loop trip
_GROUP_TOKENS = 512
# keys a block of the chunk form's XLA walk covers
CHUNK_BLOCK = 1024
# the chunk kernel's tiles: absorbed query rows (32 tokens of 64 heads), keys
_CHUNK_TQ, _CHUNK_TK = 2048, 512
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = 12 * 1024 * 1024


def absorb_q(q_n: jax.Array, q_r: jax.Array, wuk: jax.Array,
             row: int) -> jax.Array:
    """``q'' [..., H, row]`` = ``[W_uk^T q_n | q_r | 0]`` from ``q_n [..., H,
    nope]``, the rotated ``q_r [..., H, rope]`` and ``wuk [H, nope,
    kv_lora]``: a head's score with a cached row is one dot with it."""
    qa = jnp.einsum("...hd,hdc->...hc", q_n, wuk.astype(q_n.dtype),
                    preferred_element_type=jnp.float32)
    pad = row - qa.shape[-1] - q_r.shape[-1]
    parts = [qa.astype(q_n.dtype), q_r.astype(q_n.dtype)]
    if pad:
        parts.append(jnp.zeros(q_r.shape[:-1] + (pad,), q_n.dtype))
    return jnp.concatenate(parts, axis=-1)


def unabsorb_o(o_lat: jax.Array, wuv: jax.Array, dtype) -> jax.Array:
    """``o [..., H, v] = W_uv o'`` from ``o' [..., H, kv_lora]`` and ``wuv
    [H, v, kv_lora]``."""
    return jnp.einsum("...hc,hvc->...hv", o_lat.astype(dtype),
                      wuv.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def latent_attention_oracle(q_n, q_r, c, k_r, wuk, wuv, q_pos, scale):
    """The unabsorbed per-token form, float32: queries ``q_n [T, H, nope]``,
    ``q_r [T, H, rope]`` at positions ``q_pos [T]`` over the cached ``c [S,
    kv_lora]``, ``k_r [S, rope]`` (key ``j`` at position ``j``); per-head keys
    and values are expanded for every cached token. ``[T, H, v]``."""
    f32 = lambda a: a.astype(jnp.float32)
    k_n = jnp.einsum("sc,hdc->shd", f32(c), f32(wuk), precision=_HIGHEST)
    v = jnp.einsum("sc,hvc->shv", f32(c), f32(wuv), precision=_HIGHEST)
    s = (jnp.einsum("thd,shd->hts", f32(q_n), k_n, precision=_HIGHEST)
         + jnp.einsum("thr,sr->hts", f32(q_r), f32(k_r), precision=_HIGHEST))
    seen = jnp.arange(c.shape[0])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s * scale, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shv->thv", p, v, precision=_HIGHEST)


# -- the chunk form ----------------------------------------------------------


def mla_chunk_xla(qa: jax.Array, col: jax.Array, start_pos: jax.Array,
                  scale: float, vdim: int) -> jax.Array:
    """Absorbed queries ``qa [T, H, row]`` (query ``t`` at position
    ``start_pos + t``) over ONE sequence's dense latent column ``col [S,
    row]``, the chunk's own rows already written: float32 ``o' [T, H,
    vdim]``. Blocks of the context under a running softmax, as far as the
    last query sees; the operands keep the column's dtype, the statistics and
    the accumulator are float32. :func:`mla_chunk_kernel`'s oracle."""
    T, H, R = qa.shape
    S = col.shape[0]
    block = next(b for b in (CHUNK_BLOCK, 512, 256, 128, S) if S % b == 0)
    dt = col.dtype
    exact = dt == jnp.float32
    with jax.named_scope("mla_chunk"):
        q2 = qa.reshape(T * H, R).astype(dt)
        q_pos = jnp.repeat(start_pos + jnp.arange(T, dtype=jnp.int32), H)
        prec = _HIGHEST if exact else None

        def body(j, carry):
            m, l, acc = carry
            rows = jax.lax.dynamic_slice_in_dim(col, j * block, block, 0)
            s = jax.lax.dot_general(
                q2, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec) * scale
            key_pos = j * block + jnp.arange(block, dtype=jnp.int32)
            s = jnp.where(key_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
            # key 0 is in the first block and every query sees it: the
            # running maximum is finite from the first block on
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(dt), rows[:, :vdim], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)
            return m_new, l, acc

        n = (start_pos + T + block - 1) // block
        init = (jnp.full((T * H, 1), -jnp.inf, jnp.float32),
                jnp.zeros((T * H, 1), jnp.float32),
                jnp.zeros((T * H, vdim), jnp.float32))
        _m, l, acc = jax.lax.fori_loop(0, n, body, init)
        return (acc / l).reshape(T, H, vdim)


def _chunk_kernel(layer_ref, last_ref, q_ref, qpos_ref, col_ref, out_ref,
                  m_ref, l_ref, *, tk: int, scale: float, vdim: int,
                  exact: bool):
    """One tile of absorbed query rows ``q_ref [tq, row]`` (row ``r`` at
    position ``qpos_ref[r]``) against key block ``j`` of the column,
    ``col_ref [tk, row]``; ``last_ref[i]`` is tile ``i``'s last visible
    block. ``out_ref`` doubles as the float32 accumulator."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j <= last_ref[i])
    def _():
        q, rows = q_ref[...], col_ref[...]
        prec = _HIGHEST if exact else None
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec) * scale
        key_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(key_pos <= qpos_ref[...], s, -jnp.inf)
        # key 0 is in block 0 and every query sees it: the running maximum
        # is finite from the first block on
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_next
        out_ref[...] = alpha * out_ref[...] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :vdim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = out_ref[...] / l_ref[...]


def _chunk_tiles(n_rows: int, S: int, compiled: bool):  # dlint: static-fn
    """``(tq, tk)`` of the chunk kernel for ``n_rows`` query rows over ``S``
    keys: the widest that divide them in whole sublanes (of a bfloat16 tile
    where Mosaic compiles it); None where nothing does."""
    least = 16 if compiled else 8
    tq = next((t for t in (_CHUNK_TQ, 1024, 512, 256, 128, 64, 32, 16, 8)
               if t >= least and n_rows % t == 0), None)
    tk = next((t for t in (_CHUNK_TK, 256, 128) if S % t == 0), None)
    return None if tq is None or tk is None else (tq, tk)


def chunk_kernel_choice(qa_shape: tuple[int, ...], S: int,
                        row: int) -> dict | None:  # dlint: static-fn
    """The chunk kernel's gate: :func:`quant_matmul.pallas_mode_gate` (the
    ONE gate), no mesh plan, tiles that divide the shapes, whole lane tiles
    a row where Mosaic compiles it. :func:`mla_chunk_kernel` kwargs or
    None."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    kw = pallas_mode_gate(False)
    if kw is None or current_plan() is not None:
        return None
    T, H, R = qa_shape
    compiled = not kw["interpret"]
    if R != row or row % (128 if compiled else 8) \
            or _chunk_tiles(T * H, S, compiled) is None:
        return None
    return {"interpret": kw["interpret"]}


@functools.partial(jax.jit, static_argnames=("scale", "vdim", "interpret"))
def mla_chunk_kernel(qa: jax.Array, col: jax.Array, layer: jax.Array,
                     start_pos: jax.Array, *, scale: float, vdim: int,
                     interpret: bool = False) -> jax.Array:
    """:func:`mla_chunk_xla` as a Pallas kernel over layer ``layer`` (a
    traced scalar) of the WHOLE column ``col [L, 1, 1, S, row]``: float32
    ``o' [T, H, vdim]``, equal to the XLA form to reduction-order noise."""
    T, H, R = qa.shape
    S = col.shape[3]
    tq, tk = _chunk_tiles(T * H, S, not interpret)
    dt = col.dtype
    n_tiles = T * H // tq
    start_pos = jnp.asarray(start_pos, jnp.int32)
    q_pos = start_pos + jnp.arange(T * H, dtype=jnp.int32) // H
    last = q_pos[tq - 1::tq] // tk                  # [n_tiles]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer, each tile's last visible block
        grid=(n_tiles, S // tk),
        in_specs=[
            pl.BlockSpec((tq, R), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j, *_: (i, 0)),
            # a block behind the tile's last is the last again: not fetched
            pl.BlockSpec((None, None, None, tk, R),
                         lambda i, j, layer, last: (
                             layer[0], 0, 0, jnp.minimum(j, last[i]), 0)),
        ],
        out_specs=pl.BlockSpec((tq, vdim), lambda i, j, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),    # running maximum
                        pltpu.VMEM((tq, 1), jnp.float32)])   # running sum
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, tk=tk, scale=scale, vdim=vdim,
                          exact=dt == jnp.float32),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * H, vdim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        name="mla_chunk", interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), last,
      qa.reshape(T * H, R).astype(dt), q_pos[:, None], col)
    return out.reshape(T, H, vdim)


def mla_chunk(qa: jax.Array, col: jax.Array, layer: jax.Array,
              start_pos: jax.Array, scale: float, vdim: int,
              kernel: dict | None) -> jax.Array:
    """The chunk form over layer ``layer`` of the whole column ``col [L, 1,
    1, S, row]``: the kernel where :func:`chunk_kernel_choice` gave its
    kwargs, the XLA walk over the layer's slice otherwise."""
    if kernel is not None:
        return mla_chunk_kernel(qa, col, layer, start_pos, scale=scale,
                                vdim=vdim, **kernel)
    rows = jax.lax.dynamic_index_in_dim(col, layer, 0, keepdims=False)[0, 0]
    return mla_chunk_xla(qa, rows, start_pos, scale, vdim)


# -- the step form -----------------------------------------------------------


def _step_kernel(tbl_ref, pos_ref, nblk_ref, layer_ref, q_ref, pool_hbm,
                 out_ref, buf, sems, m_ref, l_ref, slot_ref, *, bs: int,
                 group: int, n_entries: int, scale: float, vdim: int):
    """One row of the batch: its heads ``q_ref [1, H, row]`` against its
    table's blocks ``0 .. nblk - 1`` in fetch groups of ``group``. ``buf [2,
    group * bs, row]`` is the double-buffered landing zone; ``out_ref``
    doubles as the float32 accumulator. ``slot_ref[0]`` is the half this
    row's first group lands in: whoever ran before started that fetch."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    gt = group * bs
    H = q_ref.shape[1]
    n = nblk_ref[b]
    trips = pl.cdiv(n, group)

    def fetch(row, j, slot):
        last = nblk_ref[row] - 1
        copies = []
        for i in range(group):
            # entries past the row's bound re-read its own newest block
            blk = tbl_ref[row * n_entries + jnp.minimum(j * group + i, last)]
            copies.append(pltpu.make_async_copy(
                pool_hbm.at[layer, blk, 0],
                buf.at[slot, pl.ds(i * bs, bs), :], sems.at[slot]))
        return copies

    nxt_row = jnp.minimum(b + 1, n_rows - 1)
    nxt_live = jnp.logical_and(b + 1 < n_rows, nblk_ref[nxt_row] > 0)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0

        @pl.when(n > 0)
        def _():
            for c in fetch(b, 0, 0):
                c.start()

    @pl.when(n == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(nxt_live)
        def _():
            for c in fetch(nxt_row, 0, slot_ref[0]):
                c.start()

    @pl.when(n > 0)
    def _():
        slot0 = slot_ref[0]
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        out_ref[...] = jnp.zeros_like(out_ref)
        # column s of the cache is visible iff s <= pos0 (one token a row)
        reach = pos_ref[b] - jax.lax.broadcasted_iota(jnp.int32, (H, gt), 1)
        q = q_ref[0]

        def body(j, _):
            slot = (slot0 + j) % 2
            more = j + 1 < trips

            @pl.when(jnp.logical_or(more, nxt_live))
            def _():
                for c in fetch(jnp.where(more, b, nxt_row),
                               jnp.where(more, j + 1, 0), 1 - slot):
                    c.start()

            for c in fetch(b, j, slot):
                c.wait()
            rows = buf[slot]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # (H, gt)
            s = jnp.where(reach >= j * gt, s, -jnp.inf)
            m_prev = m_ref[...]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            m_ref[...] = m_next
            out_ref[0] = alpha * out_ref[0] + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :vdim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # (H, vdim)

        jax.lax.fori_loop(0, trips, body, None)
        out_ref[0] = out_ref[0] / l_ref[...]
        slot_ref[0] = (slot0 + trips) % 2


def _group(n_blocks_seq: int, block_size: int) -> int:  # dlint: static-fn
    return max(1, min(n_blocks_seq, _GROUP_TOKENS // block_size))


def supports(q_shape: tuple[int, ...], row: int, n_blocks_seq: int,
             block_size: int, *, compiled: bool = False) -> bool:  # dlint: static-fn
    """Whether the step kernel covers ``q'' [B, 1, H, row]`` over tables of
    ``n_blocks_seq`` blocks: one token a row, whole sublanes a block, whole
    lane tiles a row where Mosaic compiles it (``ModelConfig.latent_row``
    pads to them), the resident set (priced at float32) under the budget."""
    _B, T, H, R = q_shape
    if T != 1 or R != row or row % (128 if compiled else 8) \
            or block_size % 8:
        return False
    gt = _group(n_blocks_seq, block_size) * block_size
    heads = -(-H // 8) * 8
    resident = (2 * gt * row * 4 + 4 * heads * row * 4
                + 4 * heads * max(gt, 128) * 4)
    return resident <= _VMEM_BUDGET


def step_kernel_choice(q_shape: tuple[int, ...], row: int, n_blocks_seq: int,
                       block_size: int) -> dict | None:  # dlint: static-fn
    """The step kernel's gate: :func:`quant_matmul.pallas_mode_gate` (the ONE
    gate; fast=False: the kernel keeps its XLA form's arithmetic), no mesh
    plan, then :func:`supports`. :func:`mla_paged_step` kwargs or None."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    kw = pallas_mode_gate(False)
    if kw is None or current_plan() is not None:
        return None
    if not supports(q_shape, row, n_blocks_seq, block_size,
                    compiled=not kw["interpret"]):
        return None
    return {"interpret": kw["interpret"]}


@functools.partial(jax.jit, static_argnames=("scale", "vdim", "interpret"))
def mla_paged_step(qa: jax.Array, pool: jax.Array, layer: jax.Array,
                   tables: jax.Array, pos0: jax.Array, *, scale: float,
                   vdim: int, interpret: bool = False) -> jax.Array:
    """Absorbed queries ``qa [B, 1, H, row]`` (row ``b``'s one token at
    position ``pos0[b]``, its own latent row already written) over layer
    ``layer`` (a traced scalar) of the WHOLE latent pool ``[L, n_blocks, 1,
    bs, row]`` through block ``tables [B, M]`` (0 = null block): float32
    ``o' [B, 1, H, vdim]``, the softmax-weighted sum of the first ``vdim``
    lanes of the rows each query sees; zero on a row whose table starts with
    the null block. Equal to :func:`mla_paged_step_xla` to reduction-order
    noise."""
    B, _T, H, R = qa.shape
    bs = pool.shape[3]
    M = tables.shape[1]
    group = _group(M, bs)
    tables = jnp.asarray(tables, jnp.int32)
    pos0 = jnp.asarray(pos0, jnp.int32)
    _first, n_walk = walk_bounds(tables, pos0, 1, bs)
    q_spec = pl.BlockSpec((1, H, R), lambda b, *_: (b, 0, 0),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # tables (flat), pos0, n_walk, layer
        grid=(B,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, vdim), lambda b, *_: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, group * bs, R), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, 1), jnp.float32),    # running maximum
            pltpu.VMEM((H, 1), jnp.float32),    # running sum
            pltpu.SMEM((1,), jnp.int32),        # next landing half
        ])
    out = pl.pallas_call(
        functools.partial(_step_kernel, bs=bs, group=group, n_entries=M,
                          scale=scale, vdim=vdim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, vdim), jnp.float32),
        name="mla_paged_step", interpret=interpret,
    )(tables.reshape(-1), pos0, n_walk,
      jnp.asarray(layer, jnp.int32).reshape(1),
      qa[:, 0].astype(pool.dtype), pool)
    return out[:, None]


def mla_paged_step_xla(qa: jax.Array, pool: jax.Array, layer: jax.Array,
                       tables: jax.Array, pos0: jax.Array, *, scale: float,
                       vdim: int) -> jax.Array:
    """:func:`mla_paged_step`'s oracle, and the form every backend but a TPU
    runs: gather the rows' blocks out of layer ``layer`` into dense columns,
    one softmax a row."""
    B, _T, H, R = qa.shape
    dt = pool.dtype
    prec = _HIGHEST if dt == jnp.float32 else None
    view = pool[layer, tables][:, :, 0].reshape(B, -1, R)         # [B, S, R]
    s = jnp.einsum("bhr,bsr->bhs", qa[:, 0].astype(dt), view,
                   preferred_element_type=jnp.float32,
                   precision=prec) * scale
    seen = jnp.arange(view.shape[1])[None, :] <= pos0[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", p.astype(dt), view[..., :vdim],
                   preferred_element_type=jnp.float32, precision=prec)
    live = (tables[:, 0] != 0)[:, None, None]
    return jnp.where(live, o, 0.0)[:, None]
