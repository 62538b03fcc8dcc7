"""A decoder of latent attention (MLA) layers over a cache of ONE compressed
row a token, a leading dense layer, then a sigmoid group-limited router over
experts of which this chip may hold a SHARE, and a shared one
(``ArchType.AXK1``; A.X-K1 is 61 such layers, 64 heads, 192 routed experts in
8 groups).

**The equations.** Layer ``l``, input ``x``, pre-norm (RMS)::

    h = rmsnorm(x; w_in);  x = x + MLA(h);  g = rmsnorm(x; w_ff);  x = x + FFN_l(g)

    MLA:  c_q = rmsnorm(W_dq h; w_qa)
          [q_n | q_r] = W_uq c_q      a head: nope lanes, then rope lanes; q_r = rope(q_r)
          [c | k_r]   = W_dkv h       c = rmsnorm(c; w_kva);  k_r = rope(k_r): ONE k_r for all heads
          THE CACHE holds [c | k_r] a token a layer (``cfg.latent_row`` lanes, the tail zero)
          s_ij = (W_uk^T q_n,i . c_j + q_r,i . k_r,j) * cfg.attn_scale      (absorbed)
          o_i  = W_uv sum_j softmax_j(s_ij) c_j;   out = W_o concat_heads(o)
    FFN_0 dense SwiGLU (``n_dense_layers`` leading layers); every other layer the
    share's routed feed-forward (models/share.py: the router's sigmoid, its
    groups, the held experts, the shared one).

The rope lanes pair half-split (lane ``j`` with ``j + r/2``) under YaRN's
banded frequencies; with ``yarn_mscale == yarn_mscale_all_dim`` the tables are
unscaled and ``cfg.attn_scale`` carries the square of the mscale on the whole
score, nope part too (not ``rope.yarn_attention_factor``'s convention).

**Both programs are absorbed** (ops/mla.py): per-head keys and values never
exist in HBM, not in the cache and not as temporaries. ``W_uk`` and ``W_uv``
are contracted per head on the OUTPUT side of their plane, so they are held
per head in the compute dtype (``wuk``, ``wuv [L, H, ., kv_lora]``,
dequantized once at load); every other matmul is a Q40 plane that reaches
``linear`` as stack + index. ``W_dkv``'s plane is padded to ``cfg.latent_row``
columns (zero codes), whole lane tiles for the fused kernels.

**The stack** is ONE scan over the layer index with the whole latent pool
(or an admission's whole dense column) in the carry, written in place; layer
0's dense feed-forward is a ``lax.cond`` on the traced index.

* :func:`forward`: a prefill chunk ``[1, T]`` over a :class:`LatentColumn`
  (one sequence's rows of every layer, gathered through its block table, so a
  matched prefix is there): the chunk's rows written at ``start_pos``, then
  :func:`~dllama_tpu.ops.mla.mla_chunk` (its kernel on a TPU, its XLA walk
  off one).
* :func:`paged_forward`: the decode step over the pool through the rows'
  block tables: :func:`~dllama_tpu.ops.mla.mla_paged_step` (its XLA form off
  a TPU).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import mla
from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm
from ..parallel.api import current_plan
from ..runtime.introspection import note_mla_path
from .config import ModelConfig
from .family import Family, Refusal, layer_kinds
from .llama import Params, _live_rows, _stack_at, _write_kv_rows
from .rope import apply_rope_partial, build_partial_rope_cache, yarn_mscale
from .share import (ffn_half, require_quantized, zero_stats,  # noqa: F401
                    zero_totals)


class AxK1Layers(NamedTuple):
    """``Params.layers``: attention's leaves stacked over all ``L`` layers,
    the dense feed-forward's over the leading ``n_dense_layers``, the routed
    one's over the ``n_moe_layers`` that have one (models/share.py)."""

    wdq: Weight            # [L, q_lora, dim]
    norm_qa: jax.Array     # [L, q_lora]
    wuq: Weight            # [L, H * head_dim, q_lora]
    wdkv: Weight           # [L, latent_row, dim]: c rows, k_r rows, zero rows
    norm_kva: jax.Array    # [L, kv_lora]
    wuk: jax.Array         # [L, H, nope, kv_lora], compute dtype
    wuv: jax.Array         # [L, H, v, kv_lora], compute dtype
    wo: Weight             # [L, dim, H * v]
    norm_att: jax.Array    # [L, dim]
    norm_ffn: jax.Array    # [L, dim]
    w1: Weight             # [n_dense, dense_hidden, dim]
    w2: Weight
    w3: Weight
    moe_gate: jax.Array    # [NM, router_width, dim] float32
    we1: Weight            # [NM, held, dim, hidden]
    we2: Weight
    we3: Weight
    ws1: Weight | None     # [NM, shared, dim]
    ws2: Weight | None
    ws3: Weight | None


_ATTN_MATMULS = ("wdq", "wuq", "wdkv", "wo")
_ATTN_LEAVES = _ATTN_MATMULS + ("norm_qa", "norm_kva", "wuk", "wuv",
                                "norm_att")


class _Attn(NamedTuple):
    wdq: Weight
    wuq: Weight
    wdkv: Weight
    wo: Weight
    norm_qa: jax.Array
    norm_kva: jax.Array
    wuk: jax.Array
    wuv: jax.Array
    norm_att: jax.Array


class LatentColumn(NamedTuple):
    """One slot's context during chunked prefill: its latent rows of every
    layer as a dense column, and the chunks' routing counters."""

    c: jax.Array       # [L, 1, 1, S, latent_row]
    stats: jax.Array   # [share.N_COUNTS + held] int32

    @classmethod
    def zeros(cls, cfg: ModelConfig, dtype) -> "LatentColumn":
        from ..runtime.kvcache import padded_cache_len

        return cls(c=jnp.zeros((cfg.n_layers, 1, 1,
                                padded_cache_len(cfg.seq_len),
                                cfg.latent_row), dtype),
                   stats=zero_stats(cfg))


def rope_table(cfg: ModelConfig):
    """``(cos, sin) [seq_len, qk_rope_dim / 2]``: YaRN's frequencies, scaled
    by the ratio of the two mscales (1 where they are equal)."""
    f = float(cfg.rope_scaling_factor)
    yarn = ((f, int(cfg.rope_scaling_orig_max_seq_len),
             float(cfg.rope_scaling_high_freq_factor),
             float(cfg.rope_scaling_low_freq_factor)) if f > 1.0 else None)
    scale = (yarn_mscale(f, cfg.yarn_mscale)
             / yarn_mscale(f, cfg.yarn_mscale_all_dim))
    return build_partial_rope_cache(cfg.seq_len, cfg.qk_rope_dim,
                                    float(cfg.rope_theta), yarn, scale)


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a decoder with latent attention and an expert "
                         "share has no mesh plan (tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a decoder with latent attention supports neither "
                         "Q80 sync emulation nor offloaded weights")


def latent_inputs(cfg: ModelConfig, h: jax.Array, ap, table,
                  positions: jax.Array):
    """From the normed input ``h [B, T, dim]``: the queries ``q_n [B, T, H,
    nope]``, ``q_r [B, T, H, rope]`` (rotated), and the token's cache row
    ``[B, T, latent_row]`` = ``[rmsnorm(c) | rope(k_r) | 0]``."""
    B, T, _ = h.shape
    H, nope, r = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    c_q = rms_norm(linear(h, ap.wdq), ap.norm_qa, cfg.norm_epsilon)
    q = linear(c_q, ap.wuq).reshape(B, T, H, cfg.head_dim)
    q_r = apply_rope_partial(q[..., nope:], *table, positions)
    kv = linear(h, ap.wdkv)
    c = rms_norm(kv[..., :cfg.kv_lora_rank], ap.norm_kva, cfg.norm_epsilon)
    k_r = apply_rope_partial(
        kv[..., None, cfg.kv_lora_rank:cfg.latent_dim], *table,
        positions)[:, :, 0]
    pad = jnp.zeros((B, T, cfg.latent_row - cfg.latent_dim), c.dtype)
    return q[..., :nope], q_r, jnp.concatenate([c, k_r, pad], axis=-1)


def _attention_half(cfg: ModelConfig, x: jax.Array, ap, table, positions,
                    attend):
    """A layer's attention half, residual added: ``attend(qa, row) -> o'``
    owns the cache (it writes ``row`` and attends the absorbed queries)."""
    B, T, _ = x.shape
    h = rms_norm(x, ap.norm_att, cfg.norm_epsilon)
    q_n, q_r, row = latent_inputs(cfg, h, ap, table, positions)
    qa = mla.absorb_q(q_n, q_r, ap.wuk, cfg.latent_row)
    o = mla.unabsorb_o(attend(qa, row), ap.wuv, x.dtype)
    return x + linear(o.reshape(B, T, cfg.n_heads * cfg.v_head_dim), ap.wo)


def _scan_layers(params: Params, cfg: ModelConfig, x, cache, stats, live,
                 positions, attend):
    """The layer scan both programs share: ``cache`` (a column's rows or the
    pool) and ``stats`` ride the carry whole. ``attend(qa, row, cache, l) ->
    (o', cache)`` is layer ``l``'s attention over it."""
    lp: AxK1Layers = params.layers
    table = rope_table(cfg)
    attn = _Attn(*(getattr(lp, n) for n in _ATTN_LEAVES))

    def layer(carry, l):
        x, cache, stats = carry
        box = {}

        def att(qa, row):
            out, box["cache"] = attend(qa, row, cache, l)
            return out

        x = _attention_half(cfg, x, _stack_at(attn, l, _ATTN_MATMULS), table,
                            positions, att)
        x, s = ffn_half(cfg, x, lp, l, live, may_be_dense=True)
        return (x, box["cache"], stats + s), None

    (x, cache, stats), _ = jax.lax.scan(
        layer, (x, cache, stats), jnp.arange(cfg.n_layers, dtype=jnp.int32))
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    logits = linear(x, params.logits, out_axis="vocab").astype(jnp.float32)
    return logits, cache, stats


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: LatentColumn,
            n_valid: jax.Array | None = None):
    """A chunk ``tokens [1, T]`` at scalar ``start_pos`` over a latent
    column: float32 logits ``[1, T, vocab]`` and the column with the chunk's
    rows written. Positions at or past ``n_valid`` (absent: all ``T``) are
    padding: their rows are overwritten later, and they are not routed."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    B, T = tokens.shape
    if start_pos.ndim or B != 1:
        raise ValueError("the chunk form takes one sequence at one start "
                         "position (the dense slot pool's ragged rows are "
                         "not carried to a latent column)")
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    live = jnp.arange(T) < n_valid
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)[None, :]
    kernel = mla.chunk_kernel_choice((T, cfg.n_heads, cfg.latent_row),
                                     col.c.shape[3], cfg.latent_row)
    note_mla_path("chunk", "xla" if kernel is None else "pallas")

    def attend(qa, row, c, l):
        c = jax.lax.dynamic_update_slice(
            c, row.astype(c.dtype)[None, :, None], (l, 0, 0, start_pos, 0))
        o = mla.mla_chunk(qa[0], c, l, start_pos, cfg.attn_scale,
                          cfg.kv_lora_rank, kernel)
        return o[None], c

    logits, c, stats = _scan_layers(params, cfg, x, col.c, col.stats, live,
                                    positions, attend)
    return logits, LatentColumn(c=c, stats=stats)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """The decode step over the latent pool: ``tokens [B, 1]`` at per-row
    ``pos_vec``; ``cache = (PagedKVCache whose k is the pool [L, n_blocks, 1,
    bs, latent_row] and whose v is None, totals)``, both given back (the pool
    written in place, the step's routing counters added to row 0 of
    ``totals``, :func:`~dllama_tpu.models.share.zero_totals`); ``tables [B,
    M]`` the rows' block tables. A row is live where its table starts with a
    real block."""
    from ..runtime.kvblocks import PagedKVCache

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("the step form takes one token a row: the latent "
                         "walk carries no speculative verify")
    pkv, totals = cache
    pos0 = jnp.asarray(pos_vec, dtype=jnp.int32)
    positions = pos0[:, None]
    live = _live_rows(tables)
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    bs, M = pkv.k.shape[3], tables.shape[1]
    blk = tables[jnp.arange(B, dtype=jnp.int32)[:, None], positions // bs]
    off = positions % bs
    kernel = mla.step_kernel_choice((B, T, cfg.n_heads, cfg.latent_row),
                                    cfg.latent_row, M, bs)
    note_mla_path("step", "xla" if kernel is None else "pallas")
    step = (mla.mla_paged_step_xla if kernel is None
            else lambda *a, **kw: mla.mla_paged_step(*a, **kw, **kernel))

    def attend(qa, row, pool, l):
        # inactive rows carry all-null tables: their writes land in the
        # null block
        pool = _write_kv_rows(pool, l, row[:, :, None], blk, off)
        o = step(qa, pool, l, tables, pos0, scale=cfg.attn_scale,
                 vdim=cfg.kv_lora_rank)
        return o, pool

    logits, pool, stats = _scan_layers(params, cfg, x, pkv.k,
                                       zero_stats(cfg), live, positions,
                                       attend)
    return logits, (PagedKVCache(k=pool, v=None), totals.at[0].add(stats))


def _load_params(ld, cfg: ModelConfig) -> Params:
    """From the tensors ``mfile._walk_axk1_layer`` names. ``W_dkv``'s plane
    is padded with zero columns to ``cfg.latent_row`` (whole lane tiles for
    the fused kernels); ``W_ukv`` is contracted per head on its plane's
    output side in the absorbed form, so it is held per head in the compute
    dtype (``wuk``, ``wuv [L, H, ., kv_lora]``), dequantized once here."""
    require_quantized(ld)
    h = ld.h
    every = list(range(h.n_layers))
    dense_ids, moe_ids = every[:h.n_dense_layers], every[h.n_dense_layers:]
    mm = lambda ids, name, o, i, **kw: ld.matmul(
        name, o, i, stacked=True, out_axis=None, in_axis=None, layers=ids,
        **kw)
    H, r, nope = h.n_heads, h.kv_lora_rank, h.qk_nope_head_dim
    wdkv = mm(every, "block_mla_dkv", r + h.qk_rope_head_dim, h.dim)
    pad = cfg.latent_row - cfg.latent_dim
    wdkv = jax.tree.map(
        lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),)), wdkv)
    wukv = mm(every, "block_mla_ukv", H * (nope + h.v_head_dim), r,
              force_dense=jnp.dtype(cfg.compute_dtype)).reshape(
        h.n_layers, H, nope + h.v_head_dim, r)
    wide, sh = h.dense_hidden_dim, h.shared_expert_dim
    experts = lambda name, o, i: ld.expert_stack(name, o, i, None, None,
                                                 layers=moe_ids)
    return ld.params(AxK1Layers(
        wdq=mm(every, "block_mla_dq", h.q_lora_rank, h.dim),
        norm_qa=ld.stacked_f32("block_mla_norm_q", h.q_lora_rank),
        wuq=mm(every, "block_mla_uq", H * h.head_dim, h.q_lora_rank),
        wdkv=wdkv,
        norm_kva=ld.stacked_f32("block_mla_norm_kv", r),
        wuk=wukv[:, :, :nope], wuv=wukv[:, :, nope:],
        wo=mm(every, "block_matmul_wo", h.dim, H * h.v_head_dim),
        norm_att=ld.stacked_f32("block_norm_0", h.dim),
        norm_ffn=ld.stacked_f32("block_norm_1", h.dim),
        w1=mm(dense_ids, "block_matmul_w1", wide, h.dim),
        w2=mm(dense_ids, "block_matmul_w2", h.dim, wide),
        w3=mm(dense_ids, "block_matmul_w3", wide, h.dim),
        moe_gate=ld.stacked_f32("block_moe_gate", h.moe_router_width, h.dim,
                                layers=moe_ids),
        we1=experts("block_expert_w1", h.hidden_dim, h.dim),
        we2=experts("block_expert_w2", h.dim, h.hidden_dim),
        we3=experts("block_expert_w3", h.hidden_dim, h.dim),
        ws1=mm(moe_ids, "block_shared_w1", sh, h.dim) if sh else None,
        ws2=mm(moe_ids, "block_shared_w2", h.dim, sh) if sh else None,
        ws3=mm(moe_ids, "block_shared_w3", sh, h.dim) if sh else None))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # what is HELD: latent attention's planes a layer (W_ukv per head in
    # the compute dtype: two Q40 weights' bytes a weight), the
    # leading dense feed-forward, the held experts of a routed layer with its
    # router (over its whole width) and shared expert, the vocabulary's
    # rows
    H = cfg.n_heads
    attn = (cfg.dim * (cfg.q_lora_rank + cfg.latent_row)
            + cfg.q_lora_rank * H * cfg.head_dim
            + 2 * cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * cfg.dim)
    routed = (cfg.dim * cfg.moe_router_width
              + 3 * cfg.dim * (cfg.hidden_dim * cfg.n_experts
                               + cfg.shared_expert_dim))
    return (cfg.n_layers * attn
            + cfg.n_dense_layers * 3 * cfg.dim * cfg.dense_hidden_dim
            + cfg.n_moe_layers * routed + cfg.dim * cfg.vocab_size)


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=None,
    # the slot's latent rows through its table, matched prefix blocks
    # included: the chunks attend over them as they lie
    column=lambda cfg, k, v: LatentColumn(c=k, stats=zero_stats(cfg)),
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(latent=cfg.n_layers),
    describe=lambda cfg, engine: (
        f"; layers: {cfg.n_layers} of latent attention (a row of "
        f"{cfg.latent_dim} in {cfg.latent_row} lanes a token); "
        f"experts: {cfg.n_experts} of {cfg.moe_router_width} held "
        f"from {cfg.moe_first_expert}, {cfg.n_active_experts} a "
        f"token of {cfg.moe_topk_group or 1} of "
        f"{cfg.moe_n_group or 1} groups"),
    refusal=Refusal(
        what=("a decoder with latent attention and an expert share (one "
              "pool of compressed rows a sequence; the layer scan has no "
              "mesh plan yet, the share's exchange between chips is not "
              "built)"),
        carries="the latent pool",
        spec_lookup=("the latent walk takes one token a row; a verify's "
                     "lanes would each need a bound of their own"),
        kv_host_blocks=("the host tier's transfer programs, and with them "
                        "kvwire export/ingest and mid-stream resume, frame "
                        "a block as K and V planes; a latent pool's block "
                        "is one plane of compressed rows")))
