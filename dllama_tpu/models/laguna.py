"""A decoder of window and full attention layers with routed experts, of
which this chip may hold a SHARE (``ArchType.LAGUNA``; Laguna-S-2.1 is one
full layer to three sliding ones, 256 routed experts and a shared one).

**The equations.** Layer ``l``, input ``x``, ``H_l`` query heads (``n_heads``
on a full layer, ``n_heads_sliding`` on a sliding one), ``G_l = H_l /
n_kv_heads``:

* ``h = rmsnorm(x; w_a)``; ``q = Wq h`` (``H_l`` heads), ``k = Wk h``, ``v = Wv
  h``; ``g = sigmoid(Wg h)``, one number a query head, in float32.
* rotary, half-split pairing, on the first ``r`` lanes of every q and k
  head: a sliding layer the whole head at ``rope_theta_sliding``, a full
  layer ``rope_dim`` lanes of YaRN's table (models/rope.py).
* causal softmax attention, query head ``j`` on K/V head ``j // G_l``; in a
  sliding layer the query at position ``i`` sees keys ``i - window + 1 .. i``.
  ``o_j <- g_j o_j``; ``x <- x + Wo concat_j(o_j)``.
* ``h2 = rmsnorm(x; w_f)``. A leading dense layer: ``x <- x + W2 (silu(W1 h2)
  * W3 h2)``. Every other: ``p = softmax(Wr h2)`` over the router's WHOLE
  width in float32, ``T`` = the ``n_active_experts`` largest, ``w_e = p_e /
  sum_T p``, ``x <- x + scale sum_{e in T, e held} w_e E_e(h2) + S(h2)``.

**The share is data.** The router always scores ``moe_router_width`` experts
and takes ``n_active_experts``; the planes hold ``n_experts`` of them, from
``moe_first_expert``. A (row, expert) pair whose expert is not held, or whose
row is dead or padding, is not computed: the decode form compacts the held
pairs to the front and :func:`~dllama_tpu.ops.expert_gemv.expert_gemv` loops
over those alone; the chunk form sorts them by expert in front of the absent
ones, which fall outside every group of ``lax.ragged_dot``. What the absent
experts would have added is left out, and that partial sum goes on to the
next layer: on one chip the layer runs without its exchange. With every
expert held the same code is the whole layer. The attention share (fewer
heads) and the vocabulary share (fewer rows) are only smaller numbers in
the header.

**The stack** is scanned once over PERIODS: a full layer, then ``P - 1``
sliding ones (a ``fori_loop`` over one traced body). Two attention stacks
(:class:`AttnParams` over the full and over the sliding layers), the routed
feed-forward's stacks over the layers that have one, the leading dense
layer's planes: every Q40 plane stays whole and reaches ``linear`` as stack +
index, every expert stack reaches ``expert_gemv`` as stack + layer + expert.
The leading dense layer is a ``lax.cond`` on the layer index inside the one
traced full-layer body, not a Python loop over the depth.

A slot's context is blocks of TWO pools (runtime/kvblocks.py): the full
layers' ``[n_full, n_blocks, n_kv, bs, hd]`` through the slot's block table,
and the sliding layers' ``[n_sliding, n_window_blocks, ...]`` through a
second table whose entries behind the window are null (their blocks went back
to the free list). Both ride the period scan's carry whole and are written
in place (PERF.md section 6, PR 33). During chunked prefill a slot's context
is ONE dense column over all layers (:class:`LagunaColumn`).

**Counters** ride the same carry: ``stats`` = (pairs computed here, pairs
that fell on absent experts, tokens each held expert saw), summed over the
layers, accumulated on the device and given back with the pools.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import expert_gemv as eg
from ..ops.attention import attention
from ..ops.linear import (LayerSlice, QuantizedWeight, Weight, _fast_mode,
                          linear)
from ..ops.norms import rms_norm
from ..parallel.api import current_plan
from ..runtime.kvcache import update_layer
from .config import ModelConfig
from .llama import (Params, _attend_dense, _attend_paged, _experts_dense,
                    _hidden_act, _stack_at)
from .rope import apply_rope_partial, build_partial_rope_cache

_HIGHEST = jax.lax.Precision.HIGHEST


class AttnParams(NamedTuple):
    """One KIND of attention layer (full, or sliding), stacked over its
    layers."""

    wq: Weight            # [N, heads * hd, dim]
    wk: Weight            # [N, kv_dim, dim]
    wv: Weight
    wo: Weight            # [N, dim, heads * hd]
    wg: jax.Array         # [N, heads, dim] float32: the per-head gate's rows
    norm_att: jax.Array   # [N, dim]


_ATTN_MATMULS = ("wq", "wk", "wv", "wo")


class LagunaLayers(NamedTuple):
    """``Params.layers``: the two attention stacks, the leading dense
    layers' feed-forward, the routed layers' (stacked over the
    ``n_moe_layers`` that have one: layer ``l`` is entry ``l -
    n_dense_layers``)."""

    full: AttnParams
    slide: AttnParams
    norm_ffn: jax.Array    # [L, dim]
    w1: Weight             # [n_dense, dense_hidden, dim]
    w2: Weight
    w3: Weight
    moe_gate: jax.Array    # [NM, router_width, dim] float32
    we1: Weight            # [NM, held, dim, hidden]   (in-major, as LayerParams')
    we2: Weight            # [NM, held, hidden, dim]
    we3: Weight
    ws1: Weight | None     # [NM, shared, dim]: the shared expert
    ws2: Weight | None
    ws3: Weight | None


def _plane(w: Weight, l) -> Weight:
    """Entry ``l`` of a stacked 2-D matmul weight: stack + index for a Q40
    plane (the fused kernel reads it where it lies, llama._layer_at), the
    slice otherwise."""
    if isinstance(w, QuantizedWeight):
        return LayerSlice(w, l)
    return jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False)


class LagunaColumn(NamedTuple):
    """One slot's context during chunked prefill: a dense K/V column over
    ALL layers in the model's order, and the chunks' routing counters."""

    k: jax.Array       # [L, 1, n_kv, S, hd]
    v: jax.Array
    stats: jax.Array   # [2 + held] int32

    @classmethod
    def zeros(cls, cfg: ModelConfig, dtype) -> "LagunaColumn":
        from ..runtime.kvcache import padded_cache_len

        shape = (cfg.n_layers, 1, cfg.n_kv_heads,
                 padded_cache_len(cfg.seq_len), cfg.head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   stats=zero_stats(cfg))


def zero_stats(cfg: ModelConfig) -> jax.Array:
    """One dispatch's routing counters: held pairs, absent pairs, tokens a
    held expert."""
    return jnp.zeros((2 + cfg.n_experts,), jnp.int32)


def zero_totals(cfg: ModelConfig) -> jax.Array:
    """The generator's running totals beside its pools: row 0 what the
    decode steps added, row 1 what the prefill chunks did (kept apart so
    that a step's own pairs can be read off after it)."""
    return jnp.zeros((2, 2 + cfg.n_experts), jnp.int32)


def rope_tables(cfg: ModelConfig):
    """``((cos, sin) of the full layers, (cos, sin) of the sliding ones)``."""
    full = build_partial_rope_cache(
        cfg.seq_len, cfg.rope_dim, float(cfg.rope_theta),
        (float(cfg.rope_scaling_factor), int(cfg.rope_scaling_orig_max_seq_len),
         float(cfg.rope_scaling_high_freq_factor),
         float(cfg.rope_scaling_low_freq_factor)))
    slide = build_partial_rope_cache(cfg.seq_len, cfg.head_dim,
                                     float(cfg.rope_theta_sliding))
    return full, slide


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a decoder with window layers and an expert share "
                         "has no mesh plan (tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a decoder with window layers supports neither Q80 "
                         "sync emulation nor offloaded weights")


# -- attention ---------------------------------------------------------------


def _attention_half(cfg: ModelConfig, x: jax.Array, ap: AttnParams,
                    heads: int, table, positions: jax.Array, attend):
    """A layer's attention half, residual added; ``attend(q, k, v) -> att``
    owns the cache."""
    B, T, _ = x.shape
    h = rms_norm(x, ap.norm_att, cfg.norm_epsilon)
    q = linear(h, ap.wq).reshape(B, T, heads, cfg.head_dim)
    k = linear(h, ap.wk).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = linear(h, ap.wv).reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    gate = jax.nn.sigmoid(jnp.einsum(
        "btd,hd->bth", h.astype(jnp.float32), ap.wg.astype(jnp.float32),
        precision=_HIGHEST))
    q = apply_rope_partial(q, *table, positions)
    k = apply_rope_partial(k, *table, positions)
    att = attend(q, k, v).astype(jnp.float32) * gate[..., None]
    return x + linear(att.astype(x.dtype).reshape(B, T, heads * cfg.head_dim),
                      ap.wo)


def _attend_window_dense(cfg: ModelConfig, q, k, v, k_l, v_l, start_pos,
                         positions):
    """A sliding layer over a dense column ``k_l, v_l [B, n_kv, S, hd]``:
    the chunk's rows written at ``start_pos``, then attention over the span
    the chunk's windows reach, cut out of the column (``window - 1 + T``
    keys, rounded up), not over all ``S``."""
    T, S = q.shape[1], k_l.shape[2]
    k_l, v_l = update_layer(k_l, v_l, k, v, start_pos)
    span = min(S, -(-(cfg.sliding_window + T) // 128) * 128)
    first = jnp.clip(start_pos + T - span, 0, S - span)
    cut = lambda a: jax.lax.dynamic_slice_in_dim(a, first, span, axis=2)
    att = attention(q, cut(k_l), cut(v_l), positions, cfg.head_dim,
                    window=cfg.sliding_window, key_start=first)
    return att, k_l, v_l


# -- the feed-forward --------------------------------------------------------


def _swiglu(cfg: ModelConfig, h: jax.Array, w1, w2, w3) -> jax.Array:
    gate = _hidden_act(cfg, linear(h, w1))
    return linear(gate * linear(h, w3), w2)


def route(cfg: ModelConfig, h: jax.Array, gate: jax.Array):
    """The router over its whole width, float32: ``(weights [N, k], experts
    [N, k])`` for ``h [N, dim]``, weights renormalised over the chosen where
    ``moe_norm_topk`` and scaled by ``moe_routed_scale``."""
    logits = jnp.einsum("nd,ed->ne", h.astype(jnp.float32),
                        gate.astype(jnp.float32), precision=_HIGHEST)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                             cfg.n_active_experts)
    if cfg.moe_norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top * cfg.moe_routed_scale, idx


def routed_pairs(cfg: ModelConfig, idx: jax.Array, live: jax.Array):
    """Of the (row, expert) pairs ``idx [N, k]``, flattened row-major:
    ``local [N k]`` the expert's index among those held (``n_experts`` where
    it is absent or the row is not ``live [N]``), and ``stats`` (held
    pairs, absent pairs of live rows, tokens a held expert)."""
    E = cfg.n_experts
    local = idx - cfg.moe_first_expert
    here = (local >= 0) & (local < E)
    held = (here & live[:, None]).reshape(-1)
    local = jnp.where(held, local.reshape(-1), E)
    absent = jnp.sum(~here & live[:, None])
    tokens = jnp.bincount(local, length=E + 1)[:E]
    stats = jnp.concatenate([jnp.stack([jnp.sum(held), absent]),
                             tokens]).astype(jnp.int32)
    return local, stats


def _sorted_pairs(cfg: ModelConfig, local: jax.Array, weights: jax.Array):
    """The pairs ``local [N k]`` sorted by held expert, the absent ones
    last: ``(rows, experts, w, n_held)``, each pair's token row, its
    expert among those held (``n_experts`` behind the first ``n_held``) and
    its router weight (0 there)."""
    k = weights.shape[1]
    order = jnp.argsort(local, stable=True)
    experts = local[order]
    w = jnp.where(experts < cfg.n_experts, weights.reshape(-1)[order], 0.0)
    n_held = jnp.sum(local < cfg.n_experts).astype(jnp.int32)
    return order // k, experts, w, n_held


def _experts_step(cfg: ModelConfig, x: jax.Array, local, weights, m, lp):
    """The decode form: the held pairs compacted to the front, one GEMV a
    pair over the chosen expert's planes read in place (``expert_gemv``; its
    XLA gather form off a TPU), rows summed back per token."""
    rows, experts, w, n_held = _sorted_pairs(cfg, local, weights)
    experts = jnp.minimum(experts, cfg.n_experts - 1)
    P = rows.shape[0]
    fast = _fast_mode(x) or lp.we1.scales.dtype == jnp.bfloat16
    kw = eg.kernel_choice(P, lp.we1, fast)
    if kw is not None and eg.kernel_choice(P, lp.we2, fast) is not None:
        gemv = lambda a, stack: eg.expert_gemv(a, stack, m, experts, n_held,
                                               **kw)
    else:
        gemv = lambda a, stack: eg.expert_gemv_xla(a, stack, m, experts,
                                                   n_held, fast=fast)
    xp = x[rows]
    a = _hidden_act(cfg, gemv(xp, lp.we1)) * gemv(xp, lp.we3)
    y = gemv(a.astype(x.dtype), lp.we2) * w[:, None]
    return jnp.zeros(x.shape, jnp.float32).at[rows].add(y)


def _experts_chunk(cfg: ModelConfig, x: jax.Array, local, weights, m, lp):
    """The chunk form: pairs sorted by held expert, the absent ones behind
    every group, one ``lax.ragged_dot`` a projection over the held planes
    (dequantized here: the chunk regime is where that is cheapest)."""
    E = cfg.n_experts
    rows, experts, w, _n_held = _sorted_pairs(cfg, local, weights)
    sizes = jnp.bincount(local, length=E + 1)[:E].astype(jnp.int32)
    xs = x[rows]
    at = lambda we: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False), we)
    d1, d2, d3 = (_experts_dense(at(we), xs)
                  for we in (lp.we1, lp.we2, lp.we3))
    dot = lambda a, d: jax.lax.ragged_dot(
        a.astype(d.dtype), d, sizes, preferred_element_type=jnp.float32)
    a = _hidden_act(cfg, dot(xs, d1)) * dot(xs, d3)
    # rows past the groups are not computed; what ragged_dot leaves there
    # is not read
    y = jnp.where((experts < E)[:, None], dot(a, d2), 0.0) * w[:, None]
    return jnp.zeros(x.shape, jnp.float32).at[rows].add(y)


def routed_ffn(cfg: ModelConfig, h: jax.Array, lp: LagunaLayers, m,
               live: jax.Array):
    """``scale sum_{held} w_e E_e(h) + S(h)`` for ``h [B, T, dim]`` in routed
    layer ``m``, and the layer's ``stats``; ``live [B * T]`` marks the rows
    that are real (a dead slot's, a chunk's padding, are not routed)."""
    from ..ops.quant_matmul import FUSED_MAX_M

    B, T, D = h.shape
    x = h.reshape(B * T, D)
    at = lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False)
    weights, idx = route(cfg, x, at(lp.moe_gate))
    local, stats = routed_pairs(cfg, idx, live)
    form = _experts_step if B * T <= FUSED_MAX_M else _experts_chunk
    y = form(cfg, x, local, weights, m, lp)
    if lp.ws1 is not None:
        y = y + _swiglu(cfg, h, _plane(lp.ws1, m), _plane(lp.ws2, m),
                        _plane(lp.ws3, m)).reshape(B * T, D)
    return y.reshape(B, T, D).astype(h.dtype), stats


def _ffn_half(cfg: ModelConfig, x: jax.Array, lp: LagunaLayers, l, live,
              may_be_dense: bool):
    """A layer's feed-forward half, residual added, and its ``stats``. Only
    a period's first layer can be a leading dense one (``may_be_dense``,
    static): there the choice is a ``cond`` on the traced layer index."""
    h = rms_norm(x, jax.lax.dynamic_index_in_dim(lp.norm_ffn, l, 0, False),
                 cfg.norm_epsilon)
    m = jnp.maximum(l - cfg.n_dense_layers, 0)

    def routed(h):
        return routed_ffn(cfg, h, lp, m, live)

    def dense(h):
        d = jnp.minimum(l, cfg.n_dense_layers - 1)
        return (_swiglu(cfg, h, _plane(lp.w1, d), _plane(lp.w2, d),
                        _plane(lp.w3, d)), zero_stats(cfg))

    if may_be_dense and cfg.n_dense_layers:
        y, stats = jax.lax.cond(l < cfg.n_dense_layers, dense, routed, h)
    else:
        y, stats = routed(h)
    return x + y, stats


# -- the two programs --------------------------------------------------------


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    return linear(x, params.logits, out_axis="vocab").astype(jnp.float32)


def _scan_periods(params: Params, cfg: ModelConfig, x, caches, stats, live,
                  positions, attend_full, attend_slide):
    """The period scan both programs share. ``caches`` (a column's arrays, or
    the two pools') and ``stats`` ride the carry whole.
    ``attend_full(q, k, v, caches, p) -> (att, caches)`` is period ``p``'s
    full layer, ``attend_slide(q, k, v, caches, p, j)`` its ``j``-th sliding
    one."""
    P = cfg.layer_period
    lp: LagunaLayers = params.layers
    t_full, t_slide = rope_tables(cfg)

    def layer(x, caches, stats, ap, heads, table, attend, l, first):
        box = {}

        def att(q, k, v):
            out, box["caches"] = attend(q, k, v, caches)
            return out

        x = _attention_half(cfg, x, ap, heads, table, positions, att)
        x, s = _ffn_half(cfg, x, lp, l, live, may_be_dense=first)
        return x, box["caches"], stats + s

    def period(carry, p):
        x, caches, stats = carry
        x, caches, stats = layer(
            x, caches, stats, _stack_at(lp.full, p, _ATTN_MATMULS),
            cfg.n_heads, t_full,
            lambda q, k, v, c: attend_full(q, k, v, c, p), p * P, True)

        def sliding(j, carry):
            x, caches, stats = carry
            return layer(
                x, caches, stats,
                _stack_at(lp.slide, p * (P - 1) + j, _ATTN_MATMULS),
                cfg.n_heads_sliding, t_slide,
                lambda q, k, v, c: attend_slide(q, k, v, c, p, j),
                p * P + 1 + j, False)

        return jax.lax.fori_loop(0, P - 1, sliding, (x, caches, stats)), None

    (x, caches, stats), _ = jax.lax.scan(
        period, (x, caches, stats),
        jnp.arange(cfg.n_periods, dtype=jnp.int32))
    return _head(params, cfg, x), caches, stats


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: LagunaColumn,
            n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a dense column:
    float32 logits ``[B, T, vocab]`` and the column with the chunk's rows
    written. Positions at or past ``n_valid`` (absent: all ``T``) are padding:
    their K/V rows are overwritten later, and they are not routed."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    if start_pos.ndim:
        raise ValueError("the chunk form takes one start position (the "
                         "dense slot pool's ragged rows are not carried to "
                         "two block pools)")
    B, T = tokens.shape
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    live = jnp.tile(jnp.arange(T) < n_valid, B)
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    positions = jnp.broadcast_to(
        start_pos + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    P = cfg.layer_period

    def at(a, l):
        return jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)

    def put(a, a_l, l):
        return jax.lax.dynamic_update_index_in_dim(a, a_l, l, 0)

    def attend_full(q, k, v, kv, p):
        k_c, v_c = kv
        att, k_l, v_l = _attend_dense(cfg, q, k, v, at(k_c, p * P),
                                      at(v_c, p * P), start_pos, positions)
        return att, (put(k_c, k_l, p * P), put(v_c, v_l, p * P))

    def attend_slide(q, k, v, kv, p, j):
        k_c, v_c = kv
        l = p * P + 1 + j
        att, k_l, v_l = _attend_window_dense(cfg, q, k, v, at(k_c, l),
                                             at(v_c, l), start_pos, positions)
        return att, (put(k_c, k_l, l), put(v_c, v_l, l))

    logits, (k, v), stats = _scan_periods(
        params, cfg, x, (col.k, col.v), col.stats, live, positions,
        attend_full, attend_slide)
    return logits, LagunaColumn(k=k, v=v, stats=stats)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """The decode step over the two pools: ``tokens [B, 1]`` at per-row
    ``pos_vec``; ``cache = (full PagedKVCache, window PagedKVCache, totals)``,
    all given back (the pools written in place, the step's routing counters
    added to row 0 of ``totals``, :func:`zero_totals`); ``tables [2, B, M]``
    the rows' full-pool and
    window-pool block tables. A row is live where its full table starts with
    a real block."""
    from ..runtime.kvblocks import PagedKVCache

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("the step form takes one token a row: a sliding "
                         "window's walk carries no speculative verify")
    pkv, wkv, totals = cache
    t_full, t_win = tables[0], tables[1]
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    live = t_full[:, 0] != 0
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    P = cfg.layer_period

    def attend_full(q, k, v, pools, p):
        fk, fv, wk, wv = pools
        att, fk, fv = _attend_paged(cfg, q, k, v, fk, fv, p, positions,
                                    t_full)
        return att, (fk, fv, wk, wv)

    def attend_slide(q, k, v, pools, p, j):
        fk, fv, wk, wv = pools
        att, wk, wv = _attend_paged(cfg, q, k, v, wk, wv, p * (P - 1) + j,
                                    positions, t_win,
                                    window=cfg.sliding_window)
        return att, (fk, fv, wk, wv)

    logits, (fk, fv, wk, wv), stats = _scan_periods(
        params, cfg, x, (pkv.k, pkv.v, wkv.k, wkv.v), zero_stats(cfg), live,
        positions, attend_full, attend_slide)
    return logits, (PagedKVCache(k=fk, v=fv), PagedKVCache(k=wk, v=wv),
                    totals.at[0].add(stats))
