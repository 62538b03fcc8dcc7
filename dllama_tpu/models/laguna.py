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
over those alone; the chunk form runs every held expert that some row chose
over every row and weights the others 0 (``models/share.py``). What the absent
experts would have added is left out, and that partial sum goes on to the
next layer: on one chip the layer runs without its exchange. With every
expert held the same code is the whole layer. The attention share (fewer
heads) and the vocabulary share (fewer rows) are only smaller numbers in
the header.

**The stack** is scanned once over PERIODS: a full layer, then ``P - 1``
sliding ones (a ``fori_loop`` over one traced body); where the header puts
the full layer elsewhere in its period (``cfg.full_layer_at``:
models/mellum.py closes its period with it) the sliding layers in front of
it are a second ``fori_loop`` over the same body. A gate, a q/k norm, a
dense layer and a shared expert each exist where their stack is not None. Two attention stacks
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
is one column (:class:`LagunaColumn`): the full layers dense at the slot's
length, the sliding layers a buffer of the window and the widest chunk that
slides with the chunks. A matched prefix brings its window with it
(runtime/kvblocks.py, "Window layers"): the generator gathers the match's
last window into the buffer, and a commit writes the buffer's newest rows to
the slot's own window blocks.

**Three programs, three pairs of closures over ONE walk**
(:func:`_scan_periods`): :func:`forward` (a prefill chunk over an admission's
column, :func:`_column_attends`), :func:`paged_forward` (the decode step, one
token a row, over the two pools in place, :func:`_pool_attends`) and
:func:`forward_and_step` (``FAMILY.tick``, PR 57: a chunk AND the tick's
decode rows through one pass over every plane, the other two programs'
closures side by side; the paged server's program for every plain chunk; no
chunk logits). The walk does everything a layer does a row at a time; the two
closures are all that knows whose rows they are.

**Counters** ride the same carry: ``stats`` = (pairs computed here, pairs
that fell on absent experts, tokens each held expert saw), summed over the
layers, accumulated on the device and given back with the pools. A step adds
its dispatches' to row 0 of the running totals (``share.zero_totals``), a
chunk's ride ``col.stats`` to the admission's commit, which adds them to row
1; the tick program's one joined dispatch is a chunk-form one and adds ALL of
its counters to row 1 itself (``models/lfm2.py`` says why).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm, rms_norm_per_head
from ..parallel.api import current_plan
from ..runtime.kvcache import update_layer
from .config import ModelConfig
from .family import Family, Refusal, layer_kinds
from .llama import (Params, _at, _attend_dense, _attend_paged, _by_row,
                    _exact_f32_dots, _join, _join_positions, _join_tokens,
                    _live_rows, _pick_rows, _put, _stack_at)
from .rope import apply_rope_partial, build_partial_rope_cache
from .share import (ffn_half, require_quantized, route,  # noqa: F401
                    routed_ffn, routed_pairs, widen_experts, zero_stats,
                    zero_totals)

_HIGHEST = jax.lax.Precision.HIGHEST


class AttnParams(NamedTuple):
    """One KIND of attention layer (full, or sliding), stacked over its
    layers."""

    wq: Weight            # [N, heads * hd, dim]
    wk: Weight            # [N, kv_dim, dim]
    wv: Weight
    wo: Weight            # [N, dim, heads * hd]
    # [N, heads, dim] float32: the per-head gate's rows (None: no gate,
    # models/mellum.py)
    wg: jax.Array | None
    norm_att: jax.Array   # [N, dim]
    # [N, hd]: the per-head RMS norm on q and on k in front of the rotary
    # embedding (``cfg.uses_qk_norm``, models/mellum.py; None: none)
    norm_q: jax.Array | None = None
    norm_k: jax.Array | None = None


_ATTN_MATMULS = ("wq", "wk", "wv", "wo")


class LagunaLayers(NamedTuple):
    """``Params.layers``: the two attention stacks, the leading dense
    layers' feed-forward, the routed layers' (stacked over the
    ``n_moe_layers`` that have one: layer ``l`` is entry ``l -
    n_dense_layers``)."""

    full: AttnParams
    slide: AttnParams
    norm_ffn: jax.Array    # [L, dim]
    w1: Weight | None      # [n_dense, dense_hidden, dim]; None: no dense layer
    w2: Weight | None
    w3: Weight | None
    moe_gate: jax.Array    # [NM, router_width, dim] float32
    we1: Weight            # [NM, held, dim, hidden]   (in-major, as LayerParams')
    we2: Weight            # [NM, held, hidden, dim]
    we3: Weight
    ws1: Weight | None     # [NM, shared, dim]: the shared expert
    ws2: Weight | None
    ws3: Weight | None


class LagunaColumn(NamedTuple):
    """One slot's context during chunked prefill, and the chunks' routing
    counters. The FULL layers' K/V is dense at the slot's length, by
    position (the slot's gathered view, matched prefix blocks included). A
    SLIDING layer never reads more than ``window - 1`` rows behind a chunk,
    so its K/V is a buffer of ``cfg.window_column_rows`` rows (the window and
    the widest chunk) that holds positions ``[base, base + rows)`` and slides
    with the chunks (:func:`_slide_column`). Behind a matched prefix the
    generator gathers the match's last window into it and sets ``base``
    (runtime/serving.py); a commit writes its newest rows to the window
    pool's blocks."""

    k: jax.Array       # [n_full, 1, n_kv, S, hd]
    v: jax.Array
    wk: jax.Array      # [n_sliding, 1, n_kv, rows, hd]
    wv: jax.Array
    base: jax.Array    # int32 scalar: the position of the buffer's row 0
    stats: jax.Array   # [share.N_COUNTS + held] int32

    @classmethod
    def behind(cls, cfg: ModelConfig, k: jax.Array,
               v: jax.Array) -> "LagunaColumn":
        """A sequence's start over the full layers' gathered view: an empty
        buffer at position 0."""
        rows = min(k.shape[3], cfg.window_column_rows or k.shape[3])
        shape = (cfg.n_window_layers, 1, cfg.n_kv_heads, rows, cfg.head_dim)
        return cls(k=k, v=v, wk=jnp.zeros(shape, k.dtype),
                   wv=jnp.zeros(shape, k.dtype), base=jnp.int32(0),
                   stats=zero_stats(cfg))

    @classmethod
    def zeros(cls, cfg: ModelConfig, dtype) -> "LagunaColumn":
        from ..runtime.kvcache import padded_cache_len

        shape = (cfg.n_kv_layers, 1, cfg.n_kv_heads,
                 padded_cache_len(cfg.seq_len), cfg.head_dim)
        return cls.behind(cfg, jnp.zeros(shape, dtype),
                          jnp.zeros(shape, dtype))


def _slide_column(col: LagunaColumn, start_pos, T: int) -> LagunaColumn:
    """The sliding layers' buffer moved up so that a chunk of ``T`` rows at
    ``start_pos`` ends inside it: rows roll towards 0 by what ``base`` grows
    (the rows rolled in behind the chunk are garbage at positions no query
    of the chunk can see yet). ``rows >= window - 1 + T``, so every key the
    chunk's windows reach stays."""
    rows = col.wk.shape[3]
    base = jnp.maximum(col.base, start_pos + T - rows)
    roll = lambda a: jnp.roll(a, col.base - base, axis=3)
    return col._replace(wk=roll(col.wk), wv=roll(col.wv), base=base)


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
    gate = None
    if ap.wg is not None:
        gate = jax.nn.sigmoid(jnp.einsum(
            "btd,hd->bth", h.astype(jnp.float32), ap.wg.astype(jnp.float32),
            precision=_HIGHEST))
    if ap.norm_q is not None:
        q = rms_norm_per_head(q, ap.norm_q, cfg.norm_epsilon)
        k = rms_norm_per_head(k, ap.norm_k, cfg.norm_epsilon)
    q = apply_rope_partial(q, *table, positions)
    k = apply_rope_partial(k, *table, positions)
    att = attend(q, k, v)
    if gate is not None:
        att = att.astype(jnp.float32) * gate[..., None]
    return x + linear(att.astype(x.dtype).reshape(B, T, heads * cfg.head_dim),
                      ap.wo)


def _attend_window_buffer(cfg: ModelConfig, q, k, v, k_l, v_l, start_pos,
                          positions, base):
    """A sliding layer over its buffer ``k_l, v_l [B, n_kv, rows, hd]`` of
    positions ``[base, base + rows)``: the chunk's rows written where
    ``start_pos`` falls in it, then attention over the buffer (the window and
    the widest chunk), not over the slot's length."""
    k_l, v_l = update_layer(k_l, v_l, k, v, start_pos - base)
    att = attention(q, k_l, v_l, positions, cfg.head_dim,
                    window=cfg.sliding_window, key_start=base)
    return att, k_l, v_l


# -- the three programs ------------------------------------------------------


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    return linear(x, params.logits, out_axis="vocab").astype(jnp.float32)


def _scan_periods(params: Params, cfg: ModelConfig, x, caches, stats, live,
                  positions, attend_full, attend_slide):
    """The period scan the three programs share: the hidden rows ``[B, T,
    dim]`` behind the last layer, in front of the final norm (:func:`_head`).
    ``caches`` (a column's arrays, the two pools', or (the tick program) a
    pair of both) and ``stats`` ride the carry whole.
    ``attend_full(q, k, v, caches, p) -> (att, caches)`` is period ``p``'s
    full layer, ``attend_slide(q, k, v, caches, p, j)`` its ``j``-th sliding
    one. Everything else a layer does (the norms, the per-head gate, the
    partial rotary by ``positions``, the dense planes, the routed
    feed-forward over the rows that are ``live``) it does a row at a time, so
    the rows along ``T`` need not be one sequence's: only the two closures
    know."""
    P, A = cfg.layer_period, cfg.full_layer_at
    lp: LagunaLayers = params.layers
    t_full, t_slide = rope_tables(cfg)

    def layer(x, caches, stats, ap, heads, table, attend, l, first):
        box = {}

        def att(q, k, v):
            out, box["caches"] = attend(q, k, v, caches)
            return out

        x = _attention_half(cfg, x, ap, heads, table, positions, att)
        x, s = ffn_half(cfg, x, lp, l, live, may_be_dense=first)
        return x, box["caches"], stats + s

    # the model's layer of a period's full layer and of its ``j``-th sliding
    # one, in front of the full layer or behind it (Python's choice, made
    # once: the traced sums are the ones a period that OPENS with its full
    # layer always had)
    if A:
        full_layer = lambda p: p * P + A
    else:
        full_layer = lambda p: p * P
    in_front = lambda p, j: p * P + j
    behind = lambda p, j: p * P + 1 + j

    def slides(carry, p, lo, hi, layer_of):
        # the period's sliding layers ``lo .. hi - 1`` (of its ``P - 1``)
        def sliding(j, carry):
            x, caches, stats = carry
            return layer(
                x, caches, stats,
                _stack_at(lp.slide, p * (P - 1) + j, _ATTN_MATMULS),
                cfg.n_heads_sliding, t_slide,
                lambda q, k, v, c: attend_slide(q, k, v, c, p, j),
                layer_of(p, j), False)

        return jax.lax.fori_loop(lo, hi, sliding, carry)

    any_in_front, any_behind = A > 0, A < P - 1

    def period(carry, p):
        if any_in_front:
            carry = slides(carry, p, 0, A, in_front)
        x, caches, stats = carry
        carry = layer(
            x, caches, stats, _stack_at(lp.full, p, _ATTN_MATMULS),
            cfg.n_heads, t_full,
            lambda q, k, v, c: attend_full(q, k, v, c, p),
            full_layer(p), A == 0)
        if any_behind:
            carry = slides(carry, p, A, P - 1, behind)
        return carry, None

    (x, caches, stats), _ = jax.lax.scan(
        period, (x, caches, stats),
        jnp.arange(cfg.n_periods, dtype=jnp.int32))
    return x, caches, stats


def _column_attends(cfg: ModelConfig, start_pos, positions, base):
    """A chunk's two closures: its rows at ``start_pos`` over a column's
    ``(k, v, wk, wv)``, a full layer over all of its layer's keys ``[n_full,
    1, n_kv, S, hd]``, a sliding one over its buffer ``[n_sliding, 1, n_kv,
    rows, hd]`` of positions from ``base`` (:func:`_slide_column` has moved
    it under the chunk)."""
    P = cfg.layer_period

    def attend_full(q, k, v, kv, p):
        ck, cv, wk, wv = kv
        att, k_l, v_l = _attend_dense(cfg, q, k, v, _at(ck, p), _at(cv, p),
                                      start_pos, positions)
        return att, (_put(ck, k_l, p), _put(cv, v_l, p), wk, wv)

    def attend_slide(q, k, v, kv, p, j):
        ck, cv, wk, wv = kv
        l = p * (P - 1) + j
        att, k_l, v_l = _attend_window_buffer(
            cfg, q, k, v, _at(wk, l), _at(wv, l), start_pos, positions, base)
        return att, (ck, cv, _put(wk, k_l, l), _put(wv, v_l, l))

    return attend_full, attend_slide


def _pool_attends(cfg: ModelConfig, positions, t_full, t_win):
    """The decode rows' two closures: one token a row at ``positions [B,
    1]``, written in place into ``(full k, full v, window k, window v)``
    through the rows' full-pool and window-pool tables ``[B, M]``, a sliding
    layer's walk behind its window."""
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

    return attend_full, attend_slide


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
    col = _slide_column(col, start_pos, T)
    x, (k, v, wk, wv), stats = _scan_periods(
        params, cfg, x, (col.k, col.v, col.wk, col.wv), col.stats, live,
        positions, *_column_attends(cfg, start_pos, positions, col.base))
    return _head(params, cfg, x), col._replace(k=k, v=v, wk=wk, wv=wv,
                                               stats=stats)


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
    live = _live_rows(t_full)
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    x, (fk, fv, wk, wv), stats = _scan_periods(
        params, cfg, x, (pkv.k, pkv.v, wkv.k, wkv.v), zero_stats(cfg), live,
        positions, *_pool_attends(cfg, positions, t_full, t_win))
    return _head(params, cfg, x), (PagedKVCache(k=fk, v=fv),
                                   PagedKVCache(k=wk, v=wv),
                                   totals.at[0].add(stats))


@_exact_f32_dots
def forward_and_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     pos_vec: jax.Array, cache, tables: jax.Array,
                     chunk: jax.Array, chunk_pos: jax.Array,
                     n_valid: jax.Array, poison: jax.Array):
    """A tick that carries a prefill chunk, as ONE program
    (``Family.tick``; ``lfm2.forward_and_step``'s signature):
    :func:`forward` over ``chunk [1, T]`` at ``chunk_pos`` into an
    admission's column AND :func:`paged_forward`'s layers over the tick's
    decode rows (``tokens [R, 1]`` at ``pos_vec`` through ``tables [2, R,
    M]``), so that every plane of every layer, a routed expert's among them,
    is read once for both. ``cache`` is ``(column, (full PagedKVCache, window
    PagedKVCache, totals))``, all given back (and donated where the server
    jits this).

    ONE call of :func:`_scan_periods` over the joined rows ``[1, T + R]``,
    its carry the column's ``k, v`` AND the four pool arrays whole. Its two
    closures are the other two programs' own, side by side: the chunk's
    ``T`` rows through :func:`_column_attends`, the ``R`` decode rows through
    :func:`_pool_attends` (a full layer into the full pool through
    ``tables[0]``, a sliding one into the window pool through ``tables[1]``
    behind its window). A row whose full table is all null is dead, as an
    inactive slot of a step is (null blocks in BOTH tables, not routed), and
    every row may be; the chunk's padding is not routed either.

    **The routed half is ONE dispatch of the chunk form** over the joined
    rows (``T + R`` is past ``share.step_form``): a plane fetched once a RUN
    over the union of what the chunk and the rows chose. Its counters are
    therefore a chunk-form dispatch's, ALL of them (the chunk's pairs and the
    decode rows', rows fed, planes, tokens an expert), and the program adds
    them itself to the totals' CHUNK row (row 1), not through ``col.stats``
    and the commit: each pair is counted once (``models/lfm2.py`` says why).
    ``col.stats`` goes back as it came; row 0 is "what the step PROGRAM's
    dispatches did" and is left as it was.

    Behind the scan the decode ROWS alone get a head, the poison, the argmax
    and the non-finite count (:func:`~dllama_tpu.models.llama._pick_rows`).
    Returns ``((token, nonfinite, logits),
    (column, (pkv, wkv, totals)))``, as the dense tick does."""
    from ..runtime.kvblocks import PagedKVCache

    _check(cfg)
    col, (pkv, wkv, totals) = cache
    chunk_pos = jnp.asarray(chunk_pos, dtype=jnp.int32)
    n_valid = jnp.asarray(n_valid, dtype=jnp.int32)
    T = chunk.shape[1]
    joined = _join_tokens(chunk, tokens)[None]                      # [1, T+R]
    cpos, rpos, positions = _join_positions(chunk_pos, pos_vec, T)
    t_full, t_win = tables[0], tables[1]
    live = jnp.concatenate([jnp.arange(T) < n_valid, _live_rows(t_full)])
    x = params.embedding[joined].astype(cfg.compute_dtype)

    def side_by_side(of_chunk, of_rows):
        def attend(q, k, v, caches, *where):
            att_c, kv = of_chunk(q[:, :T], k[:, :T], v[:, :T], caches[0],
                                 *where)
            att_r, pools = of_rows(_by_row(q, T), _by_row(k, T),
                                   _by_row(v, T), caches[1], *where)
            return _join(att_c, att_r), (kv, pools)
        return attend

    col = _slide_column(col, chunk_pos, T)
    x, ((k, v, ck, cv), (fk, fv, wk, wv)), stats = _scan_periods(
        params, cfg, x, ((col.k, col.v, col.wk, col.wv),
                         (pkv.k, pkv.v, wkv.k, wkv.v)),
        zero_stats(cfg), live, positions,
        *map(side_by_side, _column_attends(cfg, chunk_pos, cpos, col.base),
             _pool_attends(cfg, rpos, t_full, t_win)))
    return (_pick_rows(_head, params, cfg, x, T, poison),
            (col._replace(k=k, v=v, wk=ck, wv=cv),
             (PagedKVCache(k=fk, v=fv), PagedKVCache(k=wk, v=wv),
              totals.at[1].add(stats))))


def _load_params(ld, cfg: ModelConfig) -> Params:
    """From the tensors ``mfile._walk_laguna_layer`` names: two attention
    stacks by layer kind, the leading dense layers' feed-forward, and the
    routed layers' router, HELD experts and shared expert, each stacked over
    its own layers of the model."""
    require_quantized(ld)
    h = ld.h
    P, hd = h.layer_period, h.head_dim
    every = list(range(h.n_layers))
    full_ids = [l for l in every if l % P == h.full_layer_at]
    slide_ids = [l for l in every if l % P != h.full_layer_at]
    dense_ids, moe_ids = every[:h.n_dense_layers], every[h.n_dense_layers:]
    mm = lambda ids, name, o, i: ld.matmul(
        name, o, i, stacked=True, out_axis=None, in_axis=None, layers=ids)

    def attn(ids, heads):
        return AttnParams(
            wq=mm(ids, "block_matmul_q", heads * hd, h.dim),
            wk=mm(ids, "block_matmul_k", h.kv_dim, h.dim),
            wv=mm(ids, "block_matmul_v", h.kv_dim, h.dim),
            wo=mm(ids, "block_matmul_wo", h.dim, heads * hd),
            wg=(ld.stacked_f32("block_attn_gate", heads, h.dim, layers=ids)
                if cfg.has_attention_gate else None),
            norm_att=ld.stacked_f32("block_norm_0", h.dim, layers=ids),
            **({name: ld.stacked_f32("block_" + name, hd, layers=ids)
                for name in ("norm_q", "norm_k")}
               if cfg.uses_qk_norm else {}))

    wide, sh = h.dense_hidden_dim, h.shared_expert_dim
    # (an expert's planes are HELD ``cfg.expert_width_held`` wide)
    experts = lambda name, o, i, axis: widen_experts(
        ld.expert_stack(name, o, i, None, None, layers=moe_ids), axis,
        h.hidden_dim, cfg.expert_width_held)
    return ld.params(LagunaLayers(
        full=attn(full_ids, h.n_heads),
        slide=attn(slide_ids, h.n_heads_sliding),
        norm_ffn=ld.stacked_f32("block_norm_1", h.dim),
        w1=mm(dense_ids, "block_matmul_w1", wide, h.dim) if dense_ids else None,
        w2=mm(dense_ids, "block_matmul_w2", h.dim, wide) if dense_ids else None,
        w3=mm(dense_ids, "block_matmul_w3", wide, h.dim) if dense_ids else None,
        moe_gate=ld.stacked_f32("block_moe_gate", h.moe_router_width, h.dim,
                                layers=moe_ids),
        we1=experts("block_expert_w1", h.hidden_dim, h.dim, -1),
        we2=experts("block_expert_w2", h.dim, h.hidden_dim, -2),
        we3=experts("block_expert_w3", h.hidden_dim, h.dim, -1),
        ws1=mm(moe_ids, "block_shared_w1", sh, h.dim) if sh else None,
        ws2=mm(moe_ids, "block_shared_w2", h.dim, sh) if sh else None,
        ws3=mm(moe_ids, "block_shared_w3", sh, h.dim) if sh else None))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # what is HELD: two kinds of attention layer at their own head
    # counts, the leading dense feed-forward, the held experts of a
    # routed layer with its router (over its whole width) and shared
    # expert, the vocabulary's rows
    attn = lambda heads: 2 * cfg.dim * (heads * cfg.head_dim + cfg.kv_dim)
    routed = (cfg.dim * cfg.moe_router_width
              + 3 * cfg.dim * (cfg.hidden_dim * cfg.n_experts
                               + cfg.shared_expert_dim))
    return (cfg.n_kv_layers * attn(cfg.n_heads)
            + cfg.n_window_layers * attn(cfg.n_heads_sliding)
            + cfg.n_dense_layers * 3 * cfg.dim * cfg.dense_hidden_dim
            + cfg.n_moe_layers * routed + cfg.dim * cfg.vocab_size)


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=forward_and_step,
    # the full layers' gathered view, matched prefix blocks included, and
    # an empty buffer for the sliding layers: the generator gathers a
    # match's last window into it
    column=LagunaColumn.behind,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(full=cfg.n_kv_layers,
                                        sliding=cfg.n_window_layers),
    describe=lambda cfg, engine: (
        f"; layers: {cfg.n_kv_layers} full, {cfg.n_window_layers} "
        f"sliding (window {cfg.sliding_window}); experts: "
        f"{cfg.n_experts} of {cfg.moe_router_width} held from "
        f"{cfg.moe_first_expert}, {cfg.n_active_experts} a token"),
    refusal=Refusal(
        what=("a decoder with window layers and an expert share (two block "
              "pools a sequence; the period scan has no mesh plan yet, the "
              "share's exchange between chips is not built)"),
        carries="the two block pools",
        spec_lookup=("a sliding window's walk takes one token a row; a "
                     "verify's lanes would each need a window of their own"),
        kv_host_blocks=("the host tier keeps one list of blocks by token "
                        "range; the window pool's blocks behind the window "
                        "are gone, and with them kvwire export/ingest and "
                        "mid-stream resume")))
