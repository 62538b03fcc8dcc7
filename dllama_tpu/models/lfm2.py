"""A decoder of gated short-convolution layers beside grouped-query
attention layers, routed experts behind leading dense layers
(``ArchType.LFM2``; LFM2-24B-A2B is two leading conv layers, then one
attention layer to three conv ones, 64 routed experts of which a token takes
4, no shared one).

**The equations.** Every layer is pre-norm: ``h = x + Op_l(rmsnorm(x; w_o))``,
``out = h + Ffn_l(rmsnorm(h; w_f))``; a final RMS norm, then the head.

* a CONV layer, input ``u``: ``[B | C | X] = W_in u`` (``dim -> 3 dim``, split
  in that order), ``v_t = B_t * X_t``, ``c_t = sum_j w_j v_{t-(K-1)+j}`` (a
  causal depthwise convolution of ``K = conv_kernel`` taps a channel, no
  bias, NO activation: :func:`~dllama_tpu.ops.causal_conv.causal_conv` with
  ``activation=None``), ``y_t = C_t * c_t``, ``Op = W_out y``. What a sequence
  carries is the convolution's TAIL, ``v_{t-K+1} .. v_{t-1}``: ``K - 1`` rows
  of ``dim`` a layer in the compute dtype, and nothing else.
* an ATTENTION layer: ``q, k, v = W_q u, W_k u, W_v u`` (``n_heads`` query and
  ``n_kv_heads`` K/V heads of ``head_dim`` lanes), an RMS norm over each
  head's lanes of ``q`` and of ``k``, rotary positions (half-split pairing
  over the whole head), causal softmax at ``head_dim ** -0.5``, ``W_o``.
* the first ``n_dense_layers`` layers (conv layers) carry a SwiGLU
  feed-forward ``dense_hidden_dim`` wide; every other layer the routed one
  of ``models/share.py``: a sigmoid router in float32 whose SELECTION adds a
  learned bias a layer (``moe_bias``) and whose weights are the chosen
  scores alone over ``(their sum + 1e-6)``.

**The stack** is the leading conv layers (a ``fori_loop`` over one traced
body with the dense feed-forward), then ONE scan over PERIODS: an attention
layer, then ``P - 1`` conv layers (a ``fori_loop`` over one traced body); a
last period that the depth cuts short is traced once behind the scan. Two
mixer stacks (:class:`ConvParams` over the conv layers, :class:`AttnParams`
over the attention layers), the feed-forwards' stacks as ``models/share.py``
names them. Every Q40 plane stays whole and reaches ``linear`` as stack +
index, every expert stack reaches the routed kernels as stack + layer.

**A slot's context** is three things, all in the scan's carry and written
in place: K/V rows of the attention layers only (a column ``[n_attn, 1, n_kv,
S, W]`` during prefill, blocks of the paged pool ``[n_attn, n_blocks, n_kv,
bs, W]`` afterwards), the conv layers' tails
(:class:`~dllama_tpu.runtime.kvblocks.StateColumn`'s ``conv`` during
prefill, a row of :class:`~dllama_tpu.runtime.kvblocks.StatePool`
afterwards: ``s`` is None, the tail is the whole state) and the routing
counters of ``models/share.py``. ``W = cfg.cache_width``: a head's lanes
padded with zeros to whole lane tiles of 128, so that the compiled paged
kernel and the flash kernel take the pool and the column as they take the
128-lane models'; the zero lanes add nothing to a score (its scale is
``head_dim``'s) and the padded lanes of the result are dropped.

**Three programs, three pairs of closures over ONE walk**
(:func:`_scan_layers`): :func:`forward` (a prefill chunk over a slot's
gathered column), :func:`paged_forward` (the decode step, one token a row,
over the pools in place) and :func:`forward_and_step` (``FAMILY.tick``, PR
53: a chunk AND the tick's decode rows through one pass over every plane, the
paged server's program for every plain chunk; no chunk logits). The walk does
everything a layer does a row at a time; ``conv_mixer`` and ``attend`` are the
two closures that know whose rows they are. The conv mixer is split where its
rows stop being independent (:func:`_conv_gate_in`, the convolution,
:func:`_conv_gate_out`), so the tick joins its rows in front of ``W_in`` and
behind the convolution.

**Where the counters go.** A step adds its dispatches' counters to row 0 of
the running totals (``share.zero_totals``), a chunk's ride ``col.stats`` to
the admission's commit, which adds them to row 1. The tick program's one
joined dispatch is a chunk-form one: it adds ALL of its counters (the chunk's
pairs and the decode rows') to row 1 itself and touches neither row 0 nor
``col.stats`` (see :func:`forward_and_step`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.causal_conv import causal_conv
from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm, rms_norm_per_head
from ..parallel.api import current_plan
from ..runtime.introspection import note_short_conv_path
from ..runtime.kvblocks import StateColumn
from .config import ModelConfig
from .family import Family, layer_kinds, state_refusal
from .llama import (Params, _at, _attend_dense, _attend_paged, _attend_split,
                    _by_row, _exact_f32_dots, _join, _join_positions,
                    _join_tokens, _live_rows, _pick_rows, _put, _stack_at,
                    _state_rows)
from .rope import apply_rope_partial, build_partial_rope_cache
from .share import _plane, ffn_half, require_quantized, swiglu, zero_stats


class ConvParams(NamedTuple):
    """The conv layers' mixers, stacked over the ``n_conv_layers`` in the
    model's order."""

    w_in: Weight          # [NC, 3 dim, dim]: the B, C and X rows, in that order
    conv_w: jax.Array     # [NC, K, dim] float32: tap K - 1 on the current position
    w_out: Weight         # [NC, dim, dim]
    norm_att: jax.Array   # [NC, dim]: the operator's norm


class AttnParams(NamedTuple):
    """The attention layers' mixers, stacked over the ``n_attn_layers``."""

    wq: Weight            # [NA, q_dim, dim]
    wk: Weight            # [NA, kv_dim, dim]
    wv: Weight
    wo: Weight            # [NA, dim, q_dim]
    norm_q: jax.Array     # [NA, head_dim]
    norm_k: jax.Array
    norm_att: jax.Array   # [NA, dim]


_CONV_MATMULS = ("w_in", "w_out")
_ATTN_MATMULS = ("wq", "wk", "wv", "wo")


class Lfm2Layers(NamedTuple):
    """``Params.layers``: the two mixer stacks, the leading layers' dense
    feed-forward, the routed layers' (stacked over the ``n_moe_layers`` that
    have one: layer ``l`` is entry ``l - n_dense_layers``), named as
    ``models/share.py`` reads them."""

    conv: ConvParams
    attn: AttnParams
    norm_ffn: jax.Array          # [L, dim]
    w1: Weight                   # [n_dense, dense_hidden, dim]
    w2: Weight
    w3: Weight
    moe_gate: jax.Array          # [NM, router_width, dim] float32
    moe_bias: jax.Array | None   # [NM, router_width] float32: the selection's
    we1: Weight                  # [NM, held, dim, hidden]
    we2: Weight                  # [NM, held, hidden, dim]
    we3: Weight
    ws1: None = None             # no shared expert (share.routed_ffn asks)
    ws2: None = None
    ws3: None = None


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a decoder with short-convolution layers and routed "
                         "experts has no mesh plan (tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a decoder with short-convolution layers supports "
                         "neither Q80 sync emulation nor offloaded weights")


def _conv_gate_in(cfg: ModelConfig, u: jax.Array, cp: ConvParams):
    """What a conv mixer does a row at a time IN FRONT of its convolution:
    ``proj = W_in u`` in float32 and the gated input ``v = B * X``, rounded
    once to the activation dtype, which is what the tail holds, so a chunk
    and a step see the same values."""
    d = cfg.dim
    proj = linear(u, cp.w_in).astype(jnp.float32)
    return proj, (proj[..., :d] * proj[..., 2 * d:]).astype(u.dtype)


def _conv_gate_out(cfg: ModelConfig, proj: jax.Array, c: jax.Array,
                   cp: ConvParams, dtype) -> jax.Array:
    """... and BEHIND it: ``W_out (C * c)`` for the convolution's float32
    output ``c``."""
    d = cfg.dim
    return linear((proj[..., d:2 * d] * c).astype(dtype), cp.w_out)


def _conv_mixer(cfg: ModelConfig, u: jax.Array, cp: ConvParams,
                tail: jax.Array, n_valid):
    """The gated short convolution over ``u [B, T, dim]`` (normed) behind
    ``tail [B, K - 1, dim]``: ``W_out (C * conv(B * X))`` and the new tail,
    the rows of ONE program's kind (a chunk's, or one token a row)."""
    proj, v = _conv_gate_in(cfg, u, cp)
    c, tail = causal_conv(v, tail, cp.conv_w, n_valid, activation=None)
    return _conv_gate_out(cfg, proj, c, cp, u.dtype), tail


def _pad_lanes(a: jax.Array, width: int) -> jax.Array:
    pad = width - a.shape[-1]
    return a if not pad else jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))


def _attn_mixer(cfg: ModelConfig, u: jax.Array, ap: AttnParams, table,
                positions: jax.Array, attend):
    """Grouped-query attention over ``u [B, T, dim]`` (normed);
    ``attend(q, k, v) -> att`` owns the cache and takes and gives heads of
    ``cfg.cache_width`` lanes."""
    B, T, _ = u.shape
    hd, W = cfg.head_dim, cfg.cache_width
    q = linear(u, ap.wq).reshape(B, T, cfg.n_heads, hd)
    k = linear(u, ap.wk).reshape(B, T, cfg.n_kv_heads, hd)
    v = linear(u, ap.wv).reshape(B, T, cfg.n_kv_heads, hd)
    q = rms_norm_per_head(q, ap.norm_q, cfg.norm_epsilon)
    k = rms_norm_per_head(k, ap.norm_k, cfg.norm_epsilon)
    q = apply_rope_partial(q, *table, positions)
    k = apply_rope_partial(k, *table, positions)
    att = attend(_pad_lanes(q, W), _pad_lanes(k, W), _pad_lanes(v, W))
    return linear(att[..., :hd].reshape(B, T, cfg.q_dim), ap.wo)


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    return linear(x, params.logits, out_axis="vocab").astype(jnp.float32)


def _scan_layers(params: Params, cfg: ModelConfig, x, caches, stats, live,
                 positions, conv_mixer, attend):
    """The walk the three programs share: the hidden rows ``[B, T, dim]``
    behind the last layer, in front of the final norm (:func:`_head`).
    ``caches = (k, v, conv)`` (a column's arrays, the two pools', or (the
    tick program) a pair of both each) and ``stats`` ride every loop's carry
    whole. ``conv_mixer(h, cp, c, conv) -> (y, conv')`` is conv layer ``c``'s
    mixer in the program's form, ``attend(q, k, v, k_c, v_c, a) -> (att,
    k_c, v_c)`` attention layer ``a``'s cache. Everything else a layer does
    (the norms, the dense planes, the routed feed-forward over the rows that
    are ``live``) it does a row at a time, so the rows along ``T`` need not
    be one sequence's: only the two closures know."""
    lp: Lfm2Layers = params.layers
    P, lead, L = cfg.layer_period, cfg.n_dense_layers, cfg.n_layers
    eps = cfg.norm_epsilon
    table = build_partial_rope_cache(cfg.seq_len, cfg.head_dim,
                                     float(cfg.rope_theta))

    def conv_half(x, conv, c):
        cp = _stack_at(lp.conv, c, _CONV_MATMULS)
        y, conv = conv_mixer(rms_norm(x, cp.norm_att, eps), cp, c, conv)
        return x + y, conv

    def leading(l, carry):
        x, (k_c, v_c, conv) = carry
        x, conv = conv_half(x, conv, l)
        h = rms_norm(x, _at(lp.norm_ffn, l), eps)
        x = x + swiglu(cfg, h, _plane(lp.w1, l), _plane(lp.w2, l),
                       _plane(lp.w3, l))
        return x, (k_c, v_c, conv)

    def conv_layer(x, caches, stats, c, l):
        k_c, v_c, conv = caches
        x, conv = conv_half(x, conv, c)
        x, s = ffn_half(cfg, x, lp, l, live, may_be_dense=False)
        return x, (k_c, v_c, conv), stats + s

    def attn_layer(x, caches, stats, a, l):
        k_c, v_c, conv = caches
        ap = _stack_at(lp.attn, a, _ATTN_MATMULS)
        box = {}

        def att(q, k, v):
            out, box["k"], box["v"] = attend(q, k, v, k_c, v_c, a)
            return out

        x = x + _attn_mixer(cfg, rms_norm(x, ap.norm_att, eps), ap, table,
                            positions, att)
        x, s = ffn_half(cfg, x, lp, l, live, may_be_dense=False)
        return x, (box["k"], box["v"], conv), stats + s

    def period(p, carry, n_conv):
        """Period ``p``: its attention layer, then ``n_conv`` conv layers."""
        l0 = lead + p * P
        x, caches, stats = attn_layer(*carry, p, l0)

        def conv_j(j, carry):
            return conv_layer(*carry, lead + p * (P - 1) + j, l0 + 1 + j)

        return jax.lax.fori_loop(0, n_conv, conv_j, (x, caches, stats))

    x, caches = jax.lax.fori_loop(0, lead, leading, (x, caches))
    whole, rest = divmod(L - lead, P)
    (x, caches, stats), _ = jax.lax.scan(
        lambda carry, p: (period(p, carry, P - 1), None), (x, caches, stats),
        jnp.arange(whole, dtype=jnp.int32))
    if rest:
        x, caches, stats = period(jnp.int32(whole), (x, caches, stats),
                                  rest - 1)
    return x, caches, stats


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: StateColumn,
            n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a gathered
    column: float32 logits ``[B, T, vocab]`` and the column, advanced by
    the chunk's first ``n_valid`` positions (absent: all ``T``). Positions at
    or past ``n_valid`` are padding: their K/V rows are overwritten later,
    they are not routed, and they never enter a tail."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    if start_pos.ndim:
        raise ValueError("the chunk form takes one start position (the "
                         "dense slot pool's ragged rows are not carried to "
                         "a convolution's tail)")
    B, T = tokens.shape
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    live = jnp.tile(jnp.arange(T) < n_valid, B)
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    positions = jnp.broadcast_to(
        start_pos + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    def conv_mixer(h, cp, c, conv):
        note_short_conv_path("chunk", "xla")
        y, tail = _conv_mixer(cfg, h, cp, _at(conv, c), n_valid)
        return y, _put(conv, tail, c)

    def attend(q, k, v, k_c, v_c, a):
        att, k_a, v_a = _attend_dense(cfg, q, k, v, _at(k_c, a), _at(v_c, a),
                                      start_pos, positions)
        return att, _put(k_c, k_a, a), _put(v_c, v_a, a)

    x, (k, v, conv), stats = _scan_layers(
        params, cfg, x, (col.k, col.v, col.conv), col.stats, live, positions,
        conv_mixer, attend)
    return (_head(params, cfg, x),
            StateColumn(k=k, v=v, s=None, conv=conv, stats=stats))


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """The decode step over the K/V pool, the tail pool and the routing
    counters: ``tokens [B, 1]`` at per-row ``pos_vec``, ``cache =
    (PagedKVCache, StatePool, totals)``, all given back (the pools written in
    place, the step's counters added to row 0 of ``totals``). Row ``b`` is
    slot ``b``: its tails are row ``b + 1`` of the pool, or the null row 0
    while its block table is all null (such a row is not routed)."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("the step form takes one token a row: a "
                         "speculative verify's rejected drafts cannot be "
                         "rolled back out of a convolution's tail")
    pkv, pool, totals = cache
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    live = _live_rows(tables)
    rows = _state_rows(live)
    x = params.embedding[tokens].astype(cfg.compute_dtype)

    def conv_mixer(h, cp, c, conv):
        note_short_conv_path("step", "xla")
        y, tail = _conv_mixer(cfg, h, cp, _at(conv, c)[rows], None)
        return y, conv.at[c, rows].set(tail)

    def attend(q, k, v, k_pool, v_pool, a):
        return _attend_paged(cfg, q, k, v, k_pool, v_pool, a, positions,
                             tables)

    x, (k, v, conv), stats = _scan_layers(
        params, cfg, x, (pkv.k, pkv.v, pool.conv), zero_stats(cfg), live,
        positions, conv_mixer, attend)
    return (_head(params, cfg, x),
            (PagedKVCache(k=k, v=v), StatePool(s=None, conv=conv),
             totals.at[0].add(stats)))


@_exact_f32_dots
def forward_and_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     pos_vec: jax.Array, cache, tables: jax.Array,
                     chunk: jax.Array, chunk_pos: jax.Array,
                     n_valid: jax.Array, poison: jax.Array):
    """A tick that carries a prefill chunk, as ONE program
    (``Family.tick``; ``falcon_h1.forward_and_step``'s signature):
    :func:`forward` over ``chunk [1, T]`` at ``chunk_pos`` into an
    admission's column AND :func:`paged_forward`'s layers over the tick's
    decode rows (``tokens [R, 1]`` at ``pos_vec`` through ``tables``), so
    that every plane of every layer, a routed expert's among them, is read
    once for both. ``cache`` is ``(column, (PagedKVCache, StatePool,
    totals))``, all given back (and donated where the server jits this).

    ONE call of :func:`_scan_layers` over the joined rows ``[1, T + R]``,
    its carry the column AND the pools whole, each as its own program
    carries it. Only what owns a context tells the rows apart: ``attend``
    (the chunk's rows over the column's layer, the decode rows into the
    block pool in place through their tables) and ``conv_mixer`` (``W_in``,
    both gates and ``W_out`` over the joined rows; the convolution a part at
    a time, the chunk's behind the column's tail with ``n_valid``, the rows'
    one token each behind their rows of the tail pool). A row with an
    all-null table is dead, as an inactive slot of a step is (the null
    block, the pool's null row, not routed), and every row may be; the
    chunk's padding is not routed either.

    **The routed half is ONE dispatch of the chunk form** over the joined
    rows (``T + R`` is past ``share.STEP_FORM_MAX_ROWS``): a plane fetched
    once a RUN over the union of what the chunk and the rows chose. Its
    counters are therefore a chunk-form dispatch's, ALL of them (the
    chunk's pairs and the decode rows', rows fed, planes, tokens an
    expert), and the program adds them itself to the totals' CHUNK row
    (row 1), not through ``col.stats`` and the commit: each pair is counted
    once, and the decode rows' are not lost with an admission that is
    cancelled before it commits. ``col.stats`` goes back as it came. Row 0
    is "what the step PROGRAM's dispatches did" (its planes are divided by
    that program's kernel time) and is left as it was.

    Behind the scan the decode ROWS alone get a head, the poison, the argmax
    and the non-finite count (:func:`~dllama_tpu.models.llama._pick_rows`).
    Returns ``((token, nonfinite, logits),
    (column, (pkv, pool, totals)))``, as the dense tick does."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    col, (pkv, pool, totals) = cache
    chunk_pos = jnp.asarray(chunk_pos, dtype=jnp.int32)
    n_valid = jnp.asarray(n_valid, dtype=jnp.int32)
    T = chunk.shape[1]
    joined = _join_tokens(chunk, tokens)[None]                      # [1, T+R]
    cpos, rpos, positions = _join_positions(chunk_pos, pos_vec, T)
    alive = _live_rows(tables)
    live = jnp.concatenate([jnp.arange(T) < n_valid, alive])
    rows = _state_rows(alive)
    x = params.embedding[joined].astype(cfg.compute_dtype)

    def conv_mixer(h, cp, c, conv):
        conv_col, conv_pool = conv
        proj, v = _conv_gate_in(cfg, h, cp)
        note_short_conv_path("chunk", "xla")
        y_c, tail_c = causal_conv(v[:, :T], _at(conv_col, c), cp.conv_w,
                                  n_valid, activation=None)
        note_short_conv_path("step", "xla")
        y_r, tail_r = causal_conv(_by_row(v, T), _at(conv_pool, c)[rows],
                                  cp.conv_w, None, activation=None)
        return (_conv_gate_out(cfg, proj, _join(y_c, y_r), cp, h.dtype),
                (_put(conv_col, tail_c, c), conv_pool.at[c, rows].set(tail_r)))

    def attend(q, k, v, k_c, v_c, a):
        (k_col, k_pool), (v_col, v_pool) = k_c, v_c
        att, k_a, v_a, k_pool, v_pool = _attend_split(
            cfg, q, k, v, T, lambda: (_at(k_col, a), _at(v_col, a)), k_pool,
            v_pool, a, chunk_pos, cpos, rpos, tables)
        return (att, (_put(k_col, k_a, a), k_pool),
                (_put(v_col, v_a, a), v_pool))

    x, (k, v, conv), stats = _scan_layers(
        params, cfg, x, ((col.k, pkv.k), (col.v, pkv.v),
                         (col.conv, pool.conv)),
        zero_stats(cfg), live, positions, conv_mixer, attend)
    return (_pick_rows(_head, params, cfg, x, T, poison),
            (col._replace(k=k[0], v=v[0], conv=conv[0]),
             (PagedKVCache(k=k[1], v=v[1]),
              StatePool(s=None, conv=conv[1]), totals.at[1].add(stats))))


def _load_params(ld, cfg: ModelConfig) -> Params:
    """From the tensors ``mfile._walk_lfm2_layer`` names: two mixer stacks
    by layer kind, the leading layers' dense feed-forward, the routed
    layers' router, selection bias and HELD experts, each stacked over its
    own layers of the model."""
    require_quantized(ld)
    h = ld.h
    every = list(range(h.n_layers))
    attn_ids = [l for l in every if h.lfm2_is_attn(l)]
    conv_ids = [l for l in every if not h.lfm2_is_attn(l)]
    dense_ids, moe_ids = every[:h.n_dense_layers], every[h.n_dense_layers:]
    mm = lambda ids, name, o, i: ld.matmul(
        name, o, i, stacked=True, out_axis=None, in_axis=None, layers=ids)
    wide = h.dense_hidden_dim
    experts = lambda name, o, i: ld.expert_stack(name, o, i, None, None,
                                                 layers=moe_ids)
    return ld.params(Lfm2Layers(
        conv=ConvParams(
            w_in=mm(conv_ids, "block_conv_in", 3 * h.dim, h.dim),
            conv_w=ld.stacked_f32("block_conv_taps", h.short_conv_kernel,
                                  h.dim, layers=conv_ids),
            w_out=mm(conv_ids, "block_conv_out", h.dim, h.dim),
            norm_att=ld.stacked_f32("block_norm_0", h.dim, layers=conv_ids)),
        attn=AttnParams(
            wq=mm(attn_ids, "block_matmul_q", h.q_dim, h.dim),
            wk=mm(attn_ids, "block_matmul_k", h.kv_dim, h.dim),
            wv=mm(attn_ids, "block_matmul_v", h.kv_dim, h.dim),
            wo=mm(attn_ids, "block_matmul_wo", h.dim, h.q_dim),
            norm_q=ld.stacked_f32("block_norm_q", h.head_dim,
                                  layers=attn_ids),
            norm_k=ld.stacked_f32("block_norm_k", h.head_dim,
                                  layers=attn_ids),
            norm_att=ld.stacked_f32("block_norm_0", h.dim, layers=attn_ids)),
        norm_ffn=ld.stacked_f32("block_norm_1", h.dim),
        w1=mm(dense_ids, "block_matmul_w1", wide, h.dim),
        w2=mm(dense_ids, "block_matmul_w2", h.dim, wide),
        w3=mm(dense_ids, "block_matmul_w3", wide, h.dim),
        moe_gate=ld.stacked_f32("block_moe_gate", h.moe_router_width, h.dim,
                                layers=moe_ids),
        moe_bias=(ld.stacked_f32("block_moe_bias", h.moe_router_width,
                                 layers=moe_ids)
                  if h.moe_select_bias else None),
        we1=experts("block_expert_w1", h.hidden_dim, h.dim),
        we2=experts("block_expert_w2", h.dim, h.hidden_dim),
        we3=experts("block_expert_w3", h.hidden_dim, h.dim)))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # two kinds of mixer (the conv layers' in- and out-projection, or
    # q k v wo), the leading dense feed-forward, the held experts of a
    # routed layer with its router over its whole width
    conv = 4 * cfg.dim * cfg.dim
    attn = 2 * cfg.dim * (cfg.q_dim + cfg.kv_dim)
    routed = (cfg.dim * cfg.moe_router_width
              + 3 * cfg.dim * cfg.hidden_dim * cfg.n_experts)
    return (cfg.n_conv_layers * conv + cfg.n_attn_layers * attn
            + cfg.n_dense_layers * 3 * cfg.dim * cfg.dense_hidden_dim
            + cfg.n_moe_layers * routed + cfg.dim * cfg.vocab_size)


def _describe(cfg: ModelConfig, engine) -> str:
    from ..ops import paged_attention as _pa

    # the step's attention at this engine's geometry: what the paged
    # kernel's gate would say of the padded heads on a chip
    step_q = (1, 1, cfg.n_heads, cfg.cache_width)
    compiled = _pa.supports(
        step_q, cfg.n_kv_heads,
        -(-cfg.seq_len // max(1, engine.kv_block_size)),
        max(1, engine.kv_block_size), compiled=True)
    return (f"; layers: {cfg.n_conv_layers} conv ({cfg.conv_kernel} "
            f"taps, a tail of {cfg.conv_kernel - 1} x {cfg.dim} a "
            f"sequence), {cfg.n_attn_layers} full (heads of "
            f"{cfg.head_dim} lanes cached in {cfg.cache_width}: the "
            f"paged kernel {'compiles' if compiled else 'does NOT compile'}"
            f" for them); experts: {cfg.n_experts} of "
            f"{cfg.moe_router_width} held from {cfg.moe_first_expert}, "
            f"{cfg.n_active_experts} a token"
            f"{', selection bias' if cfg.moe_select_bias else ''}")


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=forward_and_step,
    column=StateColumn.zeros,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(full=cfg.n_kv_layers,
                                        conv=cfg.n_conv_layers),
    describe=_describe,
    refusal=state_refusal(
        "a decoder with gated short-convolution layers and routed experts "
        "(a convolution's tail a conv layer in the state pool, routing "
        "counters beside it; the period scan has no mesh plan yet)"))
