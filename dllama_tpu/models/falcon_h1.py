"""A decoder whose every layer runs two mixers SIDE BY SIDE over one normed
input: a Mamba-2 (SSD) state-space mixer and grouped-query softmax attention
(``ArchType.FALCON_H1``; Falcon-H1-34B-Instruct is 72 such layers).

One layer, for its input ``x`` (pre-norm, RMS)::

    h = rmsnorm(x; w_in)
    a = attn(h * attn_in)  * attn_out
    m = mamba(h * ssm_in)  * ssm_out
    x = x + a + m                                # ONE residual add for both
    g = rmsnorm(x; w_ff)
    x = x + W_down(silu(W_gate g * mlp_gate) * (W_up g)) * mlp_down

``attn``: q k v without bias or q/k norm, ``k * key``, the half-split rotary
over the whole head, causal GQA softmax at ``1 / sqrt(hd)``, ``W_o``.
``mamba`` (models/ssd_mixer.py, which models/nemotron_h.py shares;
ops/ssd.py has the recurrence), for its input ``u``::

    [z | xBC | dt] = (W_inproj u) * mup      # mup: ssm_z ssm_x ssm_b ssm_c ssm_dt
    xBC = silu(causal_conv(xBC) + conv_bias);  x_ B C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y  = SSD(x_, dt, A, B, C) + D x_
    W_outproj group_rmsnorm(y * silu(z); w_norm)     # the gate first, then
                                                     # an RMS norm a group

and around the stack ``embed(ids) * embedding`` in front and ``(W_head
rmsnorm(x)) * lm_head`` behind. The scalars are ``cfg.mult``
(:class:`~dllama_tpu.models.config.Multipliers`).

The stack is ONE homogeneous stack (``FalconH1Layers``), as
models/llama.py's is, and every layer owns K/V blocks AND a row of the state
pool (``cfg.n_kv_layers == cfg.n_layers == cfg.n_state_layers``). One scan
over the layer index; everything a slot's context is made of rides the
CARRY whole and is written in place: the K/V (a column's or the block
pool), the state and the convolution's tail (a
:class:`~dllama_tpu.runtime.kvblocks.StateColumn`'s or the
:class:`~dllama_tpu.runtime.kvblocks.StatePool`). As the scan's ``xs``/``ys``
a pool is sliced, stacked and copied back every step (PERF.md section 6, PR
33). Every Q40 plane reaches :func:`~dllama_tpu.ops.linear.linear` as stack +
index (:class:`~dllama_tpu.ops.linear.LayerSlice`). The in-projection is 9248
wide at the published sizes, 72.25 lanes of 128: its ``z x B C`` rows are one
Q40 plane (``w_in``, 9216 = 72 x 128) that the fused kernels take, its
``dt`` rows a float32 plane (``w_dt``, one row a head), as the gated delta
rule's gate rows are.

* :func:`forward`: a prefill chunk over a slot's gathered column, the mixer
  in its CHUNK form. ``n_valid`` masks padding: positions at or past it get
  ``dt = 0`` (the update is then the identity) and never enter the
  convolution's tail; their K/V rows are overwritten later.
* :func:`paged_forward`: the decode step, one token a row, the mixer in its
  STEP form over the state pool in place (the Pallas kernel ``ssd_step`` on
  a TPU, its XLA twin elsewhere); rows whose block table is all null
  (inactive slots riding along) use the pool's null row.
* :func:`forward_and_step` (``FAMILY.tick``, PR 52): a chunk AND the tick's
  decode rows through one pass over the nine planes of every layer, the
  paged server's program for every plain chunk; no chunk logits.

The three are three pairs of closures over ONE layer body
(:func:`_scan_layers`).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm
from ..parallel.api import current_plan
from ..runtime.kvblocks import StateColumn
from .config import ModelConfig
from .family import Family, layer_kinds, state_refusal
from .llama import (Params, _at, _attend_dense, _attend_paged, _attend_split,
                    _exact_f32_dots, _hidden_act, _join_positions,
                    _join_tokens, _live_rows, _pick_rows, _put, _stack_at,
                    _state_rows)
from .rope import apply_rope, build_rope_cache
from .ssd_mixer import mixer_chunk, mixer_chunk_and_step, mixer_step


class FalconH1Layers(NamedTuple):
    """``Params.layers``: every leaf carries a leading ``[n_layers]`` axis."""

    wq: Weight            # [L, q_dim, dim]
    wk: Weight
    wv: Weight
    wo: Weight            # [L, dim, q_dim]
    w_in: Weight          # [L, ssm_in_dim, dim]: the z x B C rows, packed
    w_dt: jax.Array       # [L, H, dim] float32: the dt rows
    conv_w: jax.Array     # [L, K, ssm_conv_dim]
    conv_b: jax.Array     # [L, ssm_conv_dim]
    a_log: jax.Array      # [L, H]
    d_skip: jax.Array     # [L, H]
    dt_bias: jax.Array    # [L, H]
    norm_ssm: jax.Array   # [L, ssm_inner_dim]: the gated norm's weight
    w_out: Weight         # [L, dim, ssm_inner_dim]
    w1: Weight            # [L, hidden_dim, dim] (gate)
    w2: Weight            # (down)
    w3: Weight            # (up)
    norm_att: jax.Array   # [L, dim]: the norm both mixers read through
    norm_ffn: jax.Array   # [L, dim]


_MATMULS = ("wq", "wk", "wv", "wo", "w_in", "w_out", "w1", "w2", "w3")


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a decoder with an SSD mixer beside attention has "
                         "no mesh plan (tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a decoder with an SSD mixer beside attention "
                         "supports neither Q80 sync emulation nor offloaded "
                         "weights")


def _qkv(cfg: ModelConfig, u: jax.Array, lp: FalconH1Layers, cos, sin,
         positions):
    B, T, _ = u.shape
    q = linear(u, lp.wq, out_axis="heads").reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = linear(u, lp.wk, out_axis="kv_heads").reshape(
        B, T, cfg.n_kv_heads, cfg.head_dim) * cfg.mult.key
    v = linear(u, lp.wv, out_axis="kv_heads").reshape(
        B, T, cfg.n_kv_heads, cfg.head_dim)
    return (apply_rope(q, cos, sin, positions, cfg.rope_type),
            apply_rope(k.astype(u.dtype), cos, sin, positions, cfg.rope_type),
            v)


def _scan_layers(params: Params, cfg: ModelConfig, tokens: jax.Array,
                 positions: jax.Array, s, conv, k, v, mixer, attend):
    """The layer scan the three programs share: the hidden rows ``[B, T,
    dim]`` behind the last layer, in front of the final norm (:func:`_head`).
    Everything a slot's context is made of rides the CARRY whole, a column's,
    the pools' or (the tick program) a pair of both: ``s, conv`` (every
    layer's state and tail) and ``k, v`` (every layer's cache); nothing is
    sliced into the scan or stacked out of it. ``mixer(u, lp, l, s, conv) ->
    (y, s, conv)`` is the form of the SSD mixer and ``attend(q, k, v, k_c,
    v_c, l) -> (att, k_c, v_c)`` owns the cache; both give the whole arrays
    back. Everything else a layer does it does a row at a time, so the rows
    along ``T`` need not be one sequence's: only the two closures know."""
    m = cfg.mult
    B, T = tokens.shape
    cos, sin = build_rope_cache(cfg)
    x = (params.embedding[tokens].astype(jnp.float32)
         * m.embedding).astype(cfg.compute_dtype)

    def layer(carry, l):
        x, s, conv, k_c, v_c = carry
        lp = _stack_at(params.layers, l, _MATMULS)
        h = rms_norm(x, lp.norm_att, cfg.norm_epsilon)
        q, k_new, v_new = _qkv(cfg, h * m.attn_in, lp, cos, sin, positions)
        att, k_c, v_c = attend(q, k_new, v_new, k_c, v_c, l)
        a = linear(att.reshape(B, T, cfg.q_dim), lp.wo, in_axis="heads")
        y, s, conv = mixer(h * m.ssm_in, lp, l, s, conv)
        x = x + (a * m.attn_out + y * m.ssm_out).astype(x.dtype)
        g = rms_norm(x, lp.norm_ffn, cfg.norm_epsilon)
        gate = _hidden_act(cfg, linear(g, lp.w1, out_axis="hidden") * m.mlp_gate)
        ffn = linear(gate * linear(g, lp.w3, out_axis="hidden"), lp.w2,
                     in_axis="hidden")
        x = x + (ffn * m.mlp_down).astype(x.dtype)
        return (x, s, conv, k_c, v_c), None

    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, s, conv, k, v), _ = jax.lax.scan(layer, (x, s, conv, k, v), layers)
    return x, s, conv, k, v


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Float32 logits of the hidden rows ``x``: the final norm, the head,
    its multiplier."""
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    logits = linear(x, params.logits, out_axis="vocab").astype(jnp.float32)
    return logits * cfg.mult.lm_head


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col, n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a gathered
    :class:`~dllama_tpu.runtime.kvblocks.StateColumn`: float32 logits ``[B,
    T, vocab]`` and the column, advanced by the chunk's first ``n_valid``
    positions (absent: all ``T``)."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    if start_pos.ndim:
        raise ValueError("a recurrent state's chunk form takes one start "
                         "position (the dense slot pool's ragged rows are "
                         "not carried to it)")
    B, T = tokens.shape
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    positions = jnp.broadcast_to(
        start_pos + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    def mixer(u, lp, l, s, conv):
        y, s_l, conv_l = mixer_chunk(cfg, u, lp, _at(s, l), _at(conv, l),
                                     n_valid)
        return y, _put(s, s_l, l), _put(conv, conv_l, l)

    def attend(q, k, v, k_c, v_c, l):
        att, k_l, v_l = _attend_dense(cfg, q, k, v, _at(k_c, l), _at(v_c, l),
                                      start_pos, positions)
        return att, _put(k_c, k_l, l), _put(v_c, v_l, l)

    x, s, conv, k, v = _scan_layers(params, cfg, tokens, positions,
                                    col.s, col.conv, col.k, col.v,
                                    mixer, attend)
    return _head(params, cfg, x), StateColumn(k=k, v=v, s=s, conv=conv)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """The decode step over the paged pool and the state pool: ``tokens [B,
    1]`` at per-row ``pos_vec``, ``cache = (PagedKVCache, StatePool)``, both
    given back. Row ``b`` is slot ``b``: its state is row ``b + 1`` of the
    pool, or the null row 0 while its block table is all null."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("a recurrent state's step form takes one token a "
                         "row: a speculative verify's rejected drafts "
                         "cannot be rolled back out of it")
    pkv, pool = cache
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    rows = _state_rows(_live_rows(tables))

    def mixer(u, lp, l, s, conv):
        return mixer_step(cfg, u, lp, l, rows, s, conv)

    def attend(q, k, v, k_pool, v_pool, l):
        return _attend_paged(cfg, q, k, v, k_pool, v_pool, l, positions,
                             tables)

    x, s, conv, k, v = _scan_layers(params, cfg, tokens, positions,
                                    pool.s, pool.conv, pkv.k, pkv.v,
                                    mixer, attend)
    return (_head(params, cfg, x),
            (PagedKVCache(k=k, v=v), StatePool(s=s, conv=conv)))


@_exact_f32_dots
def forward_and_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     pos_vec: jax.Array, cache, tables: jax.Array,
                     chunk: jax.Array, chunk_pos: jax.Array,
                     n_valid: jax.Array, poison: jax.Array):
    """A tick that carries a prefill chunk, as ONE program
    (``Family.tick``; the dense decoders' is ``llama.forward_and_step``,
    whose signature this is plus the chunk's valid length, which a recurrent
    state needs): :func:`forward` over ``chunk [1, T]`` at ``chunk_pos`` into
    an admission's column AND :func:`paged_forward`'s layers over the tick's
    decode rows (``tokens [R, 1]`` at ``pos_vec`` through ``tables``), so
    that every layer's nine planes are read once for both. ``cache`` is
    ``(column, (PagedKVCache, StatePool))``, all given back (and donated
    where the server jits this).

    ONE call of :func:`_scan_layers` over the joined rows ``[1, T + R]``,
    its carry the column AND the pools whole, each as its own program
    carries it. Only what owns a context tells the rows apart: ``attend``
    (the chunk's rows over the column's layer, the decode rows into the
    block pool in place through their tables) and ``mixer``
    (:func:`~dllama_tpu.models.ssd_mixer.mixer_chunk_and_step`: the
    convolution and the recurrence a part at a time, the chunk form against
    the column, the step form against the pools). A row with an all-null
    table is dead, as an inactive slot of a step is (the null block, the
    pool's null row), and every row may be.

    Behind the scan the decode ROWS alone get a head, the poison, the argmax
    and the non-finite count (:func:`~dllama_tpu.models.llama._pick_rows`).
    Returns ``((token, nonfinite, logits),
    (column, (pkv, pool)))``, as the dense tick does."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    col, (pkv, pool) = cache
    chunk_pos = jnp.asarray(chunk_pos, dtype=jnp.int32)
    n_valid = jnp.asarray(n_valid, dtype=jnp.int32)
    T = chunk.shape[1]
    joined = _join_tokens(chunk, tokens)[None]                      # [1, T+R]
    cpos, rpos, positions = _join_positions(chunk_pos, pos_vec, T)
    rows = _state_rows(_live_rows(tables))

    def mixer(u, lp, l, s, conv):
        (s_col, s_pool), (conv_col, conv_pool) = s, conv
        y, (s_l, conv_l), (s_pool, conv_pool) = mixer_chunk_and_step(
            cfg, u, lp, l, T, _at(s_col, l), _at(conv_col, l), n_valid, rows,
            s_pool, conv_pool)
        return (y, (_put(s_col, s_l, l), s_pool),
                (_put(conv_col, conv_l, l), conv_pool))

    def attend(q, k, v, k_c, v_c, l):
        (k_col, k_pool), (v_col, v_pool) = k_c, v_c
        att, k_l, v_l, k_pool, v_pool = _attend_split(
            cfg, q, k, v, T, lambda: (_at(k_col, l), _at(v_col, l)), k_pool,
            v_pool, l, chunk_pos, cpos, rpos, tables)
        return (att, (_put(k_col, k_l, l), k_pool),
                (_put(v_col, v_l, l), v_pool))

    x, s, conv, k, v = _scan_layers(
        params, cfg, joined, positions, (col.s, pool.s),
        (col.conv, pool.conv), (col.k, pkv.k), (col.v, pkv.v), mixer, attend)
    return (_pick_rows(_head, params, cfg, x, T, poison),
            (StateColumn(k=k[0], v=v[0], s=s[0], conv=conv[0]),
             (PagedKVCache(k=k[1], v=v[1]), StatePool(s=s[1], conv=conv[1]))))


def _load_params(ld, cfg: ModelConfig) -> Params:
    """The one homogeneous stack from the tensors
    ``mfile._walk_falcon_h1_layer`` names."""
    h = ld.h
    mm = lambda name, o, i: ld.matmul(name, o, i, stacked=True,
                                      out_axis=None, in_axis=None)
    f32 = ld.stacked_f32
    return ld.params(FalconH1Layers(
        wq=mm("block_matmul_q", h.q_dim, h.dim),
        wk=mm("block_matmul_k", h.kv_dim, h.dim),
        wv=mm("block_matmul_v", h.kv_dim, h.dim),
        wo=mm("block_matmul_wo", h.dim, h.q_dim),
        w_in=mm("block_ssm_in", h.ssm_in_dim, h.dim),
        w_dt=f32("block_ssm_dt", h.ssm_n_heads, h.dim),
        conv_w=f32("block_ssm_conv", h.ssm_conv_kernel, h.ssm_conv_dim),
        conv_b=f32("block_ssm_conv_bias", h.ssm_conv_dim),
        a_log=f32("block_ssm_a_log", h.ssm_n_heads),
        d_skip=f32("block_ssm_d", h.ssm_n_heads),
        dt_bias=f32("block_ssm_dt_bias", h.ssm_n_heads),
        norm_ssm=f32("block_ssm_norm", h.ssm_inner_dim),
        w_out=mm("block_ssm_out", h.dim, h.ssm_inner_dim),
        w1=mm("block_matmul_w1", h.hidden_dim, h.dim),
        w2=mm("block_matmul_w2", h.dim, h.hidden_dim),
        w3=mm("block_matmul_w3", h.hidden_dim, h.dim),
        norm_att=f32("block_norm_0", h.dim),
        norm_ffn=f32("block_norm_1", h.dim)))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # one kind of layer: q k v wo, the mixer's packed input projection
    # (its dt rows are a small float32 plane, not counted here) and
    # output projection, a dense feed-forward
    layer = (cfg.dim * cfg.q_dim + 2 * cfg.dim * cfg.kv_dim
             + cfg.q_dim * cfg.dim + cfg.dim * cfg.ssm_in_dim
             + cfg.ssm_inner_dim * cfg.dim + 3 * cfg.dim * cfg.hidden_dim)
    return cfg.n_layers * layer + cfg.dim * cfg.vocab_size


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=forward_and_step,
    column=StateColumn.zeros,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    # a layer with an SSD mixer beside its attention is neither linear nor
    # full
    layer_kinds=lambda cfg: layer_kinds(ssm_beside_full=cfg.n_layers),
    describe=lambda cfg, engine: (f"; layers: {cfg.n_layers} with an SSD "
                                  f"mixer beside attention"),
    refusal=state_refusal(
        "a decoder with an SSD mixer beside attention in every layer (a "
        "recurrent state a layer; the layer scan carries the state pool and "
        "has no mesh plan yet)"))
