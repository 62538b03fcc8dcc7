"""A hybrid decoder: gated delta-rule (linear-attention) layers and full
softmax-attention layers in a periodic pattern (``ArchType.OLMO_HYBRID``;
Olmo-Hybrid-7B is three linear layers to one full, eight periods).

The stack is TWO stacks, scanned once over PERIODS: ``HybridLayers.lin``
holds the ``n_periods * (P - 1)`` linear layers, ``HybridLayers.full`` (a
:class:`~dllama_tpu.models.llama.LayerParams`) the ``n_periods`` full ones.
One period of the scan walks ``P - 1`` linear layers (a ``fori_loop`` over
one traced body) and one full layer; nothing loops over the depth in
Python. Every Q40 plane of both stacks stays whole and reaches
:func:`~dllama_tpu.ops.linear.linear` as stack + index
(:class:`~dllama_tpu.ops.linear.LayerSlice`), as the dense decoders' decode
step does since PR 28.

A slot's context is two things side by side: K/V rows of the FULL layers
only (a column ``[n_periods, 1, n_kv, S, hd]`` during prefill, blocks of the
paged pool afterwards) and, for the linear layers, a float32 recurrent
state ``[n_linear, H, dk, dv]`` and the convolution's last ``K - 1`` inputs
(:class:`~dllama_tpu.runtime.kvblocks.StateColumn` during prefill, a row
of :class:`~dllama_tpu.runtime.kvblocks.StatePool` afterwards).

* :func:`forward`: a prefill chunk over a slot's gathered column. The
  mixer runs its CHUNK form (ops/gated_delta.gated_delta_chunk), state in
  and state out. ``n_valid`` masks padding: K/V rows written for padded
  positions are overwritten later, a state would keep them, so positions at
  or past ``n_valid`` get ``beta = 0, alpha = 1`` and never enter the
  convolution's tail.
* :func:`paged_forward`: the decode step, one token a row. The mixer runs
  its STEP form over the state pool in place (the Pallas kernel
  ``gated_delta_step`` on a TPU, its XLA twin elsewhere); rows whose block
  table is all null (inactive slots riding along) use the pool's null row.

In both, everything a slot's context is made of rides the period scan's
CARRY whole: the state and the tail, and the full layers' K/V (a column's
or the pool's), which period ``p`` writes in place and attends through the
whole array and ``p`` (:func:`~dllama_tpu.models.llama._attend_paged`). As
the scan's ``xs``/``ys`` the K/V pool was sliced, stacked and copied back
every step (PERF.md section 6, PR 33).

A linear layer's mixer, for its input ``u``: one packed projection ``[q~ k~
v~ z] = W_in u``, gates ``[a b] = W_ab u``; a causal depthwise convolution
of ``K`` taps and SiLU over ``q~ k~ v~``; per head ``q = l2norm(q') /
sqrt(dk)``, ``k = l2norm(k')``, ``beta = sigmoid(b)`` (doubled where
``lin_neg_eigval``), ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``; the
gated delta rule; ``y = W_out (rmsnorm_dv(o) * silu(z))``.

The arch implies three conventions (the Olmo 2/3 family's; none is in the
published config): block norms sit on a sublayer's OUTPUT (``x + norm(f(x))``),
q and k carry an RMS norm over the WHOLE projection before the heads are
split, and the full layers carry no rotary embedding.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta as gd
from ..ops.causal_conv import causal_conv
from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm
from ..parallel.api import current_plan
from ..runtime.introspection import note_gdn_path
from ..runtime.kvblocks import StateColumn
from .config import ModelConfig
from .family import Family, layer_kinds, state_refusal
from .llama import (LayerParams, Params, _attend_dense, _attend_paged,
                    _hidden_act, _layer_at, _stack_at)


class LinearLayerParams(NamedTuple):
    """The linear-attention layers' weights; every leaf carries a leading
    ``[n_linear]`` axis (layer ``l`` of the model, in file order, is linear
    layer ``l - l // P``)."""

    w_in: Weight          # [NL, lin_in_dim, dim]: q~ k~ v~ z rows, packed
    w_ab: jax.Array       # [NL, 2 H, dim] float32: the a and b gate rows
    conv_w: jax.Array     # [NL, K, lin_conv_dim]
    a_log: jax.Array      # [NL, H]
    dt_bias: jax.Array    # [NL, H]
    norm_o: jax.Array     # [NL, dv]: the output norm over a value head
    w_out: Weight         # [NL, dim, H dv]
    w1: Weight            # [NL, hidden_dim, dim]
    w2: Weight
    w3: Weight
    norm_att: jax.Array   # [NL, dim]: the mixer sublayer's norm
    norm_ffn: jax.Array   # [NL, dim]


_LINEAR_MATMULS = ("w_in", "w_out", "w1", "w2", "w3")


class HybridLayers(NamedTuple):
    """``Params.layers`` of a hybrid decoder: the two stacks."""

    lin: LinearLayerParams
    full: LayerParams     # norm_q/norm_k: [NF, q_dim]/[NF, kv_dim], over the whole projection


# one slot's context gathered for chunked prefill: the state's own column
# type (runtime/kvblocks.py), under the name this module gave it first
HybridColumn = StateColumn


def _sublayer(cfg: ModelConfig, x: jax.Array, norm_w: jax.Array, f):
    """``x + norm(f(x))``: the norm sits on the sublayer's output."""
    return x + rms_norm(f(x), norm_w, cfg.norm_epsilon)


def _ffn(cfg: ModelConfig, h: jax.Array, lp) -> jax.Array:
    gate = _hidden_act(cfg, linear(h, lp.w1, out_axis="hidden"))
    return linear(gate * linear(h, lp.w3, out_axis="hidden"), lp.w2,
                  in_axis="hidden")


def _mixer_inputs(cfg: ModelConfig, u: jax.Array, lp: LinearLayerParams,
                  tail: jax.Array, n_valid):
    """Everything of the mixer in front of the rule, for ``u [B, T, dim]``
    and the convolution's ``tail [B, K - 1, C]``: float32 ``q, k [B, T, H,
    dk]``, ``v [B, T, H, dv]``, ``g`` (log decay) and ``beta [B, T, H]``, the
    output gate ``z [B, T, H, dv]`` and the new tail."""
    B, T, _ = u.shape
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    proj = linear(u, lp.w_in)
    qkv, z = proj[..., :cfg.lin_conv_dim], proj[..., cfg.lin_conv_dim:]
    ab = jnp.einsum("btd,hd->bth", u.astype(jnp.float32), lp.w_ab,
                    precision=jax.lax.Precision.HIGHEST)
    y, tail = causal_conv(qkv, tail, lp.conv_w, n_valid)
    q = gd.l2norm(y[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
    k = gd.l2norm(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
    g, beta = gd.gates(ab[..., :H], ab[..., H:], lp.a_log, lp.dt_bias,
                       cfg.lin_neg_eigval)
    return q, k, v, g, beta, z.reshape(B, T, H, dv), tail


def _mixer_output(cfg: ModelConfig, o: jax.Array, z: jax.Array,
                  lp: LinearLayerParams, dtype) -> jax.Array:
    """``W_out (rmsnorm_dv(o) * silu(z))`` from float32 ``o [B, T, H, dv]``."""
    B, T = o.shape[:2]
    gated = (rms_norm(o, lp.norm_o, cfg.norm_epsilon)
             * jax.nn.silu(z.astype(jnp.float32)))
    return linear(gated.reshape(B, T, -1).astype(dtype), lp.w_out)


def _mixer_chunk(cfg, u, lp, s_l, conv_l, n_valid):
    """The mixer over a chunk: ``s_l [B, H, dk, dv]`` in and out."""
    T = u.shape[1]
    q, k, v, g, beta, z, conv_l = _mixer_inputs(cfg, u, lp, conv_l, n_valid)
    real = (jnp.arange(T) < n_valid)[None, :, None]
    note_gdn_path("chunk", "xla")
    o, s_l = gd.gated_delta_chunk(q, k, v, jnp.where(real, g, 0.0),
                                  jnp.where(real, beta, 0.0), s_l)
    return _mixer_output(cfg, o, z, lp, u.dtype), s_l, conv_l


def _mixer_step(cfg, u, lp, l, rows, s_pool, conv_pool):
    """The mixer over one token a row, the pools in and out: row ``b``'s
    state and tail are ``[l, rows[b]]`` of them."""
    tail = jax.lax.dynamic_index_in_dim(conv_pool, l, 0, keepdims=False)[rows]
    q, k, v, g, beta, z, tail = _mixer_inputs(cfg, u, lp, tail, None)
    conv_pool = conv_pool.at[l, rows].set(tail)
    kernel = gd.step_kernel_choice()
    note_gdn_path("step", "xla" if kernel is None else "pallas")
    step = (gd.gated_delta_step_xla if kernel is None
            else lambda *a: gd.gated_delta_step(*a, **kernel))
    o, s_pool = step(s_pool, l, rows, q[:, 0], k[:, 0], v[:, 0],
                     jnp.exp(g[:, 0]), beta[:, 0])
    return _mixer_output(cfg, o[:, None], z, lp, u.dtype), s_pool, conv_pool


def _full_qkv(cfg: ModelConfig, h: jax.Array, lp: LayerParams):
    """A full layer's q, k, v: q and k normed over the whole projection,
    then the heads split; no rotary embedding."""
    B, T, _ = h.shape
    q = rms_norm(linear(h, lp.wq, out_axis="heads"), lp.norm_q,
                 cfg.norm_epsilon)
    k = rms_norm(linear(h, lp.wk, out_axis="kv_heads"), lp.norm_k,
                 cfg.norm_epsilon)
    v = linear(h, lp.wv, out_axis="kv_heads")
    return (q.reshape(B, T, cfg.n_heads, cfg.head_dim),
            k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim))


def _full_layer(cfg: ModelConfig, x: jax.Array, lp: LayerParams, attend):
    """One full layer; ``attend(q, k, v) -> att`` owns the cache."""
    B, T, _ = x.shape

    def attention(h):
        q, k, v = _full_qkv(cfg, h, lp)
        return linear(attend(q, k, v).reshape(B, T, cfg.q_dim), lp.wo,
                      in_axis="heads")

    x = _sublayer(cfg, x, lp.norm_att, attention)
    return _sublayer(cfg, x, lp.norm_ffn, lambda h: _ffn(cfg, h, lp))


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a hybrid decoder's period scan has no mesh plan "
                         "(tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a hybrid decoder supports neither Q80 sync "
                         "emulation nor offloaded weights")


def _head(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params.final_norm, cfg.norm_epsilon)
    return linear(x, params.logits, out_axis="vocab").astype(jnp.float32)


def _scan_periods(params: Params, cfg: ModelConfig, x: jax.Array, s, conv,
                  k, v, mixer, store, attend):
    """The period scan both programs share: a period's linear layers (a
    ``fori_loop`` over one traced body), then its full layer. Everything a
    slot's context is made of rides the CARRY whole, a column's or the
    pool: ``s, conv`` (every linear layer's state and tail) and ``k, v``
    (the full layers' cache, indexed by the period ``p``); nothing is
    sliced into the scan or stacked out of it, so the pools are written in
    place. ``mixer(h, lp, l, s, conv) -> (y, s', conv')`` is the form of the
    mixer and ``store(a, a', l)`` puts what it gave back into the carry (a
    column's layer ``l``; the pool comes back whole);
    ``attend(q, k, v, k_c, v_c, p) -> (att, k_c, v_c)`` owns the cache."""
    per_period = cfg.layer_period - 1
    lin, full = params.layers

    def period(carry, p):
        x, s, conv, k_c, v_c = carry

        def linear_layer(j, carry):
            x, s, conv = carry
            l = p * per_period + j
            lp = _stack_at(lin, l, _LINEAR_MATMULS)
            new = {}

            def mix(h):
                y, new["s"], new["conv"] = mixer(h, lp, l, s, conv)
                return y

            x = _sublayer(cfg, x, lp.norm_att, mix)
            x = _sublayer(cfg, x, lp.norm_ffn, lambda h: _ffn(cfg, h, lp))
            return x, store(s, new["s"], l), store(conv, new["conv"], l)

        x, s, conv = jax.lax.fori_loop(0, per_period, linear_layer,
                                       (x, s, conv))
        cache = {}

        def attend_p(q, k, v):
            att, cache["k"], cache["v"] = attend(q, k, v, k_c, v_c, p)
            return att

        x = _full_layer(cfg, x, _layer_at(full, p), attend_p)
        return (x, s, conv, cache["k"], cache["v"]), None

    periods = jnp.arange(cfg.n_periods, dtype=jnp.int32)
    (x, s, conv, k, v), _ = jax.lax.scan(period, (x, s, conv, k, v), periods)
    return _head(params, cfg, x), s, conv, k, v


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: HybridColumn,
            n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a gathered
    column: float32 logits ``[B, T, vocab]`` and the column, advanced by
    the chunk's first ``n_valid`` positions (absent: all ``T``)."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    if start_pos.ndim:
        raise ValueError("a hybrid decoder's chunk form takes one start "
                         "position (the dense slot pool's ragged rows are "
                         "not carried to a recurrent state)")
    B, T = tokens.shape
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    x = params.embedding[tokens].astype(cfg.compute_dtype)
    positions = jnp.broadcast_to(
        start_pos + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    def at(a, l):
        return jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)

    def mixer(h, lp, l, s, conv):
        return _mixer_chunk(cfg, h, lp, at(s, l), at(conv, l), n_valid)

    def store(a, a_l, l):
        return jax.lax.dynamic_update_index_in_dim(a, a_l, l, 0)

    def attend(q, k, v, k_c, v_c, p):
        att, k_p, v_p = _attend_dense(cfg, q, k, v, at(k_c, p), at(v_c, p),
                                      start_pos, positions)
        return att, store(k_c, k_p, p), store(v_c, v_p, p)

    logits, s, conv, k, v = _scan_periods(params, cfg, x, col.s, col.conv,
                                          col.k, col.v, mixer, store,
                                          attend)
    return logits, HybridColumn(k=k, v=v, s=s, conv=conv)


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
        raise ValueError("a hybrid decoder's step form takes one token a "
                         "row: a speculative verify's rejected drafts "
                         "cannot be rolled back out of a recurrent state")
    pkv, pool = cache
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    rows = jnp.where(tables[:, 0] != 0, jnp.arange(1, B + 1, dtype=jnp.int32),
                     StatePool.NULL)
    x = params.embedding[tokens].astype(cfg.compute_dtype)

    def mixer(h, lp, l, s, conv):
        return _mixer_step(cfg, h, lp, l, rows, s, conv)

    def attend(q, k, v, k_pool, v_pool, p):
        return _attend_paged(cfg, q, k, v, k_pool, v_pool, p, positions,
                             tables)

    logits, s, conv, k, v = _scan_periods(params, cfg, x, pool.s, pool.conv,
                                          pkv.k, pkv.v, mixer,
                                          lambda _a, new, _l: new, attend)
    return logits, (PagedKVCache(k=k, v=v), StatePool(s=s, conv=conv))


def _load_params(ld, cfg: ModelConfig) -> Params:
    """The two stacks from the tensors ``mfile._walk_hybrid_layer`` names:
    the linear layers' and the full layers', each stacked over its own
    layers of the model."""
    h = ld.h
    P = h.layer_period
    lin_ids = [l for l in range(h.n_layers) if (l + 1) % P]
    full_ids = [l for l in range(h.n_layers) if (l + 1) % P == 0]
    vdim = h.linear_n_value_heads * h.linear_value_head_dim

    def stack(ids):
        mm = lambda name, o, i, **kw: ld.matmul(
            name, o, i, stacked=True, out_axis=None, in_axis=None,
            layers=ids, **kw)
        f32 = lambda name, *tail: ld.stacked_f32(name, *tail, layers=ids)
        return mm, f32

    mm, f32 = stack(lin_ids)
    lin = LinearLayerParams(
        w_in=mm("block_gdn_in", h.linear_in_dim, h.dim),
        w_ab=f32("block_gdn_ab", 2 * h.linear_n_value_heads, h.dim),
        conv_w=f32("block_gdn_conv", h.linear_conv_kernel, h.linear_conv_dim),
        a_log=f32("block_gdn_a_log", h.linear_n_value_heads),
        dt_bias=f32("block_gdn_dt_bias", h.linear_n_value_heads),
        norm_o=f32("block_gdn_norm", h.linear_value_head_dim),
        w_out=mm("block_gdn_out", h.dim, vdim),
        w1=mm("block_matmul_w1", h.hidden_dim, h.dim),
        w2=mm("block_matmul_w2", h.dim, h.hidden_dim),
        w3=mm("block_matmul_w3", h.hidden_dim, h.dim),
        norm_att=f32("block_norm_0", h.dim),
        norm_ffn=f32("block_norm_1", h.dim))
    mm, f32 = stack(full_ids)
    full = LayerParams(
        wq=mm("block_matmul_q", h.q_dim, h.dim),
        wk=mm("block_matmul_k", h.kv_dim, h.dim),
        wv=mm("block_matmul_v", h.kv_dim, h.dim),
        wo=mm("block_matmul_wo", h.dim, h.q_dim),
        w1=mm("block_matmul_w1", h.hidden_dim, h.dim),
        w2=mm("block_matmul_w2", h.dim, h.hidden_dim),
        w3=mm("block_matmul_w3", h.hidden_dim, h.dim),
        norm_att=f32("block_norm_0", h.dim),
        norm_ffn=f32("block_norm_1", h.dim),
        norm_q=f32("block_norm_q", h.q_dim),
        norm_k=f32("block_norm_k", h.kv_dim))
    return ld.params(HybridLayers(lin=lin, full=full))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # two kinds of layer: the mixer's packed input projection and its
    # output projection, or q k v wo; a dense feed-forward in both
    ffn = 3 * cfg.dim * cfg.hidden_dim
    lin = (cfg.dim * cfg.lin_in_dim
           + cfg.lin_heads * cfg.lin_value_dim * cfg.dim + ffn)
    full = (cfg.dim * cfg.q_dim + 2 * cfg.dim * cfg.kv_dim
            + cfg.q_dim * cfg.dim + ffn)
    return (cfg.n_linear_layers * lin + cfg.n_kv_layers * full
            + cfg.dim * cfg.vocab_size)


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=None,
    column=StateColumn.zeros,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(linear=cfg.n_linear_layers,
                                        full=cfg.n_kv_layers),
    describe=lambda cfg, engine: (f"; layers: {cfg.n_linear_layers} linear, "
                                  f"{cfg.n_kv_layers} full"),
    refusal=state_refusal(
        "a hybrid decoder (linear-attention layers with a recurrent state; "
        "the period scan has no mesh plan yet)"))
