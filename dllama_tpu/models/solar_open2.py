"""A delta rule whose decay is a VECTOR a head beside gated full attention,
routed experts behind both (``ArchType.SOLAR_OPEN2``; Solar-Open2-250B is 48
layers in periods of four, one full layer and then three delta-rule layers,
and behind EVERY mixer 8 of 320 experts and a shared one).

**The equations.** Every layer is two pre-norm sublayers::

    h = x + Mixer_l(rmsnorm(x; w_l^att));  y = h + MoE_l(rmsnorm(h; w_l^ffn))

* the FIRST layer of a period (``cfg.full_layer_at`` 0): causal softmax
  grouped-query attention at ``head_dim ** -0.5`` with NO positional embedding
  and an output gate a lane, ``W_o (sigmoid(W_g u) * attn)``.
* the others: Kimi Delta Attention (arXiv:2510.26692). ``q~ k~ v~ = W_q u, W_k
  u, W_v u``, three planes; a causal depthwise convolution of ``K`` taps and
  SiLU over each (ONE tail holds the three side by side); per head ``q =
  l2norm(q') / sqrt(dk)``, ``k = l2norm(k')``; the log decay ``g = -exp(A_log[h])
  softplus(W_f^up W_f^down u + dt_bias)``, ``dk`` numbers a head (``cfg.lin_decay_dim``,
  which the header holds to ``dk``; one a head is models/hybrid.py's rule);
  ``beta = sigmoid(W_b u)``,
  doubled where ``lin_neg_eigval``; the rule of ``ops/gated_delta.py`` with a
  decay a key channel, ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
  beta_t k_t v_t^T``; ``y = W_o (rmsnorm_dv(o) * sigmoid(W_g^up W_g^down u))``.
  Both low-rank pairs are float32 rows ``cfg.lin_gate_rank`` wide.
* ``MoE_l``: the routed feed-forward of ``models/share.py`` as it stands: a
  sigmoid router whose SELECTION alone takes the learned bias, the chosen
  scores renormalised and scaled, gated experts of which this chip may hold a
  share, an ungated shared one. Its counters ride the period scan's carry
  and come back with the pools, as models/lfm2.py's and nemotron_h.py's do.

**No walk of its own**: a :class:`~dllama_tpu.models.hybrid.Walk` over
``hybrid._scan_periods`` with the full layer FIRST in its period, norms on a
sublayer's input, and THREE stacks (:class:`KdaParams` over the delta-rule
layers, :class:`FullParams` over the full ones, :class:`MoeParams` over every
layer of the model), and ``hybrid.chunk_program`` / ``hybrid.step_program``
around it: what a slot's context is made of (K/V of the full layers, a
float32 state ``[H, dk, dv]`` and the convolution's tail of the others) is
the hybrid's. ``family.tick`` is None: a chunk and a step are two programs.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import gated_delta as gd
from ..ops.causal_conv import causal_conv
from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm
from ..runtime.kvblocks import StateColumn
from . import hybrid
from .config import ModelConfig
from .family import Family, layer_kinds, state_refusal
from .llama import Params, _at
from .share import ffn_half, require_quantized, zero_stats

_HIGHEST = jax.lax.Precision.HIGHEST


class KdaParams(NamedTuple):
    """The delta-rule layers' mixers, stacked over them in the model's
    order."""

    wq: Weight            # [NL, H dk, dim]
    wk: Weight            # [NL, H dk, dim]
    wv: Weight            # [NL, H dv, dim]
    conv_w: jax.Array     # [NL, K, lin_conv_dim]: q~ k~ v~ side by side
    a_log: jax.Array      # [NL, H]
    w_f_down: jax.Array   # [NL, rank, dim] float32: the decay's pair
    w_f_up: jax.Array     # [NL, H decays, rank]
    dt_bias: jax.Array    # [NL, H decays]
    w_b: jax.Array        # [NL, H, dim] float32: the beta rows
    w_g_down: jax.Array   # [NL, rank, dim] float32: the output gate's pair
    w_g_up: jax.Array     # [NL, H dv, rank]
    norm_o: jax.Array     # [NL, dv]: the output norm over a value head
    w_out: Weight         # [NL, dim, H dv]
    norm_att: jax.Array   # [NL, dim]: the mixer sublayer's norm


class FullParams(NamedTuple):
    """The full layers' attention."""

    wq: Weight            # [NF, q_dim, dim]
    wk: Weight            # [NF, kv_dim, dim]
    wv: Weight
    wo: Weight            # [NF, dim, q_dim]
    wg: Weight            # [NF, q_dim, dim]: the output gate, one a lane
    norm_att: jax.Array   # [NF, dim]


class MoeParams(NamedTuple):
    """Every layer's routed feed-forward, the leaves as ``models/share.py``
    names them."""

    norm_ffn: jax.Array          # [L, dim]
    moe_gate: jax.Array          # [L, router_width, dim] float32
    moe_bias: jax.Array | None   # [L, router_width] float32: the selection's
    we1: Weight                  # [L, held, dim, hidden]
    we2: Weight                  # [L, held, hidden, dim]
    we3: Weight
    ws1: Weight | None           # [L, shared, dim]
    ws2: Weight | None
    ws3: Weight | None


class SolarLayers(NamedTuple):
    """``Params.layers``: the three stacks."""

    kda: KdaParams
    full: FullParams
    moe: MoeParams


_KDA_MATMULS = ("wq", "wk", "wv", "w_out")
_FULL_MATMULS = ("wq", "wk", "wv", "wo", "wg")


def _mixer_project(cfg: ModelConfig, u: jax.Array, lp: KdaParams):
    """What the mixer does a ROW at a time in front of its convolution, for
    ``u [B, T, dim]``: the three planes' rows side by side (``qkv [B, T,
    lin_conv_dim]``, the convolution's input) and, in float32 through the
    low-rank pairs, the decay's rows ``f [B, T, H decays]``, the ``beta`` rows
    ``b [B, T, H]`` and the output gate's ``z [B, T, H dv]``."""
    qkv = jnp.concatenate(
        [linear(u, lp.wq), linear(u, lp.wk), linear(u, lp.wv)], axis=-1)
    u32 = u.astype(jnp.float32)
    rows = lambda x, w: jnp.einsum("btd,hd->bth", x, w, precision=_HIGHEST)
    f = rows(rows(u32, lp.w_f_down), lp.w_f_up)
    z = rows(rows(u32, lp.w_g_down), lp.w_g_up)
    return qkv, z, (f, rows(u32, lp.w_b))


def _mixer_heads(cfg: ModelConfig, y: jax.Array, z: jax.Array, fb,
                 lp: KdaParams):
    """Behind the convolution, in front of the rule: float32 ``q, k [B, T, H,
    dk]``, ``v [B, T, H, dv]``, ``beta [B, T, H]``, the log decay ``g [B, T, H,
    dk]`` and the output gate ``z [B, T, H, dv]``."""
    B, T, _ = y.shape
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    q = gd.l2norm(y[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
    k = gd.l2norm(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
    f, b = fb
    g, beta = gd.gates(f.reshape(B, T, H, dk), b, lp.a_log[:, None],
                       lp.dt_bias.reshape(H, dk), cfg.lin_neg_eigval)
    return q, k, v, g, beta, z.reshape(B, T, H, dv)


def _mixer_output(cfg: ModelConfig, o: jax.Array, z: jax.Array,
                  lp: KdaParams, dtype) -> jax.Array:
    """``W_out (rmsnorm_dv(o) * sigmoid(z))`` from float32 ``o [B, T, H,
    dv]``."""
    B, T = o.shape[:2]
    gated = rms_norm(o, lp.norm_o, cfg.norm_epsilon) * jax.nn.sigmoid(z)
    return linear(gated.reshape(B, T, -1).astype(dtype), lp.w_out)


def _mixer_chunk(cfg, u, lp, s_l, conv_l, n_valid):
    """The mixer over a chunk: ``s_l [B, H, dk, dv]`` and the tail ``conv_l
    [B, K - 1, C]`` in and out (``hybrid._mixer_chunk``'s signature)."""
    qkv, z, fb = _mixer_project(cfg, u, lp)
    y, conv_l = causal_conv(qkv, conv_l, lp.conv_w, n_valid)
    q, k, v, g, beta, z = _mixer_heads(cfg, y, z, fb, lp)
    o, s_l = hybrid._rule_chunk(q, k, v, g, beta, s_l, n_valid)
    return _mixer_output(cfg, o, z, lp, u.dtype), s_l, conv_l


def _mixer_step(cfg, u, lp, l, rows, s_pool, conv_pool):
    """The mixer over one token a row, the pools in and out
    (``hybrid._mixer_step``'s signature)."""
    tail = _at(conv_pool, l)[rows]                   # [B, K - 1, C]
    qkv, z, fb = _mixer_project(cfg, u, lp)
    y, tail = causal_conv(qkv, tail, lp.conv_w, None)
    q, k, v, g, beta, z = _mixer_heads(cfg, y, z, fb, lp)
    conv_pool = conv_pool.at[l, rows].set(tail)
    o, s_pool = hybrid._rule_step(l, rows, q, k, v, g, beta, s_pool)
    return _mixer_output(cfg, o, z, lp, u.dtype), s_pool, conv_pool


def _attention(cfg: ModelConfig, h: jax.Array, lp: FullParams, attend):
    """Grouped-query attention over the normed ``h [B, T, dim]``, no
    positions, the heads' output gated a lane; ``attend(q, k, v) -> att``
    owns the cache."""
    B, T, _ = h.shape
    hd = cfg.head_dim
    q = linear(h, lp.wq).reshape(B, T, cfg.n_heads, hd)
    k = linear(h, lp.wk).reshape(B, T, cfg.n_kv_heads, hd)
    v = linear(h, lp.wv).reshape(B, T, cfg.n_kv_heads, hd)
    att = attend(q, k, v).reshape(B, T, cfg.q_dim)
    gate = jax.nn.sigmoid(linear(h, lp.wg).astype(jnp.float32))
    return linear((att.astype(jnp.float32) * gate).astype(h.dtype), lp.wo)


def _walk(params: Params, cfg: ModelConfig) -> hybrid.Walk:
    kda, full, moe = params.layers
    eps = cfg.norm_epsilon

    def ffn(x, _lp, l, stats, live):
        x, st = ffn_half(cfg, x, moe, l, live, False)
        return x, stats + st

    return hybrid.Walk(
        lin=kda, lin_matmuls=_KDA_MATMULS, full=full,
        full_matmuls=_FULL_MATMULS, full_at=cfg.full_layer_at,
        sublayer=lambda x, w, f: x + f(rms_norm(x, w, eps)).astype(x.dtype),
        attention=functools.partial(_attention, cfg), ffn=ffn,
        mixer_chunk=functools.partial(_mixer_chunk, cfg),
        mixer_step=functools.partial(_mixer_step, cfg),
        acc0=functools.partial(zero_stats, cfg))


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: StateColumn,
            n_valid: jax.Array | None = None):
    """``hybrid.chunk_program`` over this family's walk: the column carries
    the chunks' routing counters (``col.stats``)."""
    return hybrid.chunk_program(_walk(params, cfg), params, cfg, tokens,
                                start_pos, col, n_valid)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """``hybrid.step_program`` over this family's walk: ``cache =
    (PagedKVCache, StatePool, totals)``."""
    return hybrid.step_program(_walk(params, cfg), params, cfg, tokens,
                               pos_vec, cache, tables, write_lens)


def _load_params(ld, cfg: ModelConfig) -> Params:
    """The three stacks from the tensors ``mfile._walk_solar_open2_layer``
    names."""
    require_quantized(ld)
    h = ld.h
    every = list(range(h.n_layers))
    full_ids = [l for l in every if l % h.layer_period == h.full_layer_at]
    kda_ids = [l for l in every if l not in full_ids]
    mm = lambda ids, name, o, i: ld.matmul(
        name, o, i, stacked=True, out_axis=None, in_axis=None, layers=ids)
    f32 = lambda ids, name, *shape: ld.stacked_f32(name, *shape, layers=ids)
    H, rank = h.linear_n_value_heads, h.linear_gate_rank
    kdim, vdim = H * h.linear_key_head_dim, H * h.linear_value_head_dim
    decays = H * h.linear_decay_dim
    sh = h.shared_expert_dim
    experts = lambda name, o, i: ld.expert_stack(name, o, i, None, None,
                                                 layers=every)
    return ld.params(SolarLayers(
        kda=KdaParams(
            wq=mm(kda_ids, "block_kda_q", kdim, h.dim),
            wk=mm(kda_ids, "block_kda_k", kdim, h.dim),
            wv=mm(kda_ids, "block_kda_v", vdim, h.dim),
            conv_w=jnp.concatenate([
                f32(kda_ids, "block_kda_conv_" + name, h.linear_conv_kernel,
                    wide)
                for name, wide in (("q", kdim), ("k", kdim), ("v", vdim))],
                axis=-1),
            a_log=f32(kda_ids, "block_kda_a_log", H),
            w_f_down=f32(kda_ids, "block_kda_f_down", rank, h.dim),
            w_f_up=f32(kda_ids, "block_kda_f_up", decays, rank),
            dt_bias=f32(kda_ids, "block_kda_dt_bias", decays),
            w_b=f32(kda_ids, "block_kda_b", H, h.dim),
            w_g_down=f32(kda_ids, "block_kda_g_down", rank, h.dim),
            w_g_up=f32(kda_ids, "block_kda_g_up", vdim, rank),
            norm_o=f32(kda_ids, "block_kda_norm", h.linear_value_head_dim),
            w_out=mm(kda_ids, "block_kda_out", h.dim, vdim),
            norm_att=f32(kda_ids, "block_norm_0", h.dim)),
        full=FullParams(
            wq=mm(full_ids, "block_matmul_q", h.q_dim, h.dim),
            wk=mm(full_ids, "block_matmul_k", h.kv_dim, h.dim),
            wv=mm(full_ids, "block_matmul_v", h.kv_dim, h.dim),
            wo=mm(full_ids, "block_matmul_wo", h.dim, h.q_dim),
            wg=mm(full_ids, "block_matmul_wg", h.q_dim, h.dim),
            norm_att=f32(full_ids, "block_norm_0", h.dim)),
        moe=MoeParams(
            norm_ffn=f32(every, "block_norm_1", h.dim),
            moe_gate=f32(every, "block_moe_gate", h.moe_router_width, h.dim),
            moe_bias=(f32(every, "block_moe_bias", h.moe_router_width)
                      if h.moe_select_bias else None),
            we1=experts("block_expert_w1", h.hidden_dim, h.dim),
            we2=experts("block_expert_w2", h.dim, h.hidden_dim),
            we3=experts("block_expert_w3", h.hidden_dim, h.dim),
            ws1=mm(every, "block_shared_w1", sh, h.dim) if sh else None,
            ws2=mm(every, "block_shared_w2", h.dim, sh) if sh else None,
            ws3=mm(every, "block_shared_w3", sh, h.dim) if sh else None)))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # the Q40 planes HELD: a delta-rule mixer's q k v and out; a full layer's
    # q k v wo and its gate; in every layer the held experts' three planes
    # and the shared one's (the router, the low-rank pairs and the beta rows
    # are float32 rows, not counted); the vocabulary's rows
    H = cfg.lin_heads
    kda = cfg.dim * H * (2 * cfg.lin_key_dim + 2 * cfg.lin_value_dim)
    full = cfg.dim * (3 * cfg.q_dim + 2 * cfg.kv_dim)
    routed = 3 * cfg.dim * (cfg.hidden_dim * cfg.n_experts
                            + cfg.shared_expert_dim)
    return (cfg.n_linear_layers * kda + cfg.n_kv_layers * full
            + cfg.n_layers * routed + cfg.dim * cfg.vocab_size)


def _describe(cfg: ModelConfig, engine) -> str:
    decay = f"a decay a key channel ({cfg.lin_decay_dim} a head)"
    return (f"; layers: {cfg.n_kv_layers} full (gated, no positions, "
            f"{cfg.n_heads}:{cfg.n_kv_heads} heads of {cfg.head_dim}; the "
            f"first of every {cfg.layer_period}), {cfg.n_linear_layers} "
            f"delta-rule ({cfg.lin_heads} heads of {cfg.lin_key_dim} x "
            f"{cfg.lin_value_dim}, {decay}, gates through "
            f"{cfg.lin_gate_rank}); experts behind every mixer: "
            f"{cfg.n_experts} of {cfg.moe_router_width} held from "
            f"{cfg.moe_first_expert}, {cfg.n_active_experts} a token, "
            f"{cfg.hidden_dim} wide, shared {cfg.shared_expert_dim}"
            f"{', selection bias' if cfg.moe_select_bias else ''}")


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=None,
    column=StateColumn.zeros,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(linear=cfg.n_linear_layers,
                                        full=cfg.n_kv_layers,
                                        moe=cfg.n_layers),
    describe=_describe,
    refusal=state_refusal(
        "a decoder of delta-rule layers with a decay a key channel beside "
        "gated full attention, routed experts behind both (a recurrent "
        "state in the state pool, routing counters beside it; the period "
        "scan has no mesh plan yet)"))
