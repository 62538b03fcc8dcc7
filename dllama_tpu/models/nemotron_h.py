"""A decoder whose every layer is ONE pre-norm block, and which one is data:
an SSD (Mamba-2) mixer, grouped-query attention without positions, or a
routed feed-forward of ungated experts in a latent space beside a shared one
(``ArchType.NEMOTRON_H``; Nemotron 3 Super 120B-A12B is 88 such layers, 40 /
8 / 40, in a pattern that is NOT periodic over its length).

**The equations.** ``x <- x + Block_l(rmsnorm(x; w_l))``, a final RMS norm,
an untied head; no bias but the convolution's. ``cfg.layer_pattern[l]``:

* ``M``: the SSD mixer of ``models/ssd_mixer.py`` (what ``models/falcon_h1.py``
  runs beside its attention), every multiplier 1.
* ``*``: ``q, k, v = W_q u, W_k u, W_v u``, causal softmax at ``head_dim **
  -0.5``, ``W_o``. NO positional embedding: the mixers carry order, and no
  rotary table is built.
* ``E``: the routed feed-forward of ``models/share.py`` with a latent
  (``cfg.moe_latent_dim``), ungated experts (two planes, ``hidden_act``
  relu2), a sigmoid router over the layer's input whose
  selection alone takes the learned bias, and an ungated shared expert over
  the layer's input.

**Three stacks** (:class:`MixerParams`, :class:`AttnParams` and the routed
leaves as ``models/share.py`` names them), each over its own layers of the
pattern; layer ``l`` is entry :func:`stack_indices` ``[l]`` of its kind's.
Every Q40 plane stays whole and reaches ``linear`` as stack + index, every
expert stack the routed kernels as stack + layer.

**The walk** (:func:`_walk`) takes any string over ``M * E``. The pattern is
cut into RUNS, the maximal repeats of a unit of one or two kinds
(:func:`pattern_runs`: ``EMEMEMEMEM*`` is ``EM`` five times, then ``*``), a
run of more than one repeat a ``fori_loop`` over ONE traced body of its unit;
where the list of runs is itself whole repeats of a shorter list
(:func:`fold_runs`), those are one ``scan``. Two whole periods of
``EMEMEMEMEM*`` are therefore one scan over two steps of (a loop of five
pairs, one attention layer): three traced layer bodies. The published 88
layers are 19 runs that fold no further. Everything a slot's context is made
of rides every loop's carry whole and is written in place, as in
``models/lfm2.py``: K/V of the attention layers (a column ``[n_attn, 1, n_kv,
S, hd]`` in prefill, the paged pool afterwards), the mixer layers' float32
state and convolution tail (a :class:`~dllama_tpu.runtime.kvblocks.StateColumn`'s
or the :class:`~dllama_tpu.runtime.kvblocks.StatePool`), and the routing
counters.

**What another family's equation may add** (``models/granite_hybrid.py``
walks these same blocks two a published layer): ``cfg.mult.residual`` on
every block's output where it joins the stream, ``cfg.mult.embedding`` on the
embedded rows and ``cfg.mult.lm_head`` on the logits, the score's scale
(``cfg.score_dim``, read by ``llama._attend_*``), gated experts and a gated
shared one (the stack's ``we3`` / ``ws3``), a head that IS the embedding. A
multiplier of 1 is not traced as a multiply: this family's programs lower to
the text they lowered to before the other came.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.linear import Weight, linear
from ..ops.norms import rms_norm
from ..parallel.api import current_plan
from ..runtime.kvblocks import StateColumn
from .config import ModelConfig
from .family import Family, layer_kinds, state_refusal
from .llama import (Params, _at, _attend_dense, _attend_paged, _live_rows,
                    _put, _stack_at, _state_rows)
from .share import (require_quantized, routed_ffn, widen_experts,
                    zero_stats)
from .ssd_mixer import mixer_chunk, mixer_step


class MixerParams(NamedTuple):
    """The ``M`` layers' blocks, stacked over them in the model's order
    (``models/ssd_mixer.py`` names the mixer's leaves)."""

    w_in: Weight          # [NM, ssm_in_dim, dim]: the z x B C rows, packed
    w_dt: jax.Array       # [NM, H, dim] float32: the dt rows
    conv_w: jax.Array     # [NM, K, ssm_conv_dim]
    conv_b: jax.Array     # [NM, ssm_conv_dim]
    a_log: jax.Array      # [NM, H]
    d_skip: jax.Array     # [NM, H]
    dt_bias: jax.Array    # [NM, H]
    norm_ssm: jax.Array   # [NM, ssm_inner_dim]: the gated norm's weight
    w_out: Weight         # [NM, dim, ssm_inner_dim]
    norm: jax.Array       # [NM, dim]: the block's norm


class AttnParams(NamedTuple):
    """The ``*`` layers' blocks."""

    wq: Weight            # [NA, q_dim, dim]
    wk: Weight            # [NA, kv_dim, dim]
    wv: Weight
    wo: Weight            # [NA, dim, q_dim]
    norm: jax.Array       # [NA, dim]


_MIXER_MATMULS = ("w_in", "w_out")
_ATTN_MATMULS = ("wq", "wk", "wv", "wo")


class NemotronHLayers(NamedTuple):
    """``Params.layers``: the two mixer stacks and the ``E`` layers' leaves,
    named as ``models/share.py`` reads them (two planes an expert: no
    ``we3``, no ``ws3``; three where the experts are gated,
    models/granite_hybrid.py)."""

    mixer: MixerParams
    attn: AttnParams
    norm_moe: jax.Array          # [NE, dim]: the block's norm
    moe_gate: jax.Array          # [NE, router_width, dim] float32
    moe_bias: jax.Array | None   # [NE, router_width] float32: the selection's
    w_lat_in: Weight | None      # [NE, latent, dim]
    w_lat_out: Weight | None     # [NE, dim, latent]
    we1: Weight                  # [NE, held, latent, wide] (up)
    we2: Weight                  # [NE, held, wide, latent] (down): ``wide``
                                 # is cfg.expert_width_held, zero lanes
                                 # behind ``hidden_dim``
    ws1: Weight | None           # [NE, shared, dim]
    ws2: Weight | None           # [NE, dim, shared]
    we3: Weight | None = None    # [NE, held, latent, wide]: gated experts'
    ws3: Weight | None = None    # [NE, shared, dim]: a gated shared one's


def stack_indices(pattern: tuple[str, ...] | str) -> np.ndarray:  # dlint: static-fn
    """Layer ``l``'s index within its own kind's stack."""
    seen: dict[str, int] = {}
    out = []
    for kind in pattern:
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return np.asarray(out, np.int32)


def pattern_runs(pattern: tuple[str, ...] | str
                 ) -> list[tuple[str, int]]:  # dlint: static-fn
    """``pattern`` as runs ``(unit, repeats)``: at each layer the unit of two
    different kinds where it stands twice or more in a row, else the layer's
    own kind as often as it repeats."""
    p = "".join(pattern)
    runs, i = [], 0
    while i < len(p):
        unit = p[i:i + 2]
        n = 1
        if len(unit) == 2 and unit[0] != unit[1]:
            while p[i + 2 * n:i + 2 * n + 2] == unit:
                n += 1
        if n == 1:
            unit = p[i]
            while p[i + n:i + n + 1] == unit:
                n += 1
        runs.append((unit, n))
        i += len(unit) * n
    return runs


def fold_runs(runs: list[tuple[str, int]]
              ) -> tuple[list, int]:  # dlint: static-fn
    """``(period, repeats)``: the shortest list of runs whose whole repeats
    are ``runs``."""
    for size in range(1, len(runs) + 1):
        if len(runs) % size == 0 and runs == runs[:size] * (len(runs) // size):
            return runs[:size], len(runs) // size
    return runs, 1


def _check(cfg: ModelConfig) -> None:
    if current_plan() is not None:
        raise ValueError("a decoder of one block a layer in a pattern (SSD "
                         "mixer, attention, routed feed-forward) has no mesh "
                         "plan (tp/sp/pp/dp > 1) yet")
    if cfg.sync_q80 or cfg.offload:
        raise ValueError("a decoder of one block a layer in a pattern "
                         "supports neither Q80 sync emulation nor offloaded "
                         "weights")


def _walk(cfg: ModelConfig, carry, blocks: dict):
    """``carry`` through every layer of ``cfg.layer_pattern``;
    ``blocks[kind](carry, i) -> carry`` is entry ``i`` of that kind's
    stack (module docstring, "The walk")."""
    index = jnp.asarray(stack_indices(cfg.layer_pattern))
    period, repeats = fold_runs(pattern_runs(cfg.layer_pattern))
    period_layers = sum(len(unit) * n for unit, n in period)

    def unit_at(carry, l, unit):
        for t, kind in enumerate(unit):
            carry = blocks[kind](carry, index[l + t])
        return carry

    def one_period(carry, base):
        at = 0
        for unit, n in period:
            if n == 1:
                carry = unit_at(carry, base + at, unit)
            else:
                carry = jax.lax.fori_loop(
                    0, n, lambda j, c, at=at, unit=unit: unit_at(
                        c, base + at + j * len(unit), unit), carry)
            at += len(unit) * n
        return carry

    if repeats == 1:
        return one_period(carry, jnp.int32(0))
    carry, _ = jax.lax.scan(
        lambda c, p: (one_period(c, p * period_layers), None), carry,
        jnp.arange(repeats, dtype=jnp.int32))
    return carry


def _attn_block(cfg: ModelConfig, u: jax.Array, ap: AttnParams, attend):
    """Grouped-query attention over ``u [B, T, dim]`` (normed), no
    positions; ``attend(q, k, v) -> att`` owns the cache."""
    B, T, _ = u.shape
    hd = cfg.head_dim
    q = linear(u, ap.wq).reshape(B, T, cfg.n_heads, hd)
    k = linear(u, ap.wk).reshape(B, T, cfg.n_kv_heads, hd)
    v = linear(u, ap.wv).reshape(B, T, cfg.n_kv_heads, hd)
    return linear(attend(q, k, v).reshape(B, T, cfg.q_dim), ap.wo)


def _run_layers(params: Params, cfg: ModelConfig, x, caches, stats, live,
                mixer, attend):
    """The stack both programs share. ``caches = (k, v, s, conv)`` (a
    column's arrays, or the pools') and ``stats`` ride every loop's carry
    whole. ``mixer(u, mp, i, s, conv) -> (y, s, conv)`` is mixer layer
    ``i`` in the program's form, ``attend(q, k, v, k_c, v_c, i) -> (att, k_c,
    v_c)`` attention layer ``i``'s cache."""
    lp: NemotronHLayers = params.layers
    eps = cfg.norm_epsilon
    r = cfg.mult.residual
    # a block's output joins the stream under the residual multiplier; 1 is
    # not traced
    scaled = (lambda y: y) if r == 1.0 else (lambda y: y * r)

    def mixer_block(carry, i):
        x, (k_c, v_c, s, conv), stats = carry
        mp = _stack_at(lp.mixer, i, _MIXER_MATMULS)
        y, s, conv = mixer(rms_norm(x, mp.norm, eps), mp, i, s, conv)
        return x + scaled(y).astype(x.dtype), (k_c, v_c, s, conv), stats

    def attn_block(carry, i):
        x, (k_c, v_c, s, conv), stats = carry
        ap = _stack_at(lp.attn, i, _ATTN_MATMULS)
        box = {}

        def att(q, k, v):
            out, box["k"], box["v"] = attend(q, k, v, k_c, v_c, i)
            return out

        x = x + scaled(_attn_block(cfg, rms_norm(x, ap.norm, eps), ap, att))
        return x, (box["k"], box["v"], s, conv), stats

    def moe_block(carry, i):
        x, caches, stats = carry
        y, st = routed_ffn(cfg, rms_norm(x, _at(lp.norm_moe, i), eps), lp, i,
                           live)
        return x + scaled(y), caches, stats + st

    x, caches, stats = _walk(cfg, (x, caches, stats),
                             {"M": mixer_block, "*": attn_block,
                              "E": moe_block})
    x = rms_norm(x, params.final_norm, eps)
    logits = linear(x, params.logits, out_axis="vocab").astype(jnp.float32)
    if cfg.mult.lm_head != 1.0:
        logits = logits * cfg.mult.lm_head
    return logits, caches, stats


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    """The embedded rows at the compute dtype, under the embedding's
    multiplier where the equation has one (float32 in between, as
    models/falcon_h1.py has it)."""
    x = params.embedding[tokens]
    if cfg.mult.embedding != 1.0:
        x = x.astype(jnp.float32) * cfg.mult.embedding
    return x.astype(cfg.compute_dtype)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: StateColumn,
            n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a gathered
    column: float32 logits ``[B, T, vocab]`` and the column, advanced by the
    chunk's first ``n_valid`` positions (absent: all ``T``). Positions at or
    past ``n_valid`` are padding: ``dt = 0`` there (the state's update is the
    identity), they never enter a tail, they are not routed, and their K/V
    rows are overwritten later."""
    _check(cfg)
    start_pos = jnp.asarray(start_pos, dtype=jnp.int32)
    if start_pos.ndim:
        raise ValueError("a recurrent state's chunk form takes one start "
                         "position (the dense slot pool's ragged rows are "
                         "not carried to it)")
    B, T = tokens.shape
    n_valid = jnp.asarray(T if n_valid is None else n_valid, jnp.int32)
    live = jnp.tile(jnp.arange(T) < n_valid, B)
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(
        start_pos + jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    def mixer(u, mp, i, s, conv):
        y, s_i, conv_i = mixer_chunk(cfg, u, mp, _at(s, i), _at(conv, i),
                                     n_valid)
        return y, _put(s, s_i, i), _put(conv, conv_i, i)

    def attend(q, k, v, k_c, v_c, i):
        att, k_i, v_i = _attend_dense(cfg, q, k, v, _at(k_c, i), _at(v_c, i),
                                      start_pos, positions)
        return att, _put(k_c, k_i, i), _put(v_c, v_i, i)

    logits, (k, v, s, conv), stats = _run_layers(
        params, cfg, x, (col.k, col.v, col.s, col.conv), col.stats, live,
        mixer, attend)
    return logits, StateColumn(k=k, v=v, s=s, conv=conv, stats=stats)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """The decode step over the K/V pool, the state pool and the routing
    counters: ``tokens [B, 1]`` at per-row ``pos_vec``, ``cache =
    (PagedKVCache, StatePool, totals)``, all given back (the pools written in
    place, the step's counters added to row 0 of ``totals``). Row ``b`` is
    slot ``b``: its state is row ``b + 1`` of the pool, or the null row 0
    while its block table is all null (such a row is not routed)."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("a recurrent state's step form takes one token a "
                         "row: a speculative verify's rejected drafts "
                         "cannot be rolled back out of it")
    pkv, pool, totals = cache
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    live = _live_rows(tables)
    rows = _state_rows(live)
    x = _embed(params, cfg, tokens)

    def mixer(u, mp, i, s, conv):
        return mixer_step(cfg, u, mp, i, rows, s, conv)

    def attend(q, k, v, k_pool, v_pool, i):
        return _attend_paged(cfg, q, k, v, k_pool, v_pool, i, positions,
                             tables)

    logits, (k, v, s, conv), stats = _run_layers(
        params, cfg, x, (pkv.k, pkv.v, pool.s, pool.conv), zero_stats(cfg),
        live, mixer, attend)
    return logits, (PagedKVCache(k=k, v=v), StatePool(s=s, conv=conv),
                    totals.at[0].add(stats))


def _load_params(ld, cfg: ModelConfig, gated: bool = False) -> Params:
    """From the tensors ``mfile._walk_nemotron_h_layer`` names: three stacks
    by layer kind, each over its own layers of the pattern. ``gated``: the
    experts and the shared one have a third plane (``we3`` / ``ws3``,
    models/granite_hybrid.py)."""
    require_quantized(ld)
    h = ld.h
    m_ids, a_ids, e_ids = (h.pattern_layers(kind) for kind in "M*E")
    mm = lambda ids, name, o, i: ld.matmul(
        name, o, i, stacked=True, out_axis=None, in_axis=None, layers=ids)
    f32 = lambda ids, name, *shape: ld.stacked_f32(name, *shape, layers=ids)
    lat = h.moe_latent_dim or h.dim
    wide = h.shared_expert_dim
    held = cfg.expert_width_held

    def experts(name, o, i, axis):
        """The held experts' planes of one projection, their hidden axis
        (``axis`` of a plane ``[in, out]``) padded with zero codes and scales
        to ``cfg.expert_width_held``."""
        return widen_experts(
            ld.expert_stack(name, o, i, None, None, layers=e_ids), axis,
            h.hidden_dim, held)

    return ld.params(NemotronHLayers(
        mixer=MixerParams(
            w_in=mm(m_ids, "block_ssm_in", h.ssm_in_dim, h.dim),
            w_dt=f32(m_ids, "block_ssm_dt", h.ssm_n_heads, h.dim),
            conv_w=f32(m_ids, "block_ssm_conv", h.ssm_conv_kernel,
                       h.ssm_conv_dim),
            conv_b=f32(m_ids, "block_ssm_conv_bias", h.ssm_conv_dim),
            a_log=f32(m_ids, "block_ssm_a_log", h.ssm_n_heads),
            d_skip=f32(m_ids, "block_ssm_d", h.ssm_n_heads),
            dt_bias=f32(m_ids, "block_ssm_dt_bias", h.ssm_n_heads),
            norm_ssm=f32(m_ids, "block_ssm_norm", h.ssm_inner_dim),
            w_out=mm(m_ids, "block_ssm_out", h.dim, h.ssm_inner_dim),
            norm=f32(m_ids, "block_norm_0", h.dim)),
        attn=AttnParams(
            wq=mm(a_ids, "block_matmul_q", h.q_dim, h.dim),
            wk=mm(a_ids, "block_matmul_k", h.kv_dim, h.dim),
            wv=mm(a_ids, "block_matmul_v", h.kv_dim, h.dim),
            wo=mm(a_ids, "block_matmul_wo", h.dim, h.q_dim),
            norm=f32(a_ids, "block_norm_0", h.dim)),
        norm_moe=f32(e_ids, "block_norm_0", h.dim),
        moe_gate=f32(e_ids, "block_moe_gate", h.moe_router_width, h.dim),
        moe_bias=(f32(e_ids, "block_moe_bias", h.moe_router_width)
                  if h.moe_select_bias else None),
        w_lat_in=(mm(e_ids, "block_latent_in", lat, h.dim)
                  if h.moe_latent_dim else None),
        w_lat_out=(mm(e_ids, "block_latent_out", h.dim, lat)
                   if h.moe_latent_dim else None),
        we1=experts("block_expert_w1", h.hidden_dim, lat, -1),
        we2=experts("block_expert_w2", lat, h.hidden_dim, -2),
        ws1=mm(e_ids, "block_shared_w1", wide, h.dim) if wide else None,
        ws2=mm(e_ids, "block_shared_w2", h.dim, wide) if wide else None,
        we3=experts("block_expert_w3", h.hidden_dim, lat, -1) if gated
        else None,
        ws3=(mm(e_ids, "block_shared_w3", wide, h.dim) if gated and wide
             else None)))


def _matmul_weight_count(cfg: ModelConfig) -> int:
    # a mixer's packed in-projection and out-projection (its dt rows are a
    # small float32 plane, not counted); q k v wo; the held experts' two
    # planes in the latent's width with the router over its whole width, the
    # two latent projections and the shared expert's two planes
    lat = cfg.moe_latent_dim or cfg.dim
    mixer = cfg.dim * (cfg.ssm_in_dim + cfg.ssm_inner_dim)
    attn = 2 * cfg.dim * (cfg.q_dim + cfg.kv_dim)
    routed = (cfg.dim * cfg.moe_router_width
              + 2 * lat * cfg.expert_width_held * cfg.n_experts
              + (2 * cfg.dim * lat if cfg.moe_latent_dim else 0)
              + 2 * cfg.dim * cfg.shared_expert_dim)
    return (cfg.n_state_layers * mixer + cfg.n_kv_layers * attn
            + cfg.n_moe_layers * routed + cfg.dim * cfg.vocab_size)


def pattern_words(cfg: ModelConfig) -> str:  # dlint: static-fn
    """The pattern and how the walk cuts it, for a start-up line:
    ``EMEM*EMEM* = 2 x [(EM)x2 *]``."""
    period, repeats = fold_runs(pattern_runs(cfg.layer_pattern))
    runs = " ".join(unit if n == 1 else f"({unit})x{n}" for unit, n in period)
    return f"{''.join(cfg.layer_pattern)} = {repeats} x [{runs}]"


def _describe(cfg: ModelConfig, engine) -> str:
    return (f"; layers: {pattern_words(cfg)}"
            f": {cfg.n_state_layers} SSD mixers ({cfg.ssm_heads} heads of "
            f"{cfg.ssm_head_dim} in {cfg.ssm_groups} groups, state "
            f"{cfg.ssm_state_dim}), {cfg.n_kv_layers} attention without "
            f"positions ({cfg.n_heads}:{cfg.n_kv_heads} heads of "
            f"{cfg.head_dim}), {cfg.n_moe_layers} routed; experts: "
            f"{cfg.n_experts} of {cfg.moe_router_width} held from "
            f"{cfg.moe_first_expert}, {cfg.n_active_experts} a token, "
            f"{cfg.hidden_dim} wide (held in {cfg.expert_width_held}) in a "
            f"latent of {cfg.moe_latent_dim or cfg.dim}, shared "
            f"{cfg.shared_expert_dim}"
            f"{', selection bias' if cfg.moe_select_bias else ''}")


FAMILY = Family(
    forward=forward,
    paged_forward=paged_forward,
    tick=None,
    column=StateColumn.zeros,
    load_params=_load_params,
    matmul_weight_count=_matmul_weight_count,
    layer_kinds=lambda cfg: layer_kinds(mamba=cfg.n_state_layers,
                                        attention=cfg.n_kv_layers,
                                        moe=cfg.n_moe_layers),
    describe=_describe,
    refusal=state_refusal(
        "a decoder of one block a layer in a pattern (an SSD mixer's "
        "recurrent state in the state pool, routing counters beside it; the "
        "three-stack walk has no mesh plan yet)"))
