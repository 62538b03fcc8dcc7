"""The routed feed-forward of which this chip may hold a SHARE: what
``models/laguna.py`` and ``models/axk1.py`` both run behind their attention
halves.

**The share is data.** The router always scores ``moe_router_width`` experts
and takes ``n_active_experts``; the planes hold ``n_experts`` of them, from
``moe_first_expert``. A (row, expert) pair whose expert is not held, or whose
row is dead or padding, is not computed: both forms sort the pairs by held
expert, the absent ones last. Which form a dispatch takes is
:func:`step_form`'s: static, from the configuration and the rows. The decode
form (:func:`_sorted_pairs`) compacts the held ones to the front
and :func:`~dllama_tpu.ops.expert_gemv.expert_gemv` loops over those alone,
a plane a PAIR; the chunk form (:func:`_experts_chunk`) runs each of the
three projections as one grouped kernel,
:func:`~dllama_tpu.ops.expert_chunk.expert_chunk`: a plane fetched and
dequantized once a RUN of pairs that share its expert and multiplied with
that run's rows alone, in tiles, an expert nobody chose not touched, the
pairs' weighted rows added back per token. Off a TPU and under a mesh plan
the chunk form is the every-row one through ``linear``
(:func:`_experts_chunk_xla`: every chosen held expert over EVERY row, the
rows that did not choose it weighted 0; the grouped kernel's oracle). What
the absent experts would have added is left out, and that partial sum goes
on to the next layer: on one chip the layer runs without its exchange. With
every expert held the same code is the whole layer.

**The router** (:func:`route`) takes its score function (``cfg.moe_score``:
a softmax or a sigmoid over the whole width, float32) and its group limit
(``cfg.moe_n_group`` groups of which a token's experts come from the
``cfg.moe_topk_group`` best, a group's score the sum of its ``n_active /
topk_group`` largest) from the configuration.

**Counters**: ``stats`` = (pairs computed here, pairs that fell on absent
experts, rows the chunk form fed its planes (with the grouped kernel the
pairs rounded up to whole tiles a run: over the pairs, what the tiling
pads), PLANES: the distinct held experts a layer's rows chose, which is what
a kernel that fetches a plane once a run of pairs reads (the pairs over
them say how many times the decode kernel, a plane a pair, reads each),
tokens each held expert saw), summed over the layers, accumulated on
the device and given back with the pools.

**An expert's form is data too**: gated (``W2 (act(W1 x) * W3 x)``, three
planes) or not (``W2 act(W1 x)``, two: the stack's ``we3`` / ``ws3`` are
None), its activation ``cfg.hidden_act``. With a LATENT
(``cfg.moe_latent_dim``: the stack's ``w_lat_in w_lat_out``) the experts live
in that width: ``w_lat_in`` projects the layer's input down in front of the
dispatch, ``w_lat_out`` the weighted sum of the held experts back up behind
it (linear, so a share's partial sum is projected as the whole would be: the
chips of a layer would exchange rows of the latent's width); the router and
the shared expert read the layer's input at the model's width.

A layer stack that uses these functions names its leaves ``norm_ffn``, ``w1
w2 w3`` (the leading dense layers'), ``moe_gate``, ``we1 we2 we3``, ``ws1 ws2
ws3``, the routed ones stacked over the ``n_moe_layers`` that have one, and
``w_lat_in w_lat_out`` where it has a latent.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import expert_chunk as ec
from ..ops import expert_gemv as eg
from ..ops.linear import (LayerSlice, QuantizedWeight, Weight, _fast_mode,
                          linear)
from ..ops.norms import rms_norm
from ..ops.quant_matmul import FUSED_MAX_M
from ..runtime.introspection import note_q40_path
from .config import ModelConfig
from .llama import _hidden_act

_HIGHEST = jax.lax.Precision.HIGHEST
# rows up to which a dispatch MAY take the decode form (a plane a PAIR);
# wider ones take the chunk form (a plane a RUN of pairs): the dense kernels'
# own boundary between their decode and chunk regimes
STEP_FORM_MAX_ROWS = FUSED_MAX_M
# entries of ``stats`` in front of the tokens a held expert: held pairs,
# absent pairs, rows fed, planes
N_COUNTS = 4


def step_form(cfg: ModelConfig, rows: int) -> bool:  # dlint: static-fn
    """Whether a dispatch of ``rows`` takes the decode form (a plane a PAIR,
    ``expert_gemv``) and not the chunk form (a plane a RUN of pairs,
    ``expert_chunk``). Static, from the configuration and the program's rows:
    up to :data:`STEP_FORM_MAX_ROWS`, and only while the pairs the rows choose
    do not outnumber the experts they choose among (``rows n_active <=
    moe_router_width``; with a share held both sides scale by it). Past that
    a plane is chosen more than once on average and the pair form reads it
    once a PAIR: 13 rows at ten of 72 are 130 pairs over some 62 planes, 13 GB
    a step over ten layers where the run form reads 6.2 (PERF.md, PR 54, has
    both measured). Sixteen rows at ten of 256 (laguna), eight of 192
    (A.X-K1), four of 64 (lfm2) and 22 of 512 (nemotron_h) stay on the pair
    form."""
    return (rows <= STEP_FORM_MAX_ROWS
            and rows * cfg.n_active_experts <= cfg.moe_router_width)


def widen_experts(stack: Weight, axis: int, hidden: int, held: int) -> Weight:
    """A held expert stack's planes with their hidden axis (``axis`` of a
    plane ``[in, out]``: -1 where it is the output, -2 the input) padded from
    ``hidden`` lanes with zero codes and scales to ``held``
    (``cfg.expert_width_held``)."""
    if held == hidden:
        return stack

    def widen(a, to):
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, to - a.shape[axis])
        return jnp.pad(a, pad)

    return type(stack)(scales=widen(stack.scales,
                                    held if axis == -1 else held // 32),
                       codes=widen(stack.codes, held))


def _plane(w: Weight, l) -> Weight:
    """Entry ``l`` of a stacked 2-D matmul weight: stack + index for a Q40
    plane (the fused kernel reads it where it lies, llama._layer_at), the
    slice otherwise."""
    if isinstance(w, QuantizedWeight):
        return LayerSlice(w, l)
    return jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False)


def zero_stats(cfg: ModelConfig) -> jax.Array:
    """One dispatch's routing counters: held pairs, absent pairs, rows the
    chunk form fed the planes, distinct held experts chosen a layer, tokens
    a held expert."""
    return jnp.zeros((N_COUNTS + cfg.n_experts,), jnp.int32)


def zero_totals(cfg: ModelConfig) -> jax.Array:
    """The generator's running totals beside its pools: row 0 what the
    decode steps added, row 1 what the prefill chunks did (kept apart so
    that a step's own pairs can be read off after it)."""
    return jnp.zeros((2, N_COUNTS + cfg.n_experts), jnp.int32)


def require_quantized(ld) -> None:
    """A routed family's loader (runtime/weights.StreamingLoader ``ld``)
    refuses a file whose expert stacks would load dense."""
    if not ld.quantized:
        raise ValueError(
            f"a {ld.h.arch_type.name} file's matmul planes must be Q40 or "
            f"Q80: the routed decode kernel (ops/expert_gemv.py) and its "
            f"XLA form read quantized expert stacks")


def swiglu(cfg: ModelConfig, h: jax.Array, w1, w2, w3) -> jax.Array:
    """A feed-forward over planes read whole: ``W2 (act(W1 h) * W3 h)``, or
    the ungated ``W2 act(W1 h)`` where ``w3`` is None."""
    gate = _hidden_act(cfg, linear(h, w1))
    return linear(gate if w3 is None else gate * linear(h, w3), w2)


def _gate_up(cfg: ModelConfig, project, x: jax.Array, lp) -> jax.Array:
    """``act(W1 x) * W3 x``, or ``act(W1 x)`` for ungated experts (a stack
    without ``we3``), through ``project(x, stack)`` (either form's kernel
    over one projection)."""
    a = _hidden_act(cfg, project(x, lp.we1))
    return a if lp.we3 is None else a * project(x, lp.we3)


def route(cfg: ModelConfig, h: jax.Array, gate: jax.Array,
          bias: jax.Array | None = None):
    """The router over its whole width, float32: ``(weights [N, k], experts
    [N, k])`` for ``h [N, dim]``. Scores are ``cfg.moe_score`` of the logits;
    with ``cfg.moe_n_group`` groups the choice is limited to the
    ``cfg.moe_topk_group`` groups whose ``k / topk_group`` largest scores sum
    highest (ties go to the lower index, as ``lax.top_k`` breaks them);
    weights are the chosen scores, renormalised over the chosen where
    ``moe_norm_topk`` (``cfg.moe_norm_eps`` added to their sum) and scaled
    by ``moe_routed_scale``. ``bias [width]`` (a layer's learned selection
    bias, ``cfg.moe_select_bias``) enters the CHOICE only: the experts are
    the ``top_k`` of ``scores + bias``, their weights the scores alone."""
    logits = jnp.einsum("nd,ed->ne", h.astype(jnp.float32),
                        gate.astype(jnp.float32), precision=_HIGHEST)
    scores = (jax.nn.sigmoid(logits) if cfg.moe_score == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    k, G = cfg.n_active_experts, cfg.moe_n_group
    if G > 1:
        if bias is not None:
            raise ValueError("a selection bias under a group limit is not "
                             "carried")
        N, W = scores.shape
        per_group = jax.lax.top_k(scores.reshape(N, G, W // G),
                                  k // cfg.moe_topk_group)[0].sum(axis=-1)
        _, best = jax.lax.top_k(per_group, cfg.moe_topk_group)
        allowed = jnp.zeros((N, G), bool).at[
            jnp.arange(N)[:, None], best].set(True)
        limited = jnp.where(jnp.repeat(allowed, W // G, axis=1), scores,
                            -jnp.inf)
        top, idx = jax.lax.top_k(limited, k)
    elif bias is not None:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        top, idx = jax.lax.top_k(scores, k)
    if cfg.moe_norm_topk:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total + cfg.moe_norm_eps if cfg.moe_norm_eps else total)
    return top * cfg.moe_routed_scale, idx


def routed_pairs(cfg: ModelConfig, idx: jax.Array, live: jax.Array):
    """Of the (row, expert) pairs ``idx [N, k]``, flattened row-major:
    ``local [N k]`` the expert's index among those held (``n_experts`` where
    it is absent or the row is not ``live [N]``), and ``stats`` (held
    pairs, absent pairs of live rows, 0 for the rows fed, which the chunk
    form fills in, the distinct held experts chosen, tokens a held
    expert)."""
    E = cfg.n_experts
    local = idx - cfg.moe_first_expert
    here = (local >= 0) & (local < E)
    held = (here & live[:, None]).reshape(-1)
    local = jnp.where(held, local.reshape(-1), E)
    absent = jnp.sum(~here & live[:, None])
    tokens = jnp.bincount(local, length=E + 1)[:E]
    stats = jnp.concatenate([
        jnp.stack([jnp.sum(held), absent, 0, jnp.sum(tokens > 0)]),
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
    a = _gate_up(cfg, gemv, x[rows], lp)
    y = gemv(a.astype(x.dtype), lp.we2) * w[:, None]
    return jnp.zeros(x.shape, jnp.float32).at[rows].add(y)


def _experts_chunk_xla(cfg: ModelConfig, x: jax.Array, local, weights, m, lp):
    """The chunk form off a TPU and under a mesh plan, and the grouped
    kernel's oracle: every held expert that some row chose, over EVERY row
    of the chunk, through ``linear`` (its plane read where it lies in the
    ``[NM, held, in, out]`` stack: entry ``m held + e``), the rows that did
    not choose it weighted 0: ``held / k`` times the pairs' FLOPs. Also the
    rows it fed the planes: the chunk's, once a chosen expert."""
    E = cfg.n_experts
    N, k = weights.shape
    flat = lambda we: QuantizedWeight(*(a.reshape((-1,) + a.shape[2:])
                                        for a in we))
    f1, f2 = flat(lp.we1), flat(lp.we2)
    f3 = None if lp.we3 is None else flat(lp.we3)
    # [N, held]: row n's router weight for held expert e, 0 where it did
    # not choose it (or is not live: ``local`` reads ``held`` there)
    w = jnp.zeros((N, E + 1), jnp.float32).at[
        jnp.arange(N)[:, None], local.reshape(N, k)].add(weights)[:, :E]
    chosen = jnp.bincount(local, length=E + 1)[:E]

    def expert(e, y):
        def some(y):
            i = m * E + e
            out = swiglu(cfg, x, LayerSlice(f1, i), LayerSlice(f2, i),
                         None if f3 is None else LayerSlice(f3, i))
            return y + out.astype(jnp.float32) * jax.lax.dynamic_slice_in_dim(
                w, e, 1, axis=1)

        return jax.lax.cond(chosen[e] > 0, some, lambda y: y, y)

    y = jax.lax.fori_loop(0, E, expert, jnp.zeros(x.shape, jnp.float32))
    return y, N * jnp.sum(chosen > 0)


def _runs(cfg: ModelConfig, local: jax.Array, tm: int):
    """The RUNS of the sorted held pairs ``local [N k]``, what
    :func:`~dllama_tpu.ops.expert_chunk.expert_chunk` loops over: ``(n_runs,
    expert, tile0, pair0, length)``, the chosen held experts ascending, each
    with the first of its whole tiles of ``tm`` rows in the fed layout, the
    first of its pairs among the sorted and how many it has; and the rows
    fed, pairs rounded up to whole tiles a run."""
    E = cfg.n_experts
    counts = jnp.bincount(local, length=E + 1)[:E].astype(jnp.int32)
    tiles = (counts + tm - 1) // tm
    order = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)
    tile_end, pair_end = jnp.cumsum(tiles), jnp.cumsum(counts)
    runs = (jnp.sum(counts > 0), order, (tile_end - tiles)[order],
            (pair_end - counts)[order], counts[order])
    return runs, tile_end[-1] * tm


# dlint: static-fn
def _chunk_pieces(cfg: ModelConfig, x: jax.Array, N: int, k: int, lp):
    """``(rows a piece, kernel kwargs)`` for a chunk of ``N`` rows: the
    whole chunk where the grouped kernel's gate takes it, else the largest
    halving of it (down to a tile of rows) that the gate does take: the
    fed layout's static bound ``fed_rows(rows min(k, held), held)`` grows
    with ``k`` and the held experts, and at 22 of 128 held a chunk of 128
    rows is past the kernel's VMEM budget where one of 64 is not. None
    where no piece passes (off a TPU, under a plan)."""
    E = cfg.n_experts
    fast = _fast_mode(x) or lp.we1.scales.dtype == jnp.bfloat16
    rows = N
    while True:
        F = ec.fed_rows(rows * min(k, E), E)
        kw = ec.kernel_choice(rows, F, lp.we1, fast, False, x.dtype.itemsize)
        if kw is not None and ec.kernel_choice(
                rows, F, lp.we2, fast, True, x.dtype.itemsize) is not None:
            return rows, kw
        if rows % 2 or rows // 2 < ec.TILE_ROWS:
            return None
        rows //= 2


def _experts_chunk(cfg: ModelConfig, x: jax.Array, local, weights, m, lp):
    """The chunk form, and the rows it fed the planes: the held pairs sorted
    by expert, each of the three projections ONE grouped kernel over the
    held stack read where it lies (``expert_chunk``: a plane fetched and
    dequantized once a RUN of pairs that share it, multiplied with that
    run's rows in tiles, an expert nobody chose not touched), the pairs'
    weighted results added to their token's row in the sorted order, a
    token's experts ascending; float32 between the projections and the
    gate-times-up product rounded once to the activation dtype, as
    :func:`_experts_step` has it. Between the projections the pairs live in
    the FED layout (a run owns whole tiles), at its static bound
    ``fed_rows(N min(k, held), held)``: the gate-times-up product there and
    the copies of those arrays in and out of the kernels' VMEM are the work
    that the bound, not the pairs, sizes. A chunk whose bound the kernel's
    VMEM predicate refuses goes through it in PIECES of rows that it takes
    (:func:`_chunk_pieces`: a scan over one traced piece, a piece fetching
    the planes its own rows chose). Off a TPU, under a plan, or where no
    piece passes: :func:`_experts_chunk_xla`."""
    N, k = weights.shape
    pieces = _chunk_pieces(cfg, x, N, k, lp)
    if pieces is None:
        return _experts_chunk_xla(cfg, x, local, weights, m, lp)
    rows_a_piece, kw = pieces
    if rows_a_piece == N:
        return _experts_grouped(cfg, x, local, weights, m, lp, kw)

    def piece(fed, xs):
        y, fed_p = _experts_grouped(cfg, *xs, m, lp, kw)
        return fed + fed_p, y

    n = N // rows_a_piece
    fed, y = jax.lax.scan(piece, jnp.int32(0), (
        x.reshape(n, rows_a_piece, -1), local.reshape(n, rows_a_piece * k),
        weights.reshape(n, rows_a_piece, k)))
    return y.reshape(N, -1), fed


def _experts_grouped(cfg: ModelConfig, x: jax.Array, local, weights, m, lp,
                     kw: dict):
    """:func:`_experts_chunk` for rows the grouped kernel takes whole."""
    N, k = weights.shape
    E = cfg.n_experts
    F = ec.fed_rows(N * min(k, E), E)
    # the sort of _sorted_pairs alone: the kernel reads a pair's weight where
    # the router left it, so nothing is gathered into sorted copies (two
    # gathers of N k values cost 34 us a layer on a v5e, a sort 6)
    order = jnp.argsort(local, stable=True)
    rows = order // k
    runs, fed = _runs(cfg, local, ec.TILE_ROWS)

    def grouped(a, stack, *pair_weights, rows_out):
        note_q40_path("grouped")
        return ec.expert_chunk(a, stack, m, runs, rows, *pair_weights,
                               rows_out=rows_out, **kw)

    a = _gate_up(cfg, lambda x, stack: grouped(x, stack, rows_out=F), x, lp)
    return grouped(a.astype(x.dtype), lp.we2, (order, weights),
                   rows_out=N), fed


def routed_ffn(cfg: ModelConfig, h: jax.Array, lp, m,
               live: jax.Array):
    """``scale sum_{held} w_e E_e(h) + S(h)`` for ``h [B, T, dim]`` in routed
    layer ``m``, and the layer's ``stats``; ``live [B * T]`` marks the rows
    that are real (a dead slot's, a chunk's padding, are not routed). With a
    latent: ``W_out (scale sum_{held} w_e E_e(W_in h)) + S(h)``, the router
    on ``h``."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    at = lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False)
    bias = getattr(lp, "moe_bias", None)
    # a stack without a bias calls the router as its clients' tests patch it
    weights, idx = (route(cfg, x, at(lp.moe_gate)) if bias is None
                    else route(cfg, x, at(lp.moe_gate), at(bias)))
    local, stats = routed_pairs(cfg, idx, live)
    # a latent (cfg.moe_latent_dim) is the stack's two projection leaves
    lat_in = getattr(lp, "w_lat_in", None)
    z = x if lat_in is None else linear(x, _plane(lat_in, m))
    if step_form(cfg, B * T):
        y = _experts_step(cfg, z, local, weights, m, lp)
    else:
        y, fed = _experts_chunk(cfg, z, local, weights, m, lp)
        stats = stats.at[2].set(fed.astype(jnp.int32))
    if lat_in is not None:
        y = linear(y.astype(h.dtype),
                   _plane(lp.w_lat_out, m)).astype(jnp.float32)
    if lp.ws1 is not None:
        y = y + swiglu(cfg, h, _plane(lp.ws1, m), _plane(lp.ws2, m),
                        None if lp.ws3 is None else _plane(lp.ws3, m)
                        ).reshape(B * T, D)
    return y.reshape(B, T, D).astype(h.dtype), stats


def ffn_half(cfg: ModelConfig, x: jax.Array, lp, l, live,
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
        return (swiglu(cfg, h, _plane(lp.w1, d), _plane(lp.w2, d),
                        _plane(lp.w3, d)), zero_stats(cfg))

    if may_be_dense and cfg.n_dense_layers:
        y, stats = jax.lax.cond(l < cfg.n_dense_layers, dense, routed, h)
    else:
        y, stats = routed(h)
    return x + y, stats


