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
  mixer runs its CHUNK form, state in and state out (the Pallas kernel
  ``gated_delta_chunk`` on a TPU, its XLA twin elsewhere). ``n_valid`` masks
  padding: K/V rows written for padded positions are overwritten later, a
  state would keep them, so positions at or past ``n_valid`` get ``beta =
  0, alpha = 1`` and never enter the convolution's tail.
* :func:`paged_forward`: the decode step, one token a row. The mixer runs
  its STEP form over the state pool in place (the Pallas kernel
  ``gated_delta_step`` on a TPU, its XLA twin elsewhere); rows whose block
  table is all null (inactive slots riding along) use the pool's null row.
* :func:`forward_and_step` (``FAMILY.tick``, PR 55): a chunk AND the tick's
  decode rows through one pass over the planes of both stacks, the paged
  server's program for every plain chunk; no chunk logits.

The three are three sets of closures (``mixer``, ``store``, ``attend``) over
ONE period scan (:func:`_scan_periods`). In all of them everything a slot's
context is made of rides the scan's CARRY whole: the state and the tail,
and the full layers' K/V (a column's, the pool's or, in the tick program,
both), which period ``p`` writes in place and attends through the whole
array and ``p`` (:func:`~dllama_tpu.models.llama._attend_paged`). As the
scan's ``xs``/``ys`` the K/V pool was sliced, stacked and copied back every
step (PERF.md section 6, PR 33).

**The scan and the two programs around it are not this family's alone**
(PR 58): what a family brings to them is a :class:`Walk` (its two stacks,
where the full layer stands in its period, a sublayer's form, the full
layer's attention, the feed-forward and the mixer's two forms, as closures),
and :func:`chunk_program` / :func:`step_program` are ``forward`` /
``paged_forward`` over any walk. This module's walk (:func:`_olmo_walk`) is
what the next paragraphs describe; ``models/solar_open2.py``'s puts the full
layer FIRST, norms on a sublayer's input and a routed feed-forward whose
counters ride the carry behind both stacks.

A linear layer's mixer, for its input ``u``: one packed projection ``[q~ k~
v~ z] = W_in u``, gates ``[a b] = W_ab u``; a causal depthwise convolution
of ``K`` taps and SiLU over ``q~ k~ v~``; per head ``q = l2norm(q') /
sqrt(dk)``, ``k = l2norm(k')``, ``beta = sigmoid(b)`` (doubled where
``lin_neg_eigval``), ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``; the
gated delta rule; ``y = W_out (rmsnorm_dv(o) * silu(z))``. The mixer is cut
where its rows stop being independent, as models/ssd_mixer.py is:
:func:`_mixer_project`, :func:`_mixer_heads` and :func:`_mixer_output` work a
row at a time (both planes, the gate rows, the norms and gates), the
convolution (against a tail) and the rule (:func:`_rule_chunk` /
:func:`_rule_step`, against a state) own a context. :func:`_mixer_chunk` and
:func:`_mixer_step` put ONE of each between them,
:func:`_mixer_chunk_and_step` both, over rows joined along ``T``.

The arch implies three conventions (the Olmo 2/3 family's; none is in the
published config): block norms sit on a sublayer's OUTPUT (``x + norm(f(x))``),
q and k carry an RMS norm over the WHOLE projection before the heads are
split, and the full layers carry no rotary embedding.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

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
from .llama import (_LAYER_MATMULS, LayerParams, Params, _at, _attend_dense,
                    _attend_paged, _attend_split, _by_row, _exact_f32_dots,
                    _hidden_act, _join, _join_positions, _join_tokens,
                    _live_rows, _pick_rows, _put, _stack_at, _state_rows)


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


def _mixer_project(cfg: ModelConfig, u: jax.Array, lp: LinearLayerParams):
    """What the mixer does a ROW at a time in front of its convolution, for
    ``u [B, T, dim]``: ONE read of the packed ``w_in`` for however many rows
    (``qkv [B, T, lin_conv_dim]`` the convolution's input, ``z [B, T, H
    dv]`` the output gate) and the float32 gate rows ``ab [B, T, 2 H]``.
    Rows of different sequences may be joined along ``T``: nothing here
    looks across them."""
    proj = linear(u, lp.w_in)
    qkv, z = proj[..., :cfg.lin_conv_dim], proj[..., cfg.lin_conv_dim:]
    ab = jnp.einsum("btd,hd->bth", u.astype(jnp.float32), lp.w_ab,
                    precision=jax.lax.Precision.HIGHEST)
    return qkv, z, ab


def _mixer_heads(cfg: ModelConfig, y: jax.Array, z: jax.Array, ab: jax.Array,
                 lp: LinearLayerParams):
    """What the mixer does a row at a time BEHIND its convolution, in front
    of the rule, from the convolved ``y [B, T, lin_conv_dim]`` and
    :func:`_mixer_project`'s ``z`` and ``ab``: float32 ``q, k [B, T, H,
    dk]``, ``v [B, T, H, dv]``, ``g`` (log decay) and ``beta [B, T, H]``, and
    the output gate ``z [B, T, H, dv]``."""
    B, T, _ = y.shape
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    q = gd.l2norm(y[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
    k = gd.l2norm(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
    g, beta = gd.gates(ab[..., :H], ab[..., H:], lp.a_log, lp.dt_bias,
                       cfg.lin_neg_eigval)
    return q, k, v, g, beta, z.reshape(B, T, H, dv)


def _mixer_output(cfg: ModelConfig, o: jax.Array, z: jax.Array,
                  lp: LinearLayerParams, dtype) -> jax.Array:
    """``W_out (rmsnorm_dv(o) * silu(z))`` from float32 ``o [B, T, H, dv]``,
    a row at a time."""
    B, T = o.shape[:2]
    gated = (rms_norm(o, lp.norm_o, cfg.norm_epsilon)
             * jax.nn.silu(z.astype(jnp.float32)))
    return linear(gated.reshape(B, T, -1).astype(dtype), lp.w_out)


def _rule_chunk(q, k, v, g, beta, s_l, n_valid):
    """The rule over ONE sequence's chunk against its state ``s_l [B, H, dk,
    dv]``: positions at or past ``n_valid`` get ``beta = 0, alpha = 1`` (in
    every channel, where ``g [B, T, H, dk]`` is a decay a key channel) and
    leave it alone. Returns ``o [B, T, H, dv]`` and the state."""
    real = (jnp.arange(q.shape[1]) < n_valid)[None, :, None]
    kernel = gd.chunk_kernel_choice(q.shape[1])
    note_gdn_path("chunk", "xla" if kernel is None else "pallas")
    chunk = (gd.gated_delta_chunk_xla if kernel is None
             else lambda *a: gd.gated_delta_chunk(*a, **kernel))
    return chunk(
        q, k, v, jnp.where(real if g.ndim == 3 else real[..., None], g, 0.0),
        jnp.where(real, beta, 0.0), s_l)


def _rule_step(l, rows, q, k, v, g, beta, s_pool):
    """The rule over one token a row, ``[B, 1, ...]``, the state pool in
    place: row ``b``'s state is ``[l, rows[b]]`` of it. Returns ``o [B, 1,
    H, dv]`` and the pool."""
    kernel = gd.step_kernel_choice()
    note_gdn_path("step", "xla" if kernel is None else "pallas")
    step = (gd.gated_delta_step_xla if kernel is None
            else lambda *a: gd.gated_delta_step(*a, **kernel))
    o, s_pool = step(s_pool, l, rows, q[:, 0], k[:, 0], v[:, 0],
                     jnp.exp(g[:, 0]), beta[:, 0])
    return o[:, None], s_pool


def _mixer_chunk(cfg, u, lp, s_l, conv_l, n_valid):
    """The mixer over a chunk: ``s_l [B, H, dk, dv]`` and the tail ``conv_l
    [B, K - 1, C]`` in and out."""
    qkv, z, ab = _mixer_project(cfg, u, lp)
    y, conv_l = causal_conv(qkv, conv_l, lp.conv_w, n_valid)
    q, k, v, g, beta, z = _mixer_heads(cfg, y, z, ab, lp)
    o, s_l = _rule_chunk(q, k, v, g, beta, s_l, n_valid)
    return _mixer_output(cfg, o, z, lp, u.dtype), s_l, conv_l


def _mixer_step(cfg, u, lp, l, rows, s_pool, conv_pool):
    """The mixer over one token a row, the pools in and out: row ``b``'s
    state and tail are ``[l, rows[b]]`` of them."""
    tail = _at(conv_pool, l)[rows]            # [B, K - 1, C]
    qkv, z, ab = _mixer_project(cfg, u, lp)
    y, tail = causal_conv(qkv, tail, lp.conv_w, None)
    q, k, v, g, beta, z = _mixer_heads(cfg, y, z, ab, lp)
    conv_pool = conv_pool.at[l, rows].set(tail)
    o, s_pool = _rule_step(l, rows, q, k, v, g, beta, s_pool)
    return _mixer_output(cfg, o, z, lp, u.dtype), s_pool, conv_pool


def _mixer_chunk_and_step(cfg, u, lp, l, T, n_valid, rows, s, conv):
    """A chunk and the tick's decode rows through ONE pass over the mixer's
    planes: ``u [1, T + R, dim]`` is the chunk's ``T`` rows and then one row
    a slot. What works a row at a time runs once over the joined rows
    (:func:`_mixer_project`, :func:`_mixer_heads`, :func:`_mixer_output`);
    the convolution and the rule run a part at a time, the chunk's as
    :func:`_mixer_chunk` has them, the rows' as :func:`_mixer_step`. ``s``
    and ``conv`` are each a pair, the column's layer (``s_l``, ``conv_l``)
    and the pool whole (rows at ``[l, rows[b]]``), and come back so beside
    ``y [1, T + R, dim]``."""
    (s_l, s_pool), (conv_l, conv_pool) = s, conv
    tail = _at(conv_pool, l)[rows]            # [B, K - 1, C]
    qkv, z, ab = _mixer_project(cfg, u, lp)
    y_c, conv_l = causal_conv(qkv[:, :T], conv_l, lp.conv_w, n_valid)
    y_r, tail = causal_conv(_by_row(qkv, T), tail, lp.conv_w, None)
    conv_pool = conv_pool.at[l, rows].set(tail)
    *heads, z = _mixer_heads(cfg, _join(y_c, y_r), z, ab, lp)
    o_c, s_l = _rule_chunk(*(a[:, :T] for a in heads), s_l, n_valid)
    o_r, s_pool = _rule_step(l, rows, *(_by_row(a, T) for a in heads), s_pool)
    return (_mixer_output(cfg, _join(o_c, o_r), z, lp, u.dtype),
            (s_l, s_pool), (conv_l, conv_pool))


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


def _attention(cfg: ModelConfig, h: jax.Array, lp: LayerParams, attend):
    """A full layer's attention over its sublayer's input ``h``;
    ``attend(q, k, v) -> att`` owns the cache."""
    B, T, _ = h.shape
    q, k, v = _full_qkv(cfg, h, lp)
    return linear(attend(q, k, v).reshape(B, T, cfg.q_dim), lp.wo,
                  in_axis="heads")


class Walk(NamedTuple):
    """What a family brings to the period scan (:func:`_scan_periods`) and
    to the two programs over it (:func:`chunk_program`,
    :func:`step_program`): its two stacks, where the full layer stands in
    its period, and what a layer is made of, as closures. This module's
    (:func:`_olmo_walk`): the full layer LAST, norms on a sublayer's output,
    a dense feed-forward. ``models/solar_open2.py``'s: the full layer FIRST,
    norms on a sublayer's input, a routed feed-forward whose counters ride
    the carry (``acc``)."""

    lin: Any                      # the linear layers' stack, [n_linear, ...]
    lin_matmuls: tuple[str, ...]  # its 2-D matmul planes (llama._stack_at)
    full: Any                     # the full layers' stack, [n_periods, ...]
    full_matmuls: tuple[str, ...]
    full_at: int                  # the full layer's place in its period
    # (x, norm_w, f) -> x: a sublayer, residual and norm placed
    sublayer: Callable
    # (h, lp, attend) -> y: the full layer's attention inside its sublayer
    attention: Callable
    # (x, lp, l, acc, live) -> (x, acc): the feed-forward sublayer of layer
    # ``l`` of the MODEL; ``lp`` is the layer's entry of its own stack
    ffn: Callable
    # the mixer's two forms: _mixer_chunk's and _mixer_step's signatures
    mixer_chunk: Callable
    mixer_step: Callable
    # () -> what ``acc`` starts a step from (None: nothing is accumulated)
    acc0: Callable = lambda: None


def _olmo_walk(params: Params, cfg: ModelConfig) -> Walk:
    def ffn(x, lp, _l, acc, _live):
        return _sublayer(cfg, x, lp.norm_ffn, lambda h: _ffn(cfg, h, lp)), acc

    return Walk(
        lin=params.layers.lin, lin_matmuls=_LINEAR_MATMULS,
        full=params.layers.full, full_matmuls=_LAYER_MATMULS,
        full_at=cfg.layer_period - 1,
        sublayer=functools.partial(_sublayer, cfg),
        attention=functools.partial(_attention, cfg), ffn=ffn,
        mixer_chunk=functools.partial(_mixer_chunk, cfg),
        mixer_step=functools.partial(_mixer_step, cfg))


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


def _scan_periods(walk: Walk, cfg: ModelConfig, x: jax.Array, s, conv, k, v,
                  acc, live, mixer, store, attend):
    """The period scan every program of every family shares: a period's
    linear layers (a ``fori_loop`` over one traced body) around its full
    layer, which stands at ``walk.full_at`` (last: one loop in front of it;
    first: one behind it); the hidden rows ``[B, T, dim]`` behind the last
    period come back, in front of the final norm (:func:`_head`).
    Everything a slot's context is made of rides the CARRY whole, a
    column's, the pool or (the tick program) a pair of both: ``s, conv``
    (every linear layer's state and tail), ``k, v`` (the full layers' cache,
    indexed by the period ``p``) and ``acc`` (what ``walk.ffn`` accumulates:
    a routed feed-forward's counters, or None); nothing is sliced into the
    scan or stacked out of it, so the pools are written in place.
    ``mixer(h, lp, l, s, conv) -> (y, s', conv')`` is the form of the mixer
    and ``store(a, a', l)`` puts what it gave back into the carry (a
    column's layer ``l``; the pool comes back whole); ``attend(q, k, v, k_c,
    v_c, p) -> (att, k_c, v_c)`` owns the cache; ``live`` (the rows that are
    real) is ``walk.ffn``'s. Everything else a layer does it does a row at a
    time, so the rows along ``T`` need not be one sequence's: only the
    closures know."""
    P, at = cfg.layer_period, walk.full_at
    # the loops of linear layers in front of a period's full layer and behind
    # it; one without layers is not traced
    front, behind = ([(lo, hi)] if hi > lo else [] for lo, hi in ((0, at), (at, P - 1)))

    def period(carry, p):
        x, s, conv, k_c, v_c, acc = carry

        def linear_layer(j, carry):
            x, s, conv, acc = carry
            l = p * (P - 1) + j
            lp = _stack_at(walk.lin, l, walk.lin_matmuls)
            new = {}

            def mix(h):
                y, new["s"], new["conv"] = mixer(h, lp, l, s, conv)
                return y

            x = walk.sublayer(x, lp.norm_att, mix)
            x, acc = walk.ffn(x, lp, p * P + j + (j >= at), acc, live)
            return x, store(s, new["s"], l), store(conv, new["conv"], l), acc

        for lo, hi in front:
            x, s, conv, acc = jax.lax.fori_loop(lo, hi, linear_layer,
                                                (x, s, conv, acc))
        lp = _stack_at(walk.full, p, walk.full_matmuls)
        cache = {}

        def attend_p(q, k, v):
            att, cache["k"], cache["v"] = attend(q, k, v, k_c, v_c, p)
            return att

        x = walk.sublayer(x, lp.norm_att,
                          lambda h: walk.attention(h, lp, attend_p))
        x, acc = walk.ffn(x, lp, p * P + at, acc, live)
        for lo, hi in behind:
            x, s, conv, acc = jax.lax.fori_loop(lo, hi, linear_layer,
                                                (x, s, conv, acc))
        return (x, s, conv, cache["k"], cache["v"], acc), None

    periods = jnp.arange(cfg.n_periods, dtype=jnp.int32)
    carry, _ = jax.lax.scan(period, (x, s, conv, k, v, acc), periods)
    return carry


def chunk_program(walk: Walk, params: Params, cfg: ModelConfig,
                  tokens: jax.Array, start_pos: jax.Array, col: HybridColumn,
                  n_valid: jax.Array | None = None):
    """A chunk ``tokens [B, T]`` at scalar ``start_pos`` over a gathered
    column: float32 logits ``[B, T, vocab]`` and the column, advanced by
    the chunk's first ``n_valid`` positions (absent: all ``T``; positions
    behind them are not ``live`` for ``walk.ffn``). A column that carries
    routing counters (``col.stats``) gets the chunk's added."""
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

    def mixer(h, lp, l, s, conv):
        return walk.mixer_chunk(h, lp, _at(s, l), _at(conv, l), n_valid)

    def attend(q, k, v, k_c, v_c, p):
        att, k_p, v_p = _attend_dense(cfg, q, k, v, _at(k_c, p), _at(v_c, p),
                                      start_pos, positions)
        return att, _put(k_c, k_p, p), _put(v_c, v_p, p)

    x, s, conv, k, v, stats = _scan_periods(
        walk, cfg, x, col.s, col.conv, col.k, col.v, col.stats,
        jnp.tile(jnp.arange(T) < n_valid, B), mixer, _put, attend)
    return _head(params, cfg, x), HybridColumn(k=k, v=v, s=s, conv=conv,
                                               stats=stats)


def step_program(walk: Walk, params: Params, cfg: ModelConfig,
                 tokens: jax.Array, pos_vec: jax.Array, cache,
                 tables: jax.Array, write_lens: jax.Array | None = None):
    """The decode step over the paged pool and the state pool: ``tokens [B,
    1]`` at per-row ``pos_vec``, ``cache = (PagedKVCache, StatePool)`` and,
    where ``walk.ffn`` accumulates (``walk.acc0``), the generator's routing
    totals behind them (the step's counters added to row 0); all given
    back. Row ``b`` is slot ``b``: its state is row ``b + 1`` of the pool,
    or the null row 0 while its block table is all null (such a row is not
    ``live``)."""
    from ..runtime.kvblocks import PagedKVCache, StatePool

    _check(cfg)
    B, T = tokens.shape
    if T != 1 or write_lens is not None:
        raise ValueError("a hybrid decoder's step form takes one token a "
                         "row: a speculative verify's rejected drafts "
                         "cannot be rolled back out of a recurrent state")
    pkv, pool, *totals = cache
    positions = jnp.asarray(pos_vec, dtype=jnp.int32)[:, None]
    live = _live_rows(tables)
    rows = _state_rows(live)
    x = params.embedding[tokens].astype(cfg.compute_dtype)

    def mixer(h, lp, l, s, conv):
        return walk.mixer_step(h, lp, l, rows, s, conv)

    def attend(q, k, v, k_pool, v_pool, p):
        return _attend_paged(cfg, q, k, v, k_pool, v_pool, p, positions,
                             tables)

    x, s, conv, k, v, stats = _scan_periods(
        walk, cfg, x, pool.s, pool.conv, pkv.k, pkv.v, walk.acc0(), live,
        mixer, lambda _a, new, _l: new, attend)
    return (_head(params, cfg, x),
            (PagedKVCache(k=k, v=v), StatePool(s=s, conv=conv))
            + tuple(t.at[0].add(stats) for t in totals))


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            start_pos: jax.Array, col: HybridColumn,
            n_valid: jax.Array | None = None):
    """:func:`chunk_program` over this module's walk."""
    return chunk_program(_olmo_walk(params, cfg), params, cfg, tokens,
                         start_pos, col, n_valid)


def paged_forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
                  pos_vec: jax.Array, cache, tables: jax.Array,
                  write_lens: jax.Array | None = None):
    """:func:`step_program` over this module's walk."""
    return step_program(_olmo_walk(params, cfg), params, cfg, tokens,
                        pos_vec, cache, tables, write_lens)


@_exact_f32_dots
def forward_and_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                     pos_vec: jax.Array, cache, tables: jax.Array,
                     chunk: jax.Array, chunk_pos: jax.Array,
                     n_valid: jax.Array, poison: jax.Array):
    """A tick that carries a prefill chunk, as ONE program
    (``Family.tick``; ``falcon_h1.forward_and_step``'s signature to the
    letter): :func:`forward` over ``chunk [1, T]`` at ``chunk_pos`` into an
    admission's column AND :func:`paged_forward`'s layers over the tick's
    decode rows (``tokens [R, 1]`` at ``pos_vec`` through ``tables``), so
    that every plane of both stacks is read once for both. ``cache`` is
    ``(column, (PagedKVCache, StatePool))``, all given back (and donated
    where the server jits this).

    ONE call of :func:`_scan_periods` over the joined rows ``[1, T + R]``,
    its carry the column AND the pools whole, each as its own program
    carries it (``store`` puts the column's layer and passes the pool
    through). Only what owns a context tells the rows apart: ``attend`` (the
    chunk's rows over the column's period, the decode rows into the block
    pool in place through their tables) and ``mixer``
    (:func:`_mixer_chunk_and_step`: the convolution and the rule a part at a
    time, the chunk form against the column, the step form against the
    pools). A row with an all-null table is dead, as an inactive slot of a
    step is (the null block, the pool's null row), and every row may be.

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
    x = params.embedding[joined].astype(cfg.compute_dtype)
    cpos, rpos, _ = _join_positions(chunk_pos, pos_vec, T)   # no rotary here
    rows = _state_rows(_live_rows(tables))

    def mixer(h, lp, l, s, conv):
        return _mixer_chunk_and_step(cfg, h, lp, l, T, n_valid, rows,
                                     (_at(s[0], l), s[1]),
                                     (_at(conv[0], l), conv[1]))

    def store(a, new, l):
        return _put(a[0], new[0], l), new[1]

    def attend(q, k, v, k_c, v_c, p):
        (k_col, k_pool), (v_col, v_pool) = k_c, v_c
        att, k_p, v_p, k_pool, v_pool = _attend_split(
            cfg, q, k, v, T, lambda: (_at(k_col, p), _at(v_col, p)), k_pool,
            v_pool, p, chunk_pos, cpos, rpos, tables)
        return (att, (_put(k_col, k_p, p), k_pool),
                (_put(v_col, v_p, p), v_pool))

    x, s, conv, k, v, _ = _scan_periods(
        _olmo_walk(params, cfg), cfg, x, (col.s, pool.s),
        (col.conv, pool.conv), (col.k, pkv.k), (col.v, pkv.v), None, None,
        mixer, store, attend)
    return (_pick_rows(_head, params, cfg, x, T, poison),
            (HybridColumn(k=k[0], v=v[0], s=s[0], conv=conv[0]),
             (PagedKVCache(k=k[1], v=v[1]), StatePool(s=s[1], conv=conv[1]))))


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
    tick=forward_and_step,
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
