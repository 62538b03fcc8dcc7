"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
ICLR 2025): the mixer of a hybrid decoder's linear-attention layers
(models/hybrid.py), in its two forms.

Per head, with a float32 state ``S [dk, dv]`` and per token a key ``k [dk]``
(unit length), a query ``q [dk]``, a value ``v [dv]``, a decay ``alpha`` in
(0, 1] and a write strength ``beta`` in [0, 2]::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

**The decay's shape is data.** ``alpha`` is one number a head (``[.., H]``:
Gated DeltaNet, models/hybrid.py) or a VECTOR a head, one decay a key channel
(``[.., H, dk]``: Kimi Delta Attention, arXiv:2510.26692,
models/solar_open2.py), in which case ``alpha_t S`` above reads ``Diag(alpha_t)
S``: row ``c`` of the state decays by ``alpha_tc``. Every form takes either;
a ``[.., H]`` decay is never broadcast to ``dk`` in memory.

* **step form** (:func:`gated_delta_step`): one token a row, the decode
  step. The state lives in a slot-indexed pool ``[layers, rows, H, dk, dv]``
  (runtime/kvblocks.StatePool) that goes through the call IN PLACE: ONE
  Pallas kernel, which the compiled program and the device trace name
  ``gated_delta_step`` after its jitted entry (as they name ``quant_matmul``
  and ``paged_ragged_attention``), reads each (row, head) state once, applies
  decay, delta update and readout, and writes it once, aliased onto its
  input; :func:`gated_delta_step_xla` is its twin for the CPU and its oracle.
* **chunk form** (:func:`gated_delta_chunk`): a prefill chunk, chunkwise
  parallel. Inside sub-chunks of ``SUB_CHUNK`` tokens everything is a
  matmul; only the pass over sub-chunks is sequential, with the incoming
  state in and the outgoing state out. Plain ``jax.numpy``/``lax`` (XLA).
  :func:`gated_delta_recurrent`, the per-token scan, is its oracle.

The chunk form's algebra. Inside a sub-chunk write ``G_t = prod_{s<=t}
alpha_s`` and ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)``, so that ``S_t =
G_t S_0 + sum_{j<=t} (G_t / G_j) k_j u_j^T``. Substituting ``S_{t-1}`` into
``u_t`` gives ``(I + L) U = beta V - (beta K G) S_0`` with ``L`` strictly
lower triangular, ``L_tj = beta_t (G_t / G_j) (k_t . k_j)``. ``I + L`` is
inverted by blocked forward substitution (:func:`_unit_lower_inverse`). Then ``O = (Q G) S_0 + tril(Q K^T G_t / G_j) U`` and
``S_C = G_C S_0 + (K G_C / G)^T U``. A token with ``beta = 0`` and ``alpha =
1`` leaves the state as it was: that is how a padded position is masked.

With a VECTOR decay ``G_t`` is a vector too and ``G_t / G_j`` no longer leaves
the contraction over the key channels: ``L_tj = beta_t sum_c k_tc (G_tc /
G_jc) k_jc`` (and ``Q K^T`` likewise), ``K G``, ``Q G`` and ``K G_C / G`` scale
channel by channel, and ``G_C S_0`` is ``Diag(G_C) S_0``. The obvious split
``(k * G) . (k / G)`` overflows float32 as soon as one channel decays by
``e^-88`` inside a sub-chunk (``g = -1.4`` a token over 64 tokens), so
:func:`_decayed_pairs` keeps EVERY exponent <= 0: inside diagonal blocks of
``_SOLVE_BLOCK`` rows the pairwise exponents ``log G_tc - log G_jc`` exactly
(``[b, b, dk]``, products and sums on the vector unit), and between blocks a
split at the row block's boundary ``B``, ``(G_t / G_B) (G_B / G_j)``, both
factors <= 1 (one matmul a sub-chunk); a factor that underflows to 0 stands
for a term below ``1e-38``. Everything else is the scalar case's algebra.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB_CHUNK = 64
L2_EPS = 1e-6
# the mixer's small matmuls feed a float32 state that is carried over
# thousands of tokens: they run at full float32 precision on every backend
# (a TPU's default for an f32 dot is one bf16 pass)
_PREC = jax.lax.Precision.HIGHEST
# heads one grid step of the step kernel handles: amortizes the ~0.35 us a
# grid step costs over several 74 KB states
_HEADS_PER_STEP = (6, 5, 4, 3, 2, 1)


def l2norm(x: jax.Array) -> jax.Array:
    """``x / sqrt(sum x^2 + 1e-6)`` over the trailing axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gates(a: jax.Array, b: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
          neg_eigval: bool) -> tuple[jax.Array, jax.Array]:
    """``(g, beta)`` from the gate projections ``a, b [..., H]``: ``g = log
    alpha = -exp(A_log) softplus(a + dt_bias)`` and ``beta = sigmoid(b)``,
    doubled where negative eigenvalues are allowed. Float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a + dt_bias.astype(jnp.float32))
    beta = jax.nn.sigmoid(b) * (2.0 if neg_eigval else 1.0)
    return g, beta


# ---------------------------------------------------------------------------
# per-token recurrence (the oracle) and the step form's XLA twin
# ---------------------------------------------------------------------------


def _per_channel(decay, S) -> bool:  # dlint: static-fn
    """Whether ``decay`` is a vector a head (``[.., H, dk]``) against the
    state ``S [.., H, dk, dv]``, and not one number a head (``[.., H]``)."""
    return decay.ndim == S.ndim - 1


def _one_step(S, q, k, v, alpha, beta):
    """The recurrence for one token: ``S [.., H, dk, dv]``, ``q, k [.., H,
    dk]``, ``v [.., H, dv]``, ``beta [.., H]``, ``alpha [.., H]`` or ``[.., H,
    dk]``. Products and sums on the vector unit: exact float32 on every
    backend."""
    S = (alpha[..., None] if _per_channel(alpha, S)
         else alpha[..., None, None]) * S
    kS = jnp.sum(k[..., None] * S, axis=-2)
    u = beta[..., None] * (v - kS)
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.sum(q[..., None] * S, axis=-2)


def gated_delta_recurrent(q, k, v, g, beta, S0):
    """The rule as written, a scan over tokens. ``q, k [B, T, H, dk]``, ``v
    [B, T, H, dv]``, ``beta [B, T, H]``, ``g`` (log decay) ``[B, T, H]`` or
    ``[B, T, H, dk]``, ``S0 [B, H, dk, dv]``; all float32. Returns ``o [B, T,
    H, dv]`` and ``S_T``."""
    def body(S, xs):
        qt, kt, vt, gt, bt = xs
        return _one_step(S, qt, kt, vt, jnp.exp(gt), bt)

    S, o = jax.lax.scan(body, S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def gated_delta_step_xla(pool, layer, rows, q, k, v, alpha, beta):
    """The step form in XLA: gather the rows' states of ``layer`` out of
    ``pool [layers, R, H, dk, dv]``, one recurrence step, scatter back.
    ``q, k [B, H, dk]``, ``v [B, H, dv]``, ``beta [B, H]``, ``alpha [B, H]``
    or ``[B, H, dk]``. Returns ``o [B, H, dv]`` and the pool."""
    S = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[rows]
    S, o = _one_step(S, q, k, v, alpha, beta)
    return o, pool.at[layer, rows].set(S)


# ---------------------------------------------------------------------------
# the step form's Pallas kernel
# ---------------------------------------------------------------------------


def _step_kernel(layer_ref, rows_ref, qk_ref, vab_ref, s_ref,
                 o_ref, s_out_ref, *, heads: int, per_channel: bool):
    """One (row, group of ``heads`` heads) of the step form. ``qk_ref [1,
    heads, dk, 2]`` holds q and k as columns, ``vab_ref [1, heads, 8, dv]``
    holds v, alpha and beta as rows 0, 1, 2 (alpha and beta repeated along
    the row); ``s_ref [heads, dk, dv]`` is the state, read once, and
    ``s_out_ref`` the same cells of the same pool, written once. With a
    decay a key channel (``per_channel``) ``qk_ref`` is ``[1, heads, 8, dk]``:
    q, k and the ``dk`` decays as ROWS 0, 1, 2 (whole lane tiles in HBM, where
    a ``[dk, 3]`` operand's minor axis is padded to 128 lanes), turned into
    columns here, one a row of the state; row 1 of ``vab_ref`` is not read."""
    del layer_ref, rows_ref  # spent in the index maps
    for h in range(heads):
        if per_channel:
            cols = qk_ref[0, h].T                 # [dk, 8]
            q, k, alpha = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        else:
            q = qk_ref[0, h, :, 0:1]              # [dk, 1]
            k = qk_ref[0, h, :, 1:2]
            alpha = vab_ref[0, h, 1:2, :]
        v = vab_ref[0, h, 0:1, :]                 # [1, dv]
        beta = vab_ref[0, h, 2:3, :]
        S = s_ref[h] * alpha
        u = beta * (v - jnp.sum(S * k, axis=0, keepdims=True))
        S = S + k * u
        s_out_ref[h] = S
        o_ref[0, h] = jnp.sum(S * q, axis=0, keepdims=True)


def step_kernel_choice() -> dict | None:  # dlint: static-fn
    """The step kernel's gate: the mode comes from
    :func:`quant_matmul.pallas_mode_gate` (the ONE gate; ``fast=False``:
    kernel and twin compute the same float32), and no mesh plan may be
    active (the auto-sharder cannot partition a ``pallas_call``). Returns
    :func:`gated_delta_step` kwargs, or None for the XLA twin."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    kw = pallas_mode_gate(False)
    if kw is None or current_plan() is not None:
        return None
    return {"interpret": kw["interpret"]}


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step(pool, layer, rows, q, k, v, alpha, beta, *,
                            interpret: bool = False):
    """:func:`gated_delta_step_xla` as ONE Pallas kernel over the pool in
    place: layer and rows ride in as scalar-prefetch operands, the index
    maps pick ``(layer, rows[b], head group)``, and the pool's output is
    aliased onto its input, so cells no row names are never touched.
    ``alpha [B, H]`` rides ``vab`` as it always did and q and k are two
    columns: the scalar case's operands are today's bytes. ``alpha [B, H, dk]``
    rides beside q and k, the three as ROWS of one ``[B, H, 8, dk]`` operand
    that the kernel turns into columns."""
    _NL, _R, H, dk, dv = pool.shape
    B = q.shape[0]
    hb = next(c for c in _HEADS_PER_STEP if H % c == 0)
    f32 = jnp.float32
    per_channel = alpha.ndim == 3
    row = lambda t: jnp.broadcast_to(t.astype(f32)[..., None], (B, H, dv))
    if per_channel:
        qk = jnp.stack([q.astype(f32), k.astype(f32), alpha.astype(f32)]
                       + [jnp.zeros((B, H, dk), f32)] * 5, axis=2)  # [B, H, 8, dk]
        alpha = jnp.zeros((B, H), f32)
    else:
        qk = jnp.stack([q.astype(f32), k.astype(f32)], axis=-1)  # [B, H, dk, 2]
    vab = jnp.stack([v.astype(f32), row(alpha), row(beta)]
                    + [jnp.zeros((B, H, dv), f32)] * 5, axis=2)  # [B, H, 8, dv]
    vmem = pltpu.VMEM
    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda b, h, l, r: (l[0], r[b], h, 0, 0),
                         memory_space=vmem)
    per_row = lambda *tail: pl.BlockSpec(
        (1, hb) + tail, lambda b, h, l, r: (b, h, 0, 0), memory_space=vmem)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer, rows
        grid=(B, H // hb),
        in_specs=[per_row(*qk.shape[2:]), per_row(8, dv), state],
        out_specs=[per_row(1, dv), state],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, per_channel=per_channel),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},  # the pool, after layer rows qk vab
        name="gated_delta_step", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      qk, vab, pool)
    return o[:, :, 0, :], pool


# ---------------------------------------------------------------------------
# chunk form
# ---------------------------------------------------------------------------


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=_PREC,
                      preferred_element_type=jnp.float32)


_SOLVE_BLOCK = 16


def _unit_lower_inverse(L: jax.Array) -> jax.Array:
    """``(I + L)^-1`` for strictly lower triangular ``L [..., C, C]``, ``C`` a
    power of two: the diagonal blocks of ``_SOLVE_BLOCK`` rows by forward
    substitution (one short loop over a block's rows, every block of every
    head at once), then pairs of blocks merged upwards, ``[[A, 0], [B, D]]^-1
    = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]``. (The closed product ``(I - L)(I +
    L^2)(I + L^4)..`` is exact on paper and useless in float32: keys behind a
    SiLU are nearly parallel, ``L``'s entries are near ``beta``, and its
    powers reach 1e20 before they cancel.)"""
    C = L.shape[-1]
    b = min(C, _SOLVE_BLOCK)
    diag = jnp.stack([L[..., i:i + b, i:i + b] for i in range(0, C, b)], axis=-3)
    eye = jnp.eye(b, dtype=L.dtype)

    def row(i, X):
        # X[i] = e_i - sum_{j<i} L[i, j] X[j]: the rows above are final, and
        # L[i, j] is 0 from the diagonal on. Products on the vector unit.
        l_i = jax.lax.dynamic_index_in_dim(diag, i, axis=-2, keepdims=False)
        x_i = eye[i] - jnp.sum(l_i[..., :, None] * X, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(X, x_i, i, axis=-2)

    inv = jax.lax.fori_loop(0, b, row, jnp.broadcast_to(eye, diag.shape))
    blocks = [inv[..., i, :, :] for i in range(C // b)]
    size = b
    while len(blocks) > 1:
        merged = []
        for i in range(0, len(blocks), 2):
            a_inv, d_inv = blocks[i], blocks[i + 1]
            r = i * size
            B = L[..., r + size:r + 2 * size, r:r + size]
            low = -_mm("...ij,...jk->...ik", _mm("...ij,...jk->...ik", d_inv, B), a_inv)
            merged.append(jnp.concatenate([
                jnp.concatenate([a_inv, jnp.zeros_like(low)], axis=-1),
                jnp.concatenate([low, d_inv], axis=-1)], axis=-2))
        blocks, size = merged, size * 2
    return blocks[0]


def _decayed_pairs(a: jax.Array, k: jax.Array, gc: jax.Array) -> jax.Array:
    """``A_tj = sum_c a_tc (G_tc / G_jc) k_jc`` for ``t >= j`` and 0 above the
    diagonal, for a decay a key channel: ``a [.., C, dk]`` (leading axes may
    broadcast against ``k``'s), ``k`` and ``gc = log G [.., C, dk]``. No
    exponent is ever positive (module docstring): diagonal blocks of
    ``_SOLVE_BLOCK`` rows take the pairwise exponents exactly, on the vector
    unit; row block ``I``'s columns in front of it take ``(G_t / G_B) (G_B /
    G_j)`` with ``B`` the last row in front of the block, as one matmul over
    the channels."""
    C, dk = k.shape[-2:]
    b = min(C, _SOLVE_BLOCK)
    nb = C // b
    blocks = lambda x: x.reshape(x.shape[:-2] + (nb, b, dk))
    a_b, k_b, g_b = blocks(a), blocks(k), blocks(gc)
    idx = jnp.arange(b)
    lower = (idx[:, None] >= idx[None, :])[..., None]
    pair = jnp.exp(jnp.where(lower, g_b[..., :, None, :] - g_b[..., None, :, :],
                             -jnp.inf))                       # [.., nb, b, b, dk]
    diag = jnp.sum(a_b[..., :, None, :] * k_b[..., None, :, :] * pair, axis=-1)
    # log G at the last row in front of each row block (block 0 has none in
    # front of it: its columns below are all masked)
    edge = jnp.concatenate([jnp.zeros_like(g_b[..., :1, -1, :]),
                            g_b[..., :-1, -1, :]], axis=-2)    # [.., nb, dk]
    left = a_b * jnp.exp(g_b - edge[..., :, None, :])
    in_front = (jnp.arange(C)[None, :] // b < jnp.arange(nb)[:, None])[..., None]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        in_front, edge[..., :, None, :] - gc[..., None, :, :], -jnp.inf))
    cross = _mm("...itc,...ijc->...itj", left, right)          # [.., nb, b, C]
    placed = diag[..., :, :, None, :] * jnp.eye(nb, dtype=diag.dtype)[:, None, :, None]
    return (cross.reshape(cross.shape[:-3] + (C, C))
            + placed.reshape(placed.shape[:-4] + (C, C)))


def gated_delta_chunk(q, k, v, g, beta, S0):
    """The chunk form: same arguments and results as
    :func:`gated_delta_recurrent` (``g [B, T, H]`` or ``[B, T, H, dk]``),
    chunkwise parallel over sub-chunks of ``gcd(T, SUB_CHUNK)`` tokens (the
    module docstring has the algebra). No loop over tokens: one scan over
    the ``T / C`` sub-chunks carries the state."""
    B, T, H, dk = q.shape
    C = math.gcd(T, SUB_CHUNK)
    N = T // C
    per_channel = g.ndim == 4

    def heads_first(x):     # [B, T, H, d] -> [N, B, H, C, d]
        return jnp.transpose(x.reshape(B, N, C, H, -1), (1, 0, 3, 2, 4))

    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    # [N, B, H, C, dk] a channel, [N, B, H, C] a head
    g = heads_first(g) if per_channel else heads_first(g[..., None])[..., 0]
    beta = heads_first(beta[..., None])
    gc = jnp.cumsum(g, axis=-2 if per_channel else -1)           # log G_t
    idx = jnp.arange(C)
    if per_channel:
        lanes = lambda x: x                           # [.., C, dk] as it is
        rows = lambda x: jnp.swapaxes(x, -1, -2)      # a channel a state row
    else:
        lanes = rows = lambda x: x[..., None]
        lower = idx[:, None] >= idx[None, :]
        # G_t / G_j for t >= j, 0 above the diagonal (the exponent there would
        # be positive and may overflow)
        ratio = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kb, vb = k * beta, v * beta
    strict = idx[:, None] > idx[None, :]
    if per_channel:
        # both products in ONE pass: they share every exponent
        KK, QK = _decayed_pairs(jnp.stack([kb, q]), k, gc)
    else:
        KK = _mm("...ik,...jk->...ij", kb, k) * ratio
    inv = _unit_lower_inverse(jnp.where(strict, KK, 0.0))
    V = _mm("...ij,...jv->...iv", inv, vb)                       # (I+L)^-1 beta V
    W = _mm("...ij,...jk->...ik", inv, kb * lanes(jnp.exp(gc)))
    if not per_channel:
        QK = _mm("...ik,...jk->...ij", q, k) * ratio             # tril(Q K^T G_t/G_j)
    qg = q * lanes(jnp.exp(gc))
    g_end = gc[..., -1:, :] if per_channel else gc[..., -1:]     # log G_C
    k_end = k * lanes(jnp.exp(g_end - gc))

    def body(S, xs):
        V_n, W_n, QK_n, qg_n, k_end_n, g_end_n = xs
        U = V_n - _mm("...ck,...kv->...cv", W_n, S)
        o = _mm("...ck,...kv->...cv", qg_n, S) + _mm("...ij,...jv->...iv", QK_n, U)
        S = (rows(jnp.exp(g_end_n)) * S
             + _mm("...ck,...cv->...kv", k_end_n, U))
        return S, o

    S, o = jax.lax.scan(body, S0, (V, W, QK, qg, k_end, g_end))
    # [N, B, H, C, dv] -> [B, T, H, dv]
    return jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, T, H, -1), S
