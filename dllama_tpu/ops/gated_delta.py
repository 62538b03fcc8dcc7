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
  parallel over sub-chunks of ``SUB_CHUNK`` tokens; only the pass over
  sub-chunks is sequential, with the incoming state in and the outgoing
  state out. ONE Pallas kernel (PR 61), named ``gated_delta_chunk`` after
  its jitted entry: a grid step holds one sub-chunk of a group of heads in
  VMEM (:func:`_chunk_head` has the body), the state stays there across a
  head's sub-chunks, and nothing goes back to HBM but ``o`` and, once, the
  state. :func:`gated_delta_chunk_xla` is its twin for the CPU and for a
  mesh plan (:func:`chunk_kernel_choice`), plain ``jax.numpy``/``lax``;
  :func:`gated_delta_recurrent`, the per-token scan, is the oracle of both.

The chunk form's algebra. Inside a sub-chunk write ``G_t = prod_{s<=t}
alpha_s`` and ``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)``, so that ``S_t =
G_t S_0 + sum_{j<=t} (G_t / G_j) k_j u_j^T``. Substituting ``S_{t-1}`` into
``u_t`` gives ``(I + L) U = beta V - (beta K G) S_0`` with ``L`` strictly
lower triangular, ``L_tj = beta_t (G_t / G_j) (k_t . k_j)``. ``I + L`` is
inverted by blocked forward substitution (:func:`_unit_lower_inverse`; the
kernel solves for ``U`` instead, a column of ``L`` at a time). Then ``O = (Q
G) S_0 + tril(Q K^T G_t / G_j) U`` and ``S_C = G_C S_0 + (K G_C / G)^T U``. A
token with ``beta = 0`` and ``alpha = 1`` leaves the state as it was: that is
how a padded position is masked.

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


def gated_delta_chunk_xla(q, k, v, g, beta, S0):
    """The chunk form in XLA: same arguments and results as
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


# ---------------------------------------------------------------------------
# the chunk form's Pallas kernel
# ---------------------------------------------------------------------------

# float32 bytes of q, k, v, o (and a vector decay) that one grid step of the
# chunk kernel moves: what the head group is chosen from
# (:func:`chunk_heads_per_step`), as ``ssd._STATE_BLOCK_BYTES`` chooses the
# step kernels'
_CHUNK_BLOCK_BYTES = 1 << 20


def chunk_heads_per_step(H: int, C: int, dk: int, dv: int,
                         per_channel: bool) -> int:  # dlint: static-fn
    """Heads one grid step of the chunk kernel handles: the largest divisor
    of ``H`` whose sub-chunk of q, k (and a decay a key channel), v and o
    stays at or under :data:`_CHUNK_BLOCK_BYTES`; 1 where one head is over."""
    per_head = 4 * C * ((3 if per_channel else 2) * dk + 2 * dv)
    most = max(1, _CHUNK_BLOCK_BYTES // per_head)
    return max(c for c in range(1, H + 1) if H % c == 0 and c <= most)


def _dot(a: jax.Array, b: jax.Array, contract=((1,), (0,))) -> jax.Array:
    """A float32 matmul inside the kernel at :data:`_PREC` (Mosaic's
    ``contract_precision<fp32>``: the six passes ``_mm`` gets from XLA)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_PREC,
                               preferred_element_type=jnp.float32)


# rows a block of the kernel's solve: its columns in front of a block go
# through the matrix unit, its own through the vector unit, and at 32 the two
# take about as long (tools/delta_chunk_sweep.py; the XLA twin's
# ``_SOLVE_BLOCK`` is its own)
_KERNEL_BLOCK = 32
# heads the kernel walks in lockstep: one head's matmuls wait six passes
# each, the other's columns fill the wait
_LOCKSTEP = 2


def _chunk_head(q, k, v, g, beta, S, *, per_channel: bool):
    """One head's sub-chunk against its state, everything a value in VMEM:
    ``q, k [C, dk]``, ``v [C, dv]``, ``S [dk, dv]``, ``beta`` a ROW ``[1, C]``
    and the log decay ``g`` ``[C, dk]`` (a key channel) or, one a head, a row
    ``[1, C]`` too. A generator: it yields between its stages (so that
    :func:`_chunk_kernel` can trace several heads' stages in turn) and
    returns ``o [C, dv]`` and the state behind the sub-chunk.

    The module docstring's algebra by row blocks of ``_KERNEL_BLOCK`` tokens,
    EVERY exponent <= 0 for either decay (``_decayed_pairs``' two cases; a
    decay a head is the same lines with ``[.., 1]`` where a key channel's has
    ``[.., dk]``): a block's columns in front of it take the split at the
    block's edge, ``(G_t / G_e) (G_e / G_j)``, ONE matmul for ``K K^T`` and ``Q
    K^T`` together and one against the rows of ``U`` that are done; its own
    columns take the pairwise exponents exactly, a column at a time on the
    vector unit (over the rows from the column's own sublane tile down: the
    rows above it are zeros), and each column of ``L`` is spent at once on
    the forward substitution (``R <- R - L[:, j] R[j]``: row ``j`` of the
    right-hand side is final when column ``j`` comes). The matrix unit's six
    passes a float32 product are what this kernel waits for
    (tools/delta_chunk_sweep.py): a ``[b, b]`` triangle through it costs more
    than these ``b`` columns."""
    C, dk = k.shape
    b = min(C, _KERNEL_BLOCK)
    f32 = jnp.float32
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    tril = iota((C, C), 0) >= iota((C, C), 1)
    eye = iota((C, C), 0) == iota((C, C), 1)
    beta = jnp.sum(jnp.where(eye, beta, 0.0), axis=1, keepdims=True)  # the row as a column [C, 1]
    if per_channel:
        gc = _dot(tril.astype(f32), g)                          # log G_t [C, dk]
    else:
        gc = jnp.sum(jnp.where(tril, g, 0.0), axis=1, keepdims=True)  # [C, 1]
    kb, vb = k * beta, v * beta
    eg = jnp.exp(gc)
    # (beta K G) S_0 and (Q G) S_0 read the state once
    both = _dot(jnp.concatenate([kb * eg, q * eg], axis=0), S)  # [2C, dv]
    rhs, o_state = vb - both[:C], both[C:]
    sub = iota((b, 1), 0)
    below = lambda x, lo, new: jnp.concatenate([x[:lo], new], axis=0) if lo else new
    U, o = [], []
    yield
    for r0 in range(0, C, b):
        g_i, k_i, kb_i, q_i = (x[r0:r0 + b] for x in (gc, k, kb, q))
        R, o_i = rhs[r0:r0 + b], o_state[r0:r0 + b]
        if r0:
            edge = gc[r0 - 1:r0]
            left = jnp.concatenate([kb_i, q_i], axis=0) * jnp.exp(
                jnp.concatenate([g_i, g_i], axis=0) - edge)
            right = k[:r0] * jnp.exp(edge - gc[:r0])
            cross = _dot(_dot(left, right, ((1,), (1,))),       # [2b, r0]
                         jnp.concatenate(U, axis=0))            # [2b, dv]
            R, o_i = R - cross[:b], o_i + cross[b:]
        yield
        qk = []
        for j in range(b):
            lo = j // 8 * 8                                     # column j is zeros above row j
            pair = jnp.exp(jnp.where(sub[lo:] >= j, g_i[lo:] - g_i[j:j + 1], -jnp.inf))
            k_j = k_i[j:j + 1] * pair                           # [b - lo, dk]
            l_j = jnp.sum(kb_i[lo:] * k_j, axis=1, keepdims=True)   # L[lo:, j]
            qk.append(jnp.sum(q_i[lo:] * k_j, axis=1, keepdims=True))
            R = below(R, lo, R[lo:] - jnp.where(sub[lo:] > j, l_j, 0.0) * R[j:j + 1])
            if j % 4 == 3:
                yield
        for j in range(b):
            lo = j // 8 * 8
            o_i = below(o_i, lo, o_i[lo:] + qk[j] * R[j:j + 1])
        U.append(R)
        o.append(o_i)
    U = jnp.concatenate(U, axis=0)
    g_end = gc[C - 1:C]                                         # log G_C
    if per_channel:   # a channel a state row: the row [1, dk] as a column
        decay = jnp.sum(jnp.where(iota((dk, dk), 0) == iota((dk, dk), 1), jnp.exp(g_end), 0.0),
                        axis=1, keepdims=True)
    else:
        decay = jnp.exp(g_end)
    S = decay * S + _dot(k * jnp.exp(g_end - gc), U, ((0,), (0,)))
    return jnp.concatenate(o, axis=0), S


def _chunk_kernel(q_ref, k_ref, v_ref, *rest, heads: int, per_channel: bool):
    """One (sequence, group of ``heads`` heads, sub-chunk) of the chunk
    form; the sub-chunks are the innermost, sequential grid axis. ``s_ref
    [heads, dk, dv]`` is the state's OUTPUT block, which every sub-chunk of
    a head group names: it stays in VMEM across them, is filled from
    ``s0_ref`` at the first and goes back to HBM once, behind the last.
    ``q_ref, k_ref [heads, C, dk]``, ``v_ref, o_ref [heads, C, dv]``;
    ``rows_ref [heads, 2, C]`` holds the log decay a head and, last, beta as
    ROWS (whole lane tiles in HBM, where a ``[C, 1]`` operand's minor axis
    is padded to 128 lanes); a decay a key channel comes as ``g_ref [heads,
    C, dk]`` in front of a ``rows_ref [heads, 1, C]`` that holds beta alone.
    The heads go ``_LOCKSTEP`` at a time, a stage of each in turn
    (:func:`_chunk_head` yields between stages): the program's order is what
    the scheduler fills one head's waits from."""
    g_ref, rows_ref, s0_ref, o_ref, s_ref = rest if per_channel else (None, *rest)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    for h0 in range(0, heads, _LOCKSTEP):
        live = {h: _chunk_head(q_ref[h], k_ref[h], v_ref[h],
                               g_ref[h] if per_channel else rows_ref[h, 0:1],
                               rows_ref[h, -1:], s_ref[h], per_channel=per_channel)
                for h in range(h0, min(heads, h0 + _LOCKSTEP))}
        while live:
            for h, stages in list(live.items()):
                try:
                    next(stages)
                except StopIteration as done:
                    o_ref[h], s_ref[h] = done.value
                    del live[h]


def chunk_kernel_choice(T: int) -> dict | None:  # dlint: static-fn
    """The chunk kernel's gate: :func:`step_kernel_choice`'s (the ONE mode
    gate, no mesh plan), and a sub-chunk ``gcd(T, SUB_CHUNK)`` that fills
    whole sublane tiles. Returns :func:`gated_delta_chunk` kwargs, or None
    for the XLA twin."""
    return step_kernel_choice() if math.gcd(T, SUB_CHUNK) % 8 == 0 else None


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_chunk(q, k, v, g, beta, S0, *, interpret: bool = False):
    """:func:`gated_delta_chunk_xla` as ONE Pallas kernel: a grid over
    (sequence, head group, sub-chunk) whose grid step holds a sub-chunk of
    :func:`chunk_heads_per_step` heads in VMEM and writes nothing back but
    ``o`` and, behind a head group's last sub-chunk, the state (aliased onto
    ``S0``). q, k, v (and a decay a key channel) go in heads first, ``[B, H,
    T, d]``: one transpose each in front and one of ``o`` behind."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = math.gcd(T, SUB_CHUNK)
    per_channel = g.ndim == 4
    hb = chunk_heads_per_step(H, C, dk, dv, per_channel)
    f32 = jnp.float32
    heads_first = lambda x: jnp.swapaxes(x.astype(f32), 1, 2)   # [B, H, T, d]
    vmem = pltpu.VMEM
    tokens = lambda d: pl.BlockSpec((None, hb, C, d), lambda b, h, n: (b, h, n, 0),
                                    memory_space=vmem)
    state = pl.BlockSpec((None, hb, dk, dv), lambda b, h, n: (b, h, 0, 0),
                         memory_space=vmem)
    operands = [(heads_first(x), tokens(x.shape[-1])) for x in (q, k, v)]
    if per_channel:
        operands.append((heads_first(g), tokens(dk)))
    # what is one number a token and head, as rows: [B, H, N, 1 or 2, C]
    rows = jnp.stack(([] if per_channel else [g]) + [beta], axis=-1).astype(f32)
    rows = jnp.transpose(rows.reshape(B, T // C, C, H, -1), (0, 3, 1, 4, 2))
    operands.append((rows, pl.BlockSpec((None, hb, None, rows.shape[3], C),
                                        lambda b, h, n: (b, h, n, 0, 0), memory_space=vmem)))
    operands.append((S0.astype(f32), state))
    o, S = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, per_channel=per_channel),
        grid=(B, H // hb, T // C),
        in_specs=[spec for _x, spec in operands],
        out_specs=[tokens(dv), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), f32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
        input_output_aliases={len(operands) - 1: 1},  # S0, the last operand
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gated_delta_chunk", interpret=interpret,
    )(*(x for x, _spec in operands))
    return jnp.swapaxes(o, 1, 2), S
