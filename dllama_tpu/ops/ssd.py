"""Mamba-2's state-space duality (Dao and Gu, "Transformers are SSMs", ICML
2024): the scalar-decay SSD mixer of a layer that runs it beside attention
(models/falcon_h1.py), in its two forms and their oracle.

Per head ``j`` of ``H`` (group ``j // (H / G)``: the ``G`` groups share their
``B`` and ``C``), with a float32 state ``S [P, N]`` (``P`` the head's width,
``N`` the state size) and per token an input ``x [P]``, a step ``dt > 0``, a
decay ``exp(dt A)`` with one scalar ``A < 0`` a head, and the group's ``B [N]``
and ``C [N]``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t

(the skip ``D x_t``, the gate and the norm are the model's). A token with
``dt = 0`` leaves the state as it was: that is how a padded position is masked.

* **step form** (:func:`ssd_step`): one token a row, the decode step. The
  state lives in a slot-indexed pool ``[layers, rows, H, P, N]``
  (runtime/kvblocks.StatePool) that goes through the call IN PLACE: ONE
  Pallas kernel, named ``ssd_step`` after its jitted entry in the compiled
  program and the device trace (as ``gated_delta_step`` is), reads each (row,
  head) state once, decays it, adds the outer product, reads it out and
  writes it once, aliased onto its input. It moves ``2 x 4 P N`` bytes a head
  for ``5 P N`` operations: HBM-bound. :func:`ssd_step_xla` is its twin for
  the CPU and its oracle.
* **chunk form** (:func:`ssd_chunk`): a prefill chunk. Inside sub-chunks of
  ``chunk`` tokens everything is a matmul, ``(C B^T * decay) (dt x)`` within
  and ``C S`` from the state before; only the pass over sub-chunks is
  sequential, state in and state out. The decays are exponentials of
  DIFFERENCES of a cumulative sum of ``dt A`` (never ratios of exponentials:
  the cumulative product underflows over a long chunk), float32 at
  ``highest``. There is no triangular inverse here (the delta rule's; a
  scalar decay needs none), so it is plain ``jax.numpy`` (XLA).
* :func:`ssd_recurrent`, the per-token scan, is the oracle of both.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the small matmuls feed a float32 state that is carried over thousands of
# tokens: full float32 precision on every backend
_PREC = jax.lax.Precision.HIGHEST
# the state one grid step of the step kernel moves each way, at most. On a v5e
# (tools/state_step_sweep.py; PERF.md section 5, PR 59) a grid step costs
# 0.3-0.45 us beside its bytes while the block is small (32 KB a head at P 64,
# N 128: 8 heads, 256 KB, ran at 56.6% of the HBM roof, 32 heads, 1 MB, at
# 67.8%) and nothing is left to win past 1 MB (2 MB: 68.0% there; 128 KB a
# head at P 128, N 256 reads 80.0% at 1 MB and at 2 MB, where a plain copy
# through the same blocks stands). The block in and the block out are each
# double-buffered: four blocks, 4 MB of the 16 MB a kernel's scope may hold
# there without a raised ``vmem_limit_bytes`` (the lane-padded vectors beside
# them are under 0.3 MB), so the call raises nothing
_STATE_BLOCK_BYTES = 1 << 20


def heads_per_step(H: int, G: int, P: int, N: int) -> int:  # dlint: static-fn
    """Heads one grid step of the step kernel moves: the largest count that
    divides ``H``, holds part of ONE group or WHOLE groups (a divisor or a
    multiple of ``H / G``: never parts of two groups, whose B and C a block
    could not name), and keeps the float32 state block ``hb x P x N`` at or
    under :data:`_STATE_BLOCK_BYTES`; 1 where one head's state is over it."""
    per_group = H // G
    most = max(1, _STATE_BLOCK_BYTES // (4 * P * N))
    return max(c for c in range(1, H + 1)
               if H % c == 0 and c <= most
               and (per_group % c == 0 or c % per_group == 0))


def _per_head(m: jax.Array, heads: int) -> jax.Array:
    """A group's ``B`` or ``C`` ``[..., G, N]`` for each of its heads:
    ``[..., H, N]``."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def _one_step(S, x, dt, decay, Bh, Ch):
    """The recurrence for one token: ``S [.., H, P, N]``, ``x [.., H, P]``,
    ``dt, decay [.., H]``, ``Bh, Ch [.., H, N]``. Products and sums on the
    vector unit: exact float32 on every backend."""
    S = decay[..., None, None] * S + (dt[..., None] * x)[..., None] * Bh[..., None, :]
    return S, jnp.sum(S * Ch[..., None, :], axis=-1)


def ssd_recurrent(x, dt, A, Bm, Cm, S0):
    """The recurrence as written, a scan over tokens. ``x [B, T, H, P]``,
    ``dt [B, T, H]``, ``A [H]``, ``Bm, Cm [B, T, G, N]``, ``S0 [B, H, P, N]``;
    all float32. Returns ``y [B, T, H, P]`` and ``S_T``."""
    H = x.shape[2]

    def body(S, xs):
        xt, dtt, bt, ct = xs
        return _one_step(S, xt, dtt, jnp.exp(dtt * A), _per_head(bt, H),
                         _per_head(ct, H))

    S, y = jax.lax.scan(body, S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), S


def ssd_step_xla(pool, layer, rows, x, dt, decay, Bm, Cm):
    """The step form in XLA: gather the rows' states of ``layer`` out of
    ``pool [layers, R, H, P, N]``, one recurrence step, scatter back. ``x [B,
    H, P]``, ``dt, decay [B, H]``, ``Bm, Cm [B, G, N]``. Returns ``y [B, H,
    P]`` and the pool."""
    H = x.shape[1]
    S = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[rows]
    S, y = _one_step(S, x, dt, decay, _per_head(Bm, H), _per_head(Cm, H))
    return y, pool.at[layer, rows].set(S)


def step_kernel_choice() -> dict | None:  # dlint: static-fn
    """The step kernel's gate, as ``gated_delta.step_kernel_choice`` is: the
    mode comes from :func:`quant_matmul.pallas_mode_gate` (the ONE gate;
    ``fast=False``: kernel and twin compute the same float32), and no mesh
    plan may be active. Returns :func:`ssd_step` kwargs, or None for the
    XLA twin."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    kw = pallas_mode_gate(False)
    if kw is None or current_plan() is not None:
        return None
    return {"interpret": kw["interpret"]}


def _step_kernel(layer_ref, rows_ref, xa_ref, bc_ref, s_ref, y_ref, s_out_ref,
                 *, heads: int, groups: int):
    """One (row, block of ``heads`` heads) of the step form. ``xa_ref [1, 1,
    2, P, heads]`` holds ``dt x`` (plane 0) and the decay (plane 1, repeated
    down the column) with a head a LANE: head ``h``'s column is ``[:, h:h +
    1]``; ``bc_ref [1, groups, 8, N]`` the B and C of the block's ``groups``
    groups as rows 0 and 1 (one group where the block is part of it, head
    ``h``'s is ``h // (heads / groups)``); ``s_ref [heads, P, N]`` is the
    state, read once, and ``s_out_ref`` the same cells of the same pool,
    written once; ``y_ref [1, 1, P, heads]`` takes head ``h``'s readout as its
    column ``h``."""
    del layer_ref, rows_ref  # spent in the index maps
    bc = [(bc_ref[0, g, 0:1, :], bc_ref[0, g, 1:2, :])    # [1, N] each
          for g in range(groups)]
    for h in range(heads):
        b, c = bc[h * groups // heads]
        dx = xa_ref[0, 0, 0, :, h:h + 1]          # [P, 1]
        decay = xa_ref[0, 0, 1, :, h:h + 1]
        S = s_ref[h] * decay + dx * b
        s_out_ref[h] = S
        y_ref[0, 0, :, h:h + 1] = jnp.sum(S * c, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_step(pool, layer, rows, x, dt, decay, Bm, Cm, *,
             interpret: bool = False):
    """:func:`ssd_step_xla` as ONE Pallas kernel over the pool in place:
    layer and rows ride in as scalar-prefetch operands, the index maps pick
    ``(layer, rows[b], block of heads)``, and the pool's output is aliased
    onto its input, so cells no row names are never touched. A block is
    :func:`heads_per_step` heads, chosen from the shapes seen here: part of
    one group, or ``gb`` whole groups whose B and C ride in together.

    The kernel wants ``dt x`` and the decay as COLUMNS down a head's ``P``
    sublanes. They reach it ``[B, H / hb, 2, P, hb]``, a grid step's ``hb``
    heads side by side on the lanes, and ``y`` comes back ``[B, H / hb, P,
    hb]``: the tiled layout pads a minor dimension to 128 lanes, so a head a
    lane costs ``128 / hb`` times the values where a minor dimension of 2 (or
    1) cost 64 (or 128) times (at 32 rows x 128 heads x 64 that was 134 MB
    an operand a layer, written and read around a kernel that moves 270 MB
    of state: PERF.md section 6, PR 51)."""
    _L, _R, H, P, N = pool.shape
    B, G = x.shape[0], Bm.shape[1]
    per_group = H // G
    hb = heads_per_step(H, G, P, N)
    gb = max(1, hb // per_group)
    f32 = jnp.float32
    by_lane = lambda a: jnp.swapaxes(a.reshape(B, H // hb, hb, P), 2, 3)
    xa = jnp.stack([by_lane((dt[..., None] * x).astype(f32)),
                    by_lane(jnp.broadcast_to(decay.astype(f32)[..., None],
                                             (B, H, P)))],
                   axis=2)                                # [B, H / hb, 2, P, hb]
    bc = jnp.stack([Bm.astype(f32), Cm.astype(f32)]
                   + [jnp.zeros((B, G, N), f32)] * 6, axis=2)       # [B, G, 8, N]
    vmem = pltpu.VMEM
    state = pl.BlockSpec((None, None, hb, P, N),
                         lambda b, h, l, r: (l[0], r[b], h, 0, 0),
                         memory_space=vmem)
    columns = pl.BlockSpec((1, 1, 2, P, hb), lambda b, h, l, r: (b, h, 0, 0, 0),
                           memory_space=vmem)
    readout = pl.BlockSpec((1, 1, P, hb), lambda b, h, l, r: (b, h, 0, 0),
                           memory_space=vmem)
    group = pl.BlockSpec((1, gb, 8, N),
                         lambda b, h, l, r: (b, (h * hb) // (per_group * gb), 0, 0),
                         memory_space=vmem)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # layer, rows
        grid=(B, H // hb),
        in_specs=[columns, group, state],
        out_specs=[readout, state],
    )
    y, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, groups=gb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H // hb, P, hb), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},  # the pool, after layer rows xa bc
        name="ssd_step", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      xa, bc, pool)
    return jnp.swapaxes(y, 2, 3).reshape(B, H, P), pool


def _mm(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(spec, a, b, precision=_PREC,
                      preferred_element_type=jnp.float32)


def ssd_chunk(x, dt, A, Bm, Cm, S0, chunk: int):
    """The chunk form: same arguments and results as :func:`ssd_recurrent`,
    chunkwise parallel over sub-chunks of ``gcd(T, chunk)`` tokens. With
    ``a_t = dt_t A`` and ``c_t`` its cumulative sum inside a sub-chunk::

        y_t = exp(c_t) C_t S_0 + sum_{j<=t} exp(c_t - c_j) (C_t . B_j) dt_j x_j
        S_C = exp(c_C) S_0 + sum_j exp(c_C - c_j) dt_j x_j B_j^T

    Every exponent is a difference that is <= 0. The heads of a group share
    ``C B^T``; nothing is repeated per head. No loop over tokens: one scan
    over the ``T / C`` sub-chunks carries the state. The whole form is XLA
    under the named scope ``ssd_chunk`` (as ``ops/mla.py`` names its XLA
    form), so a device trace can tell its fusions from a program's others."""
    with jax.named_scope("ssd_chunk"):
        return _ssd_chunk(x, dt, A, Bm, Cm, S0, chunk)


def _ssd_chunk(x, dt, A, Bm, Cm, S0, chunk: int):
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    K = H // G
    C = math.gcd(T, chunk)
    NC = T // C
    # [B, T, ...] -> sub-chunk major, the heads as (group, head of the group)
    dtx = jnp.transpose((x * dt[..., None]).reshape(B, NC, C, G, K, P),
                        (1, 0, 3, 4, 2, 5))                  # [NC, B, G, K, C, P]
    a = jnp.transpose((dt * A).reshape(B, NC, C, G, K), (1, 0, 3, 4, 2))
    cum = jnp.cumsum(a, axis=-1)                             # [NC, B, G, K, C]
    by_group = lambda m: jnp.transpose(m.reshape(B, NC, C, G, N), (1, 0, 3, 2, 4))
    Bg, Cg = by_group(Bm), by_group(Cm)                      # [NC, B, G, C, N]
    idx = jnp.arange(C)
    lower = idx[:, None] >= idx[None, :]
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                     # [NC, B, G, K, C, C]
    cb = _mm("nbgcs,nbgds->nbgcd", Cg, Bg)                   # C_t . B_j
    y_intra = _mm("nbgkcd,nbgkdp->nbgkcp", cb[:, :, :, None] * decay, dtx)
    into = jnp.exp(cum)                                      # exp(c_t)
    out_of = dtx * jnp.exp(cum[..., -1:] - cum)[..., None]   # exp(c_C - c_j) dt_j x_j
    end = jnp.exp(cum[..., -1])                              # exp(c_C)

    def body(S, xs):
        Cg_n, Bg_n, into_n, out_n, end_n = xs
        y = _mm("bgcn,bgkpn->bgkcp", Cg_n, S) * into_n[..., None]
        S = end_n[..., None, None] * S + _mm("bgkcp,bgcn->bgkpn", out_n, Bg_n)
        return S, y

    S, y_inter = jax.lax.scan(body, S0.reshape(B, G, K, P, N),
                              (Cg, Bg, into, out_of, end))
    y = jnp.transpose(y_intra + y_inter, (1, 0, 4, 2, 3, 5))  # [B, NC, C, G, K, P]
    return y.reshape(B, T, H, P), S.reshape(B, H, P, N)
