"""Pallas TPU kernel ``expert_gemv``: the decode step's routed matmuls.

A decode step of ``B`` rows routes each row to ``k`` experts: ``B x k``
(row, expert) PAIRS, of which this chip computes the ones whose expert it
holds, for rows that are live. Each pair is one GEMV over one expert's Q40
planes: ``y[p] = x[p] @ dequant(stack[layer, expert[p]])``.

The fused dequant-GEMV of :mod:`quant_matmul` takes a layer stack ``[L, K,
N]`` and a layer index; an expert stack ``[L, E, K, N]`` needs a second
index, per pair. The XLA form gathers every pair's planes into a copy
(``stack[layer][experts]``: 3 MB a pair a matrix at 3072 x 1024) before it
reads them. This kernel reads a chosen expert's planes out of the stack
where it lies, once a pair:

* the pools stay in HBM (``memory_space=ANY``); the layer, each pair's
  expert and the number of pairs ride in as scalar-prefetch operands;
* ONE grid step loops over the first ``n_pairs`` pairs (a traced trip
  count: the caller compacts the held, live pairs to the front, so absent
  experts and dead rows cost nothing, not even a skipped grid step);
* a pair's two planes (codes ``[K, N]`` int8, scales ``[K/32, N]``) arrive
  by two DMAs into one half of a double buffer while the previous pair is
  dequantized and multiplied: the next pair's fetch runs under this pair's
  work;
* a plane too wide for VMEM whole (7168 x 2048 is 58.7 MB with its two
  landing halves and its dequantized copy) is walked in STRIPES of ``tn`` of
  its ``N`` output columns (:func:`stripe`: the widest divisor of ``N`` in
  whole lane tiles whose resident set fits), a stripe a double-buffer half:
  the contraction stays whole, so a stripe's columns are final and no
  partial sums are kept. A plane that fits (3072 x 1024) is one stripe and
  the kernel is what it was;
* the dequant is :func:`quant_matmul._decode_kernel`'s: one Q40 block of 32
  rows at a time on the VPU, the scale rounded to the dequant dtype first,
  then ONE dot over the whole contraction (``[1, K] @ [K, N]``), so a pair's
  result is what the fused kernel gives for the same plane and row.

Rows ``p >= n_pairs`` of the result are zero. Mode selection routes through
:func:`quant_matmul.pallas_mode_gate` (the one gate): the kernel on a TPU
(interpret mode where forced off one, the test path), :func:`expert_gemv_xla`
(gather, dequant, dot: the oracle, and the CPU's form) otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..formats.quants import Q40_BLOCK_SIZE
from .linear import QuantizedWeight, dequantize_weight

_HIGHEST = jax.lax.Precision.HIGHEST

# VMEM the kernel asks Mosaic for: two landing halves of codes and scales,
# the dequantized plane, the widened scales, the pairs' inputs and outputs
# (a 3072 x 1024 expert at 160 pairs is some 19 MB; a v5e has 128)
_VMEM_LIMIT = 48 * 1024 * 1024
_VMEM_BUDGET = 36 * 1024 * 1024


def _kernel(layer_ref, eid_ref, n_ref, x_ref, codes_hbm, scales_hbm, out_ref,
            cbuf, sbuf, sems, wd_ref, s32_ref, *, groups: int, fast: bool):
    layer, n = layer_ref[0], n_ref[0]
    wd_dt = wd_ref.dtype
    out_ref[...] = jnp.zeros_like(out_ref)
    tn = cbuf.shape[2]
    n_stripes = out_ref.shape[1] // tn

    def copies(p, t, slot):
        """Stripe ``t`` (static) of pair ``p``'s two planes into half
        ``slot``."""
        e = eid_ref[p]
        cols = () if n_stripes == 1 else (slice(None), pl.ds(t * tn, tn))
        at = lambda hbm: hbm.at[(layer, e) + cols]
        return (pltpu.make_async_copy(at(codes_hbm), cbuf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(at(scales_hbm), sbuf.at[slot],
                                      sems.at[1, slot]))

    @pl.when(n > 0)
    def _():
        for c in copies(0, 0, 0):
            c.start()

    n_blocks = cbuf.shape[1] // Q40_BLOCK_SIZE

    def pair(p, carry):
        for t in range(n_stripes):
            slot = (p * n_stripes + t) % 2
            if t + 1 < n_stripes:
                for c in copies(p, t + 1, 1 - slot):
                    c.start()
            else:
                @pl.when(p + 1 < n)
                def _(slot=slot):
                    for c in copies(p + 1, 0, 1 - slot):
                        c.start()

            for c in copies(p, t, slot):
                c.wait()
            s32_ref[...] = sbuf[slot].astype(wd_dt).astype(jnp.float32)

            def dequant(c, carry, slot=slot):
                for j in range(groups):
                    g = c * groups + j
                    k0 = pl.multiple_of(g * Q40_BLOCK_SIZE, Q40_BLOCK_SIZE)
                    rows = pl.ds(k0, Q40_BLOCK_SIZE)
                    wd_ref[rows, :] = (cbuf[slot, rows, :].astype(jnp.float32)
                                       * s32_ref[pl.ds(g, 1), :]).astype(wd_dt)
                return carry

            jax.lax.fori_loop(0, n_blocks // groups, dequant, 0)
            out_ref[pl.ds(p, 1), t * tn:(t + 1) * tn] = jax.lax.dot_general(
                x_ref[pl.ds(p, 1), :].astype(wd_dt), wd_ref[...],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=None if fast else _HIGHEST)
        return carry

    jax.lax.fori_loop(0, n, pair, 0)


def stripe(n_pairs: int, K: int, N: int, fast: bool, *,
           compiled: bool = True) -> int | None:  # dlint: static-fn
    """Output columns a stripe of one expert's planes takes: the widest
    ``N / i`` in whole lane tiles (``compiled``: for Mosaic, whose DMAs land
    them; interpret mode takes whole sublanes) for which a stripe twice, its
    dequantized copy and the pairs' rows fit the kernel's VMEM budget. ``N``
    itself where the whole plane fits; None where nothing does."""
    lane = 128 if compiled else 8
    if K % Q40_BLOCK_SIZE or N % lane or n_pairs < 1:
        return None
    wd_bytes = 2 if fast else 4
    kb = K // Q40_BLOCK_SIZE
    rows = 2 * n_pairs * (K + N) * 4
    for i in range(1, N // lane + 1):
        tn = N // i
        if N % i or tn % lane:
            continue
        if K * tn * (2 + wd_bytes) + kb * tn * (2 * 4 + 4) + rows \
                <= _VMEM_BUDGET:
            return tn
    return None


def supports(n_pairs: int, K: int, N: int, fast: bool, *,
             compiled: bool = True) -> bool:  # dlint: static-fn
    """Whether the kernel covers these planes, whole or in stripes."""
    return stripe(n_pairs, K, N, fast, compiled=compiled) is not None


def kernel_choice(n_pairs: int, stack: QuantizedWeight,
                  fast: bool) -> dict | None:  # dlint: static-fn
    """The expert GEMV's gate: :func:`quant_matmul.pallas_mode_gate` (the ONE
    gate) asked about one pair's shapes, one row over one expert's planes,
    then this kernel's own VMEM predicate. Returns :func:`expert_gemv`
    kwargs, or None (the XLA gather form)."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    K, N = stack.codes.shape[-2:]
    one = QuantizedWeight(
        scales=jax.ShapeDtypeStruct((K // Q40_BLOCK_SIZE, N),
                                    stack.scales.dtype),
        codes=jax.ShapeDtypeStruct((K, N), jnp.int8))
    kw = pallas_mode_gate(fast, (1, K), one)
    if kw is None or current_plan() is not None \
            or not supports(n_pairs, K, N, fast,
                            compiled=not kw["interpret"]):
        return None
    return {"interpret": kw["interpret"], "fast": fast}


def _stripe_of(P: int, K: int, N: int, fast: bool, interpret: bool) -> int:
    return stripe(P, K, N, fast, compiled=not interpret) or N


@functools.partial(jax.jit, static_argnames=("interpret", "fast"))
def expert_gemv(x: jax.Array, stack: QuantizedWeight, layer: jax.Array,
                experts: jax.Array, n_pairs: jax.Array, *,
                interpret: bool = False, fast: bool = False) -> jax.Array:
    """``y[p] = x[p] @ dequant(stack[layer, experts[p]])`` for ``p <
    n_pairs``, zero rows after: ``x [P, K]``, ``stack`` codes ``[L, E, K,
    N]`` and scales ``[L, E, K/32, N]``, ``layer`` and ``n_pairs`` traced
    int32 scalars, ``experts [P]`` int32 (entries at or past ``n_pairs`` are
    not read). float32 ``[P, N]``."""
    P, K = x.shape
    N = stack.codes.shape[-1]
    kb = K // Q40_BLOCK_SIZE
    groups = next(c for c in (8, 4, 2, 1) if kb % c == 0)
    wd_dtype = jnp.bfloat16 if fast else jnp.float32
    tn = _stripe_of(P, K, N, fast, interpret)
    whole = lambda i, *_: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, each pair's expert, the pair count
        grid=(1,),
        in_specs=[pl.BlockSpec((P, K), whole, memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((P, N), whole, memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, K, tn), jnp.int8),
            pltpu.VMEM((2, kb, tn), stack.scales.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (codes | scales, half)
            pltpu.VMEM((K, tn), wd_dtype),
            pltpu.VMEM((kb, tn), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_kernel, groups=groups, fast=fast),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="expert_gemv", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.asarray(experts, jnp.int32),
      jnp.reshape(n_pairs, (1,)).astype(jnp.int32),
      x.astype(jnp.float32), stack.codes, stack.scales)


def expert_gemv_xla(x: jax.Array, stack: QuantizedWeight, layer: jax.Array,
                    experts: jax.Array, n_pairs: jax.Array, *,
                    fast: bool = False) -> jax.Array:
    """:func:`expert_gemv`'s oracle, and the form every backend but a TPU
    runs: gather each pair's planes out of layer ``layer``, dequantize,
    one dot a pair. It computes all ``P`` rows (XLA's shapes are static) and
    zeroes those at or past ``n_pairs``."""
    P = x.shape[0]
    at = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    experts = jnp.where(jnp.arange(P) < n_pairs, experts, 0)
    w = QuantizedWeight(scales=at(stack.scales)[experts],
                        codes=at(stack.codes)[experts])
    dt = jnp.bfloat16 if fast else jnp.float32
    wd = dequantize_weight(w, dtype=dt)
    y = jnp.einsum("pk,pkn->pn", x.astype(dt), wd,
                   preferred_element_type=jnp.float32,
                   precision=None if fast else _HIGHEST)
    return jnp.where((jnp.arange(P) < n_pairs)[:, None], y, 0.0)
