"""Pallas TPU kernel ``expert_chunk``: a prefill chunk's routed matmuls.

A chunk of ``N`` rows routes each row to ``k`` experts; the pairs this chip
holds, SORTED by expert, fall into RUNS that share one expert's Q40 planes.
The decode form (:mod:`expert_gemv`) fetches and dequantizes a plane once a
pair, which is right at a step's 8-90 pairs; a chunk has hundreds of pairs
over the same held experts, and until PR 43 ran every chosen plane over
EVERY row of the chunk (``held / k`` times the pairs' FLOPs). This kernel
fetches and dequantizes a plane once a RUN and multiplies it with that run's
rows alone:

* the stack ``[L, E, K, N]`` stays in HBM; the layer, each run's expert,
  first tile and length, the number of runs and the sorted pairs' rows (for
  the down projection also their places among the router's weights, and
  those) ride in as scalar-prefetch operands. ONE grid step loops over the runs (a
  traced trip count: an expert nobody chose costs nothing);
* a run's plane arrives by two DMAs (codes, scales) into one half of a
  double buffer under the previous run's work, in STRIPES of ``tn`` output
  columns where it does not fit VMEM whole (:func:`stripe`), and is
  dequantized ONCE by :func:`quant_matmul.dequant_blocks`, the fused
  kernel's block-at-a-time dequant (the scale rounded to the dequant dtype
  first);
* the run's rows are multiplied in TILES of :data:`TILE_ROWS` (``[tm, K] @
  [K, tn]``, one dot over the whole contraction, float32 accumulation: a
  pair's result is what the fused chunk kernel gives for the same plane and
  row); tiles a run is a traced trip count too, and a run longer than a tile
  keeps its dequantized stripe over its tiles.

Between the three projections the pairs live in the FED layout: run ``j``
owns whole tiles, rows ``[tile0[j] tm, tile0[j] tm + len[j])``, the rest of
its last tile padding that is multiplied and never read. The kernel has two
ends (static ``scatter``):

* gather (gate, up): ``x [N, K]`` whole in VMEM, a run's rows picked out of
  it by the sorted pairs' token rows, the result written to the run's tiles
  of ``[F, N_out]`` float32;
* scatter (down): ``x [F, K]`` in the fed layout, a tile read where it
  lies, each pair's result row weighted and ADDED to its token's row of
  ``[N, N_out]`` float32, in the sorted order: a token's experts ascending,
  the order the every-row form summed them in.

Results stay float32 between the projections, as the decode form keeps
``expert_gemv``'s (its caller rounds the gate-times-up product ONCE to the
activation dtype). That is also what the every-row form comes to on a TPU,
where XLA elides the round trip through bfloat16 that ``linear`` states for
each projection's result: with the results rounded inside this kernel the
two forms differed by one bfloat16 rounding in 36-97% of a layer's elements
(PERF.md section 6, PR 43).

Rows of a gather result that no pair owns are not written (whatever the
buffer held). Mode selection routes through
:func:`quant_matmul.pallas_mode_gate` (:func:`kernel_choice`); off a TPU and
under a plan the every-row form through ``linear`` stays
(``models.share._experts_chunk_xla``: also this kernel's oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..formats.quants import Q40_BLOCK_SIZE
from .linear import QuantizedWeight
from .quant_matmul import dequant_blocks

_HIGHEST = jax.lax.Precision.HIGHEST

# rows a tile: whole sublane tiles of either dtype. A dot of 32 rows costs
# the MXU what one of 1 does (the plane's blocks are pushed either way: on a
# v5e a plane of 7168 x 2048 reads 22.6 us at 1-32 rows a run, 84% of its
# fetch's roof), so a run of up to 32 rows, which both clients' mostly are,
# is ONE pass over its plane; at 16 a run of 32 rows reads 32.7 us and one
# of 64 52.7 against 25.9 (tools/expert_chunk_sweep.py, PERF.md section 6).
# A taller tile pads the fed layout by ``held x tm`` and buys nothing
TILE_ROWS = 32

# VMEM asked of Mosaic, and what the resident set may take of it: the
# chunk's rows and the result once each (whole-array VMEM operands: a blocked
# one is double-buffered even over one grid step), a run's gathered rows, two
# landing halves of a stripe and its dequantized copy (a v5e has 128 MB)
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 72 * 1024 * 1024


def fed_rows(n_pairs: int, n_experts: int, tm: int = TILE_ROWS) -> int:  # dlint: static-fn
    """The static bound on the fed layout's rows: every pair, and the
    padding of each held expert's last tile."""
    return (n_pairs // tm + n_experts) * tm


def _kernel(layer_ref, n_ref, eid_ref, tile0_ref, pair0_ref, len_ref,
            rows_ref, *refs, groups: int, tm: int, scatter: bool, fast: bool):
    if scatter:
        (at_ref, w_ref, x_ref, codes_hbm, scales_hbm, out_ref,
         cbuf, sbuf, sems, wd_ref, s32_ref, y_ref) = refs
    else:
        (x_ref, codes_hbm, scales_hbm, out_ref,
         cbuf, sbuf, sems, wd_ref, s32_ref, xr_ref) = refs
    layer, n = layer_ref[0], n_ref[0]
    wd_dt = wd_ref.dtype
    tn = cbuf.shape[2]
    n_stripes = out_ref.shape[1] // tn
    if scatter:
        out_ref[...] = jnp.zeros_like(out_ref)
    else:
        # padding rows of a tile are multiplied: whatever VMEM held must
        # at least be numbers the MXU takes
        xr_ref[...] = jnp.zeros_like(xr_ref)

    def copies(j, t, slot):
        """Stripe ``t`` (static) of run ``j``'s two planes into half
        ``slot``."""
        e = eid_ref[j]
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

    def run(j, carry):
        tile0, pair0, length = tile0_ref[j], pair0_ref[j], len_ref[j]
        n_tiles = (length + tm - 1) // tm

        if not scatter:
            def pick(r, carry):
                xr_ref[pl.ds(r, 1), :] = x_ref[pl.ds(rows_ref[pair0 + r], 1), :]
                return carry

            jax.lax.fori_loop(0, length, pick, 0)

        for t in range(n_stripes):
            slot = (j * n_stripes + t) % 2
            if t + 1 < n_stripes:
                for c in copies(j, t + 1, 1 - slot):
                    c.start()
            else:
                @pl.when(j + 1 < n)
                def _(slot=slot):
                    for c in copies(j + 1, 0, 1 - slot):
                        c.start()

            for c in copies(j, t, slot):
                c.wait()
            s32_ref[...] = sbuf[slot].astype(wd_dt).astype(jnp.float32)
            dequant_blocks(cbuf.at[slot], s32_ref, wd_ref, groups)
            cols = slice(t * tn, (t + 1) * tn)

            def tile(i, carry, cols=cols):
                fed0 = pl.multiple_of((tile0 + i) * tm, tm)
                if scatter:
                    xt = x_ref[pl.ds(fed0, tm), :]
                else:
                    xt = xr_ref[pl.ds(pl.multiple_of(i * tm, tm), tm), :]
                y = jax.lax.dot_general(
                    xt.astype(wd_dt), wd_ref[...],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=None if fast else _HIGHEST)
                if not scatter:
                    out_ref[pl.ds(fed0, tm), cols] = y
                    return carry
                y_ref[...] = y

                def add(r, carry):
                    p = pair0 + i * tm + r
                    row = pl.ds(rows_ref[p], 1)
                    out_ref[row, cols] = (out_ref[row, cols] + w_ref[at_ref[p]]
                                          * y_ref[pl.ds(r, 1), :])
                    return carry

                jax.lax.fori_loop(0, jnp.minimum(tm, length - i * tm), add, 0)
                return carry

            jax.lax.fori_loop(0, n_tiles, tile, 0)
        return carry

    jax.lax.fori_loop(0, n, run, 0)


def stripe(rows: int, fed: int, K: int, N: int, fast: bool, scatter: bool,
           *, x_bytes: int = 2, compiled: bool = True,
           tm: int = TILE_ROWS) -> int | None:  # dlint: static-fn
    """Output columns a stripe of one expert's planes takes: the widest
    ``N / i`` in whole lane tiles (``compiled``: for Mosaic; interpret mode
    takes whole sublanes) for which two landing halves, the dequantized
    copy and the call's resident rows (``rows`` of the chunk, ``fed`` of
    the fed layout) fit the VMEM budget. None where nothing does."""
    lane = 128 if compiled else 8
    if K % Q40_BLOCK_SIZE or N % lane or rows < 1 or fed % tm:
        return None
    wd_bytes = 2 if fast else 4
    kb = K // Q40_BLOCK_SIZE
    if scatter:
        resident = fed * K * x_bytes + rows * N * 4
    else:
        resident = (2 * rows + tm) * K * 4 + fed * N * 4
    for i in range(1, N // lane + 1):
        tn = N // i
        if N % i or tn % lane:
            continue
        if (K * tn * (2 + wd_bytes) + kb * tn * (2 * 4 + 4) + tm * tn * 4
                + resident <= _VMEM_BUDGET):
            return tn
    return None


def kernel_choice(rows: int, fed: int, stack: QuantizedWeight, fast: bool,
                  scatter: bool, x_bytes: int = 2) -> dict | None:  # dlint: static-fn
    """The grouped kernel's gate: :func:`quant_matmul.pallas_mode_gate` (the
    ONE gate) asked about the chunk's rows over one expert's planes, as
    ``linear`` asks it for the every-row form, no mesh plan, then this
    kernel's own VMEM predicate. Returns :func:`expert_chunk` kwargs, or
    None (the every-row form)."""
    from ..parallel.api import current_plan
    from .quant_matmul import pallas_mode_gate

    K, N = stack.codes.shape[-2:]
    one = QuantizedWeight(
        scales=jax.ShapeDtypeStruct((K // Q40_BLOCK_SIZE, N),
                                    stack.scales.dtype),
        codes=jax.ShapeDtypeStruct((K, N), jnp.int8))
    kw = pallas_mode_gate(fast, (rows, K), one)
    if kw is None or current_plan() is not None \
            or stripe(rows, fed, K, N, fast, scatter, x_bytes=x_bytes,
                      compiled=not kw["interpret"]) is None:
        return None
    return {"interpret": kw["interpret"], "fast": fast}


@functools.partial(jax.jit, static_argnames=("rows_out", "interpret", "fast",
                                             "tn", "tm"))
def expert_chunk(x: jax.Array, stack: QuantizedWeight, layer: jax.Array,
                 runs, rows: jax.Array, weights: tuple | None = None, *,
                 rows_out: int, interpret: bool = False, fast: bool = False,
                 tn: int | None = None, tm: int = TILE_ROWS) -> jax.Array:
    """One projection of a chunk's held pairs, a plane a run.

    ``runs = (n_runs, expert [E], tile0 [E], pair0 [E], length [E])``: the
    runs of the sorted pairs (entries at or past ``n_runs`` are not read);
    ``rows [P]`` each sorted pair's token row. Without ``weights`` (gather)
    ``x`` is the chunk ``[N, K]`` and the result ``[rows_out, N_out]`` in the
    fed layout, float32, written in the runs' tiles alone. With
    ``weights = (at [P], w)`` (scatter: the router's weights as it gave
    them, and each sorted pair's place among them, so no sorted copy is
    gathered) ``x`` is ``[F, K]`` in the fed layout and the result
    ``[rows_out, N_out]`` float32: ``sum_p w[at[p]] (x[fed(p)] @
    W[expert(p)])`` over the pairs of each token row."""
    n_runs, experts, tile0, pair0, length = runs
    K = x.shape[1]
    N = stack.codes.shape[-1]
    kb = K // Q40_BLOCK_SIZE
    groups = next(c for c in (8, 4, 2, 1) if kb % c == 0)
    wd_dtype = jnp.bfloat16 if fast else jnp.float32
    n_rows, fed = ((x.shape[0], rows_out) if weights is None
                   else (rows_out, x.shape[0]))
    if tn is None:
        tn = stripe(n_rows, fed, K, N, fast, weights is not None,
                    x_bytes=x.dtype.itemsize, compiled=not interpret,
                    tm=tm) or N
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    prefetch = [jnp.reshape(i32(layer), (1,)), jnp.reshape(i32(n_runs), (1,)),
                i32(experts), i32(tile0), i32(pair0), i32(length), i32(rows)]
    if weights is None:
        # a single row is picked by a dynamic sublane index: float32 rows
        # are what one can address
        x = x.astype(jnp.float32)
        last = pltpu.VMEM((-(-x.shape[0] // tm) * tm, K), jnp.float32)
    else:
        prefetch += [i32(weights[0]),
                     jnp.asarray(weights[1], jnp.float32).reshape(-1)]
        last = pltpu.VMEM((tm, tn), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, K, tn), jnp.int8),
            pltpu.VMEM((2, kb, tn), stack.scales.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (codes | scales, half)
            pltpu.VMEM((K, tn), wd_dtype),
            pltpu.VMEM((kb, tn), jnp.float32),
            last,
        ])
    return pl.pallas_call(
        functools.partial(_kernel, groups=groups, tm=tm,
                          scatter=weights is not None, fast=fast),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_out, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="expert_chunk", interpret=interpret,
    )(*prefetch, x, stack.codes, stack.scales)
