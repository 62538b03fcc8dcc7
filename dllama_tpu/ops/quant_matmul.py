"""Pallas TPU kernel: Q40 weight-dequantizing matmul.

The TPU replacement for the reference's Q80×Q40 integer-dot kernels
(reference: matmul_Q80_Q40_F32, src/nn/nn-cpu-ops.cpp:229-447, and the
llamafile sgemm prefill path, SURVEY.md §2 #7): weights stream from HBM in
their K-major plane layout (int8 codes ``[K, N]`` + f32 scales ``[K/32, N]``)
and are dequantized in VMEM right before hitting the MXU — the dense weight
never exists in HBM, so the matmul moves ~3.5× fewer bytes than a dense-f32
weight would.

Kernel shape: ``y[M, N] = x[M, K] @ dequant(codes, scales)``

Grid ``(N // BN, K // BK)``; each step:

1. expands the step's scale block to ``[BK, BN]`` via a tiny MXU matmul with a
   constant 0/1 sublane-expansion matrix ``E[BK, BK/32]`` (this Mosaic
   toolchain rejects reshape-broadcast and ``jnp.repeat`` lowerings, and
   ``pltpu.repeat`` has tile-repeat — not element-repeat — semantics);
2. dequantizes codes on the VPU (``codes * sexp``);
3. accumulates ``x_blk @ wd`` into the revisited f32 output tile.

Both dots run at ``Precision.HIGHEST`` — measured ~2e-5 absolute error vs the
exact host oracle on real hardware (default MXU precision loses ~3e-3).
K-major layout is what makes every operand block-indexable: the out-major
layout needed narrow f16/f32 scale blocks or in-kernel dynamic slices, both
of which this Mosaic build refuses to lower.

Falls back to the XLA dequant+dot path (ops.linear) when shapes don't fit the
tile grid; parity is tested in tests/test_quant_matmul.py the way
nn-vulkan-test.cpp checks GPU ops against the CPU reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..parallel.api import current_plan, on_tpu, shard_map
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..formats.quants import Q40_BLOCK_SIZE
from .linear import QuantizedWeight

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(x_ref, codes_ref, scales_ref, expand_ref, out_ref, *, fast: bool):
    """One (n, k) grid step: out[M, BN] += x[M, BK] @ dequant(W[BK, BN]).

    ``fast=False`` (exact/parity mode): f32 dequant, both dots at
    ``Precision.HIGHEST`` (~6 bf16 MXU passes per dot) — matches the host
    oracle to ~2e-5.  ``fast=True`` (serving mode): dequant lands in bf16 and
    the main dot runs ONE default-precision MXU pass with f32 accumulation —
    the TPU analogue of the reference's integer-dot philosophy (Q80×Q40
    int8-dot with f32 per-block scale epilogue, nn-cpu-ops.cpp:229-447):
    low-precision multiplies, full-precision accumulate, scales applied at
    block granularity.
    """
    k = pl.program_id(1)

    # element-repeat each scale 32× along K (sublanes) as a 0/1 matmul; each
    # output is a single selected scale (no accumulation), so HIGHEST here
    # costs little and keeps exact-mode scales bit-clean
    sexp = jax.lax.dot_general(
        expand_ref[:], scales_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)

    if fast:
        wd = codes_ref[:].astype(jnp.bfloat16) * sexp.astype(jnp.bfloat16)
        partial = jax.lax.dot_general(
            x_ref[:], wd,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        wd = codes_ref[:].astype(jnp.float32) * sexp
        partial = jax.lax.dot_general(
            x_ref[:], wd,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_HIGHEST)

    @pl.when(k == 0)
    def _():
        out_ref[:] = partial

    @pl.when(k != 0)
    def _():
        out_ref[:] += partial


# default tile candidates, largest first (gemv_sweep picks these)
BN_CANDIDATES = (512, 256, 128)
BK_CANDIDATES = (512, 256, 128)


def dequant_blocks(codes_ref, s32_ref, wd_ref, groups: int) -> None:
    """``wd_ref [K, n] = codes_ref [K, n] * s32_ref [K/32, n]``, one Q40
    block of 32 rows at a time: the dequant of :func:`_decode_kernel` (its
    docstring has the reasons), shared with the kernels that land a plane
    themselves (``ops.expert_chunk``). The product is taken in float32
    over scales already rounded to ``wd_ref``'s dtype, and rounds once."""
    wd_dt = wd_ref.dtype

    def dequant(c, carry):
        # `groups` Q40 blocks a trip, unrolled: independent loads, converts
        # and stores for the scheduler to overlap
        for j in range(groups):
            g = c * groups + j
            k0 = pl.multiple_of(g * Q40_BLOCK_SIZE, Q40_BLOCK_SIZE)
            rows = pl.ds(k0, Q40_BLOCK_SIZE)
            wd_ref[rows, :] = (codes_ref[rows, :].astype(jnp.float32)
                               * s32_ref[pl.ds(g, 1), :]).astype(wd_dt)
        return carry

    n_blocks = codes_ref.shape[0] // Q40_BLOCK_SIZE
    jax.lax.fori_loop(0, n_blocks // groups, dequant, 0)


def _decode_kernel(x_ref, codes_ref, scales_ref, out_ref, wd_ref, s32_ref,
                   *, groups: int, fast: bool):
    """One n-column stripe of the DECODE-shaped fused dequant-GEMV.

    Unlike :func:`_kernel`'s (n, k) grid, the decode kernel keeps the whole
    K axis in one block: the grid walks N only, each step streams the full
    ``[K, bn]`` code stripe from HBM once, dequantizes it into the ``wd``
    VMEM scratch and runs ONE dot over the whole contraction. No revisited
    output tile, no k-step read-modify-write: the kernel is a single pass
    over the weight planes, which is exactly the decode regime's byte
    budget (weights dominate; the T<=16 activation rides along in VMEM).

    The same body is the prefill CHUNK's kernel in fast mode (17..320 rows,
    PR 35): there the stripe's dequant is what XLA otherwise does in passes
    of its own through HBM (slice the codes, convert, spread the scales,
    multiply, write a bf16 plane, read it back: 48% of a cell's device
    time), and the one dot is an MXU pass at 130-145 TFLOP/s on a v5e
    against 153-174 for a dense bf16 plane of the same shape
    (tools/gemv_sweep.py, PERF.md section 6).

    The dequant is VPU work, one Q40 block of 32 rows at a time: an int8
    tile is 32 sublanes, so a block's codes are whole tiles and its scale
    row broadcasts across them. (Until PR 28 the scales were expanded by a
    0/1 matmul at HIGHEST precision, which costs the MXU seven times what
    the dot itself costs at <= 16 rows: 125 GB/s on a v5e where this reads
    590-710, tools/gemv_sweep.py and PERF.md.) The values are the old
    ones bit for bit: a code is an integer in [-8, 7] (Q80: [-127, 127])
    and the scale is first rounded to the dequant dtype, so their f32
    product is exact and rounds once, to what a multiply at that dtype
    gives.

    The single full-K dot is also what makes the kernel bit-parity with the
    XLA fused-dequant reference (ops.linear's dequant+dot fallback) instead
    of merely close: the blocked k-accumulation of :func:`_kernel` sums
    partials in a different order. Exact mode dequantizes at the activation
    dtype (the reference's rule) with a HIGHEST dot — BITWISE vs the
    reference on f32 activation graphs (the golden-parity configuration);
    a bf16 graph is drift-bounded instead, because XLA's in-jaxpr fusion
    may elide the bf16 dequant rounding on either side. Fast mode: bf16
    dequant, one default-precision MXU pass, f32 accumulation —
    drift-bounded for the same reason.
    """
    wd_dt = wd_ref.dtype  # bf16 in fast mode, the activation dtype in exact
    # scales widen here, not in HBM (a fast-mode load stores them bf16, and
    # the stack entry cannot afford a cast of all L layers per call); f32
    # rows are what a dynamic sublane index can address
    s32_ref[...] = scales_ref[...].astype(wd_dt).astype(jnp.float32)
    dequant_blocks(codes_ref, s32_ref, wd_ref, groups)
    out_ref[...] = jax.lax.dot_general(
        x_ref[...], wd_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=None if fast else _HIGHEST)


def _decode_kernel_at(layer_ref, *refs, groups: int, fast: bool):
    """:func:`_decode_kernel` behind a scalar-prefetch operand: the layer
    index is spent in the BlockSpec index maps (the stripe of layer ``l``
    is DMA'd straight out of the ``[L, K, N]`` stack), the body is the
    same."""
    del layer_ref
    _decode_kernel(*refs, groups=groups, fast=fast)


# Widest dispatch that counts as the decode regime for the fused kernel:
# single steps (T=1), fused-chunk scan bodies, speculative verifies
# (T=K+1, small) — the same rule as models.llama._OVERLAP_MAX_WIDTH.
FUSED_MAX_M = 16
# Widest dispatch the same kernel takes in its CHUNK regime (fast mode
# only): the widest prefill bucket (runtime.engine.PREFILL_BUCKETS, 256)
# with a tick's decode rows joined to it (models.llama.forward_and_step:
# the chunk's rows and up to 64 slots' through ONE matmul, so a stripe is
# fetched and dequantized once for both). At K = 14336 the resident set of
# 320 rows is 52 MB of the budget's 64 at bn = 512, as _decode_blocks
# reckons it. Past it the dequant amortizes over enough rows for XLA's
# dequant + dot.
CHUNK_MAX_M = 256 + 64

# VMEM the kernel asks Mosaic for (the default scoped limit is 16 MB of a
# v5e's 128), and what its resident set may take of that: the wd scratch,
# the double-buffered code and scale stripes, the widened scales and the
# full-K activation block, with room left for Mosaic's own. A chunk's
# activation block alone is 7 MB at K = 14336, so its regime asks for more.
_FUSED_VMEM_LIMIT = 32 * 1024 * 1024
_FUSED_VMEM_BUDGET = 20 * 1024 * 1024
_CHUNK_VMEM_LIMIT = 96 * 1024 * 1024
_CHUNK_VMEM_BUDGET = 64 * 1024 * 1024


def _decode_blocks(M: int, K: int, N: int,
                   fast: bool) -> tuple[int, int] | None:
    """``(bn, groups)`` for the decode kernel, or None when the shape
    doesn't fit: bn is the largest 128-multiple (or whole-N, >=8-aligned)
    dividing N whose resident set fits the VMEM budget (on the chip 256 and
    512 read alike and 1024 reads worse: PERF.md, PR 28); groups the Q40
    blocks dequantized per loop trip. Rows 1..``FUSED_MAX_M`` are the
    decode regime; ``FUSED_MAX_M`` + 1..``CHUNK_MAX_M`` the chunk regime,
    fast mode's alone (exact mode's f32 ``wd`` would double the stripe, and
    its wide dispatches keep the tiled kernel the goldens were taken with)."""
    if M <= 0 or K % Q40_BLOCK_SIZE:
        return None
    if M <= FUSED_MAX_M:
        budget, chunk = _FUSED_VMEM_BUDGET, False
    elif fast and M <= CHUNK_MAX_M:
        budget, chunk = _CHUNK_VMEM_BUDGET, True
    else:
        return None
    kb = K // Q40_BLOCK_SIZE
    groups = next(c for c in (8, 4, 2, 1) if kb % c == 0)
    wd_bytes = 2 if fast else 4
    x_bytes = M * K * (2 if fast else 4)
    for bn in BN_CANDIDATES + ((N,) if N % 8 == 0 else ()):
        if N % bn:
            continue
        # wd + 2x codes, and per scale row: 2x stored (<= f32) + widened
        resident = K * bn * (wd_bytes + 2) + kb * bn * 12 + x_bytes
        if chunk:
            # at chunk width the activation's second buffer and the
            # double-buffered f32 output block are no longer small change
            resident += x_bytes + 2 * M * bn * 4
        if resident <= budget:
            return bn, groups
    return None


# dlint: static-fn (shape gate; w may carry ShapeDtypeStruct leaves)
def fused_path(x_shape: tuple[int, ...], w: QuantizedWeight,
               fast: bool = False) -> str | None:
    """Which regime of the full-K fused kernel covers these shapes, by the
    name :func:`~dllama_tpu.runtime.introspection.note_q40_path` files it
    under: ``"fused"`` (1..``FUSED_MAX_M`` flattened rows), ``"chunk"``
    (up to ``CHUNK_MAX_M``, fast mode) or None."""
    K = x_shape[-1]
    M = 1
    for d in x_shape[:-1]:
        M *= d
    if (w.codes.ndim != 2 or w.codes.shape[0] != K
            or _decode_blocks(M, K, w.codes.shape[1], fast) is None):
        return None
    return "fused" if M <= FUSED_MAX_M else "chunk"


# dlint: static-fn (shape gate; w may carry ShapeDtypeStruct leaves)
def supports_decode(x_shape: tuple[int, ...], w: QuantizedWeight,
                    fast: bool = False) -> bool:
    """Whether the fused kernel's DECODE regime covers these shapes."""
    return fused_path(x_shape, w, fast) == "fused"


def _decode_call(xf: jax.Array, w: QuantizedWeight, *, interpret: bool,
                 fast: bool, layer: jax.Array | None = None) -> jax.Array:
    """Dispatch the decode kernel over ``xf [M, K]`` (already cast).

    Exact mode dequantizes at the ACTIVATION dtype — the same rule as the
    XLA reference (``dequantize_weight(w, dtype=x.dtype)``), so an
    exact-mode bf16 graph gets bf16 dequant on both paths instead of the
    kernel silently upgrading to f32 and breaking xla↔fused identity.

    With ``layer`` (an int32 scalar, traced) ``w`` is the LAYER STACK —
    codes ``[L, K, N]``, scales ``[L, K/32, N]`` — and the index rides in
    as a scalar-prefetch operand: the index maps pick ``(layer, 0, n)``, so
    the stripes stream out of the stack where it lies. Handing the kernel
    ``stack[layer]`` instead makes XLA materialize a copy of both planes
    in front of every custom call (a scan's ``xs`` slice is the same
    copy), which costs what the fusion saved (PERF.md, PR 26 / PR 28)."""
    M, K = xf.shape
    N = w.out_features
    bn, groups = _decode_blocks(M, K, N, fast)
    wd_dtype = jnp.bfloat16 if fast else xf.dtype
    kb = K // Q40_BLOCK_SIZE
    vmem = pltpu.VMEM
    if layer is None:
        kernel, prefetch, lead = _decode_kernel, (), ()
        plane = lambda n, *_: (0, n)
    else:
        kernel = _decode_kernel_at
        prefetch, lead = (jnp.reshape(layer, (1,)).astype(jnp.int32),), (None,)
        plane = lambda n, l: (l[0], 0, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((M, K), lambda n, *_: (0, 0), memory_space=vmem),
            pl.BlockSpec(lead + (K, bn), plane, memory_space=vmem),
            pl.BlockSpec(lead + (kb, bn), plane, memory_space=vmem),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda n, *_: (0, n),
                               memory_space=vmem),
        scratch_shapes=[pltpu.VMEM((K, bn), wd_dtype),
                        pltpu.VMEM((kb, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(kernel, groups=groups, fast=fast),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(_FUSED_VMEM_LIMIT if M <= FUSED_MAX_M
                              else _CHUNK_VMEM_LIMIT)),
        interpret=interpret,
    )(*prefetch, xf, w.codes, w.scales)


def _pick_block(dim: int, candidates: tuple[int, ...], min_align: int) -> int | None:
    """A 128-aligned block dividing ``dim``, or the whole dim (Mosaic allows a
    block equal to the array extent) when it at least meets ``min_align`` and
    is no larger than the largest candidate: a whole-dim block past that
    outgrows VMEM (the chip's compiler refuses the tp=4 logits shard,
    N = 128256/4 = 32064 = 64 x 501, with 134 MB of spills) — such a shape
    gets None and its caller the XLA dequant+dot path."""
    for c in candidates:
        if dim % c == 0:
            return c
    if dim % min_align == 0 and dim <= max(candidates):
        return dim
    return None


@functools.lru_cache(maxsize=8)
def _expansion_matrix(bk: int) -> np.ndarray:
    """0/1 matrix ``E[bk, bk/32]`` with ``E[32i:32(i+1), i] = 1``.

    Returns numpy (not jnp): this is called during traces, where caching a
    jnp constant would leak a tracer."""
    return np.kron(np.eye(bk // Q40_BLOCK_SIZE, dtype=np.float32),
                   np.ones((Q40_BLOCK_SIZE, 1), np.float32))


@functools.partial(jax.jit,
                   static_argnames=("interpret", "fast", "bn", "bk", "fused"))
def quant_matmul(x: jax.Array, w: QuantizedWeight, *, interpret: bool = False,
                 fast: bool = False, bn: int | None = None,
                 bk: int | None = None, fused: bool = False,
                 layer: jax.Array | None = None) -> jax.Array:
    """``y[..., N] = x[..., K] @ dequant(w)`` via the Pallas kernel.

    ``fast=False``: ``x`` is cast to f32 for the dequantized dot (parity with
    the XLA exact path). ``fast=True``: bf16 operands, one MXU pass, f32
    accumulation (see _kernel). Leading dims flatten into M.  ``bn``/``bk``
    override the tile picks (tools/gemv_sweep.py measures the candidates).
    ``fused=True`` prefers the full-K kernel (:func:`_decode_kernel` —
    bit-parity with the XLA fused-dequant reference) in either of its
    regimes (:func:`fused_path`: up to 16 rows, or a fast-mode chunk of up
    to ``CHUNK_MAX_M``), falling back to the (n, k)-tiled kernel otherwise,
    so a ``fused``-mode dispatch never fails on a shape past both. ``layer`` (an
    int32 scalar) says that ``w`` is the layer STACK, leading axis = layer,
    and picks one: that kernel's stack-and-index entry
    (:func:`_decode_call`), for callers that checked :func:`fused_path` on
    one layer's shapes — there is no tiled twin, so anything else raises.
    """
    *lead, K = x.shape
    N = w.out_features
    M = 1
    for d in lead:
        M *= d

    full_k = (fused and bn is None and bk is None
              and _decode_blocks(M, K, N, fast) is not None)
    if layer is not None and not full_k:
        raise ValueError(f"the layer-stack entry is the fused kernel's: "
                         f"x {x.shape}, stack {w.codes.shape} do not fit it")
    if full_k:
        # fast casts to bf16; exact keeps the activation dtype (the XLA
        # reference dequantizes at x.dtype — see _decode_call)
        xf = x.reshape(M, K)
        if fast:
            xf = xf.astype(jnp.bfloat16)
        out = _decode_call(xf, w, interpret=interpret, fast=fast, layer=layer)
        return out.reshape(*lead, N).astype(x.dtype)

    bn = bn or _pick_block(N, BN_CANDIDATES, min_align=8)
    bk = bk or _pick_block(K, BK_CANDIDATES, min_align=Q40_BLOCK_SIZE)
    if bn is None or bk is None:
        raise ValueError(f"shapes N={N}, K={K} do not fit the tile grid")
    if N % bn or K % bk or bk % Q40_BLOCK_SIZE:
        # overrides included: a non-dividing block would truncate the grid
        # and return uninitialized output columns
        raise ValueError(f"blocks bn={bn}, bk={bk} do not tile N={N}, K={K}")

    xf = x.reshape(M, K).astype(jnp.bfloat16 if fast else jnp.float32)
    grid = (N // bn, K // bk)

    out = pl.pallas_call(
        functools.partial(_kernel, fast=fast),
        grid=grid,
        in_specs=[
            pl.BlockSpec((M, bk), lambda n, k: (0, k), memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda n, k: (k, n), memory_space=pltpu.VMEM),
            pl.BlockSpec((bk // Q40_BLOCK_SIZE, bn), lambda n, k: (k, n),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bk // Q40_BLOCK_SIZE), lambda n, k: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda n, k: (0, n), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(xf, w.codes, w.scales.astype(jnp.float32), _expansion_matrix(bk))

    return out.reshape(*lead, N).astype(x.dtype)


def quant_matmul_sharded(plan, x: jax.Array, w: QuantizedWeight,
                         out_axis: str | None = None,
                         in_axis: str | None = None, *,
                         interpret: bool = False,
                         fast: bool = False,
                         fused: bool = False) -> jax.Array | None:
    """Tensor-parallel Pallas quant matmul: the kernel inside a shard_map.

    The auto-sharder cannot partition a ``pallas_call``, so under a mesh plan
    the kernel runs manual-SPMD (same pattern as
    ops.flash_attention.flash_attention_sharded). Two layouts, mirroring the
    reference's weight slicers:

    * **row-split** (``out_axis``; reference sliceRowMatmul,
      nn-core.cpp:207-217): the K-major planes shard their N axis; each device
      computes its slice of the output features, zero collectives.
    * **col-split** (``in_axis``; reference sliceColMatmul,
      nn-core.cpp:219-230): planes shard K, activations shard their feature
      axis, and a ``psum`` reduces the partial sums — the reference's
      SYNC_NODE_SLICES + OP_MERGE_ADD pair in one collective.

    When the named axis doesn't resolve on this mesh (or the dim isn't
    divisible — e.g. wk/wv under KV replication), the weight is replicated and
    every device runs the full kernel, matching what param_shardings did at
    load time. Returns ``None`` only when the *local* shapes don't fit the
    kernel's tile grid (caller falls back to the XLA dequant+dot path).
    """
    from jax.sharding import PartitionSpec as P

    assert x.ndim == 3 and w.codes.ndim == 2, (x.shape, w.codes.shape)
    assert (out_axis is None) or (in_axis is None)
    B, T, K = x.shape
    N = w.out_features

    def _axis_n(sz: int, logical: str | None):
        """Mesh axis for a logical name, or None when it can't divide ``sz``
        — MeshPlan.sharding_for's degradation rule, so the specs here always
        match the layout param_shardings chose at load time."""
        if logical is None:
            return None
        m = plan.resolve(logical)
        if m is None or sz % plan._axis_size(m) != 0:
            return None
        return m

    dp_ax = _axis_n(B, "batch")
    n_ax = _axis_n(N, out_axis)
    k_ax = _axis_n(K, in_axis) if n_ax is None else None

    def _sz(ax) -> int:
        return 1 if ax is None else plan._axis_size(ax)

    n_loc, k_loc = N // _sz(n_ax), K // _sz(k_ax)
    b_loc = B // _sz(dp_ax)
    local_w = QuantizedWeight(
        scales=jax.ShapeDtypeStruct((k_loc // Q40_BLOCK_SIZE, n_loc), jnp.float32),
        codes=jax.ShapeDtypeStruct((k_loc, n_loc), jnp.int8))
    if not (supports((b_loc, T, k_loc), local_w)
            or (fused and fused_path((b_loc, T, k_loc), local_w, fast))):
        return None

    if k_ax is not None:
        from ..parallel.qcollectives import wire_psum

        def local(xl, sc, cd):
            # f32 partials so the cross-device reduction doesn't round in bf16
            # (fast mode keeps bf16 multiplies but its accumulator/output is
            # already f32, so the psum is f32 either way). wire_psum ships
            # Q80-quantized partials when --wire q80 is on (the reference's
            # quantized sync pipes; parallel/qcollectives.py).
            part = quant_matmul(xl.astype(jnp.float32),
                                QuantizedWeight(scales=sc, codes=cd),
                                interpret=interpret, fast=fast, fused=fused)
            return wire_psum(part, k_ax, plan._axis_size(k_ax))

        fn = shard_map(
            local, mesh=plan.mesh,
            in_specs=(P(dp_ax, None, k_ax), P(k_ax, None), P(k_ax, None)),
            out_specs=P(dp_ax, None, None), check_vma=False)
    else:
        def local(xl, sc, cd):
            return quant_matmul(xl, QuantizedWeight(scales=sc, codes=cd),
                                interpret=interpret, fast=fast, fused=fused)

        fn = shard_map(
            local, mesh=plan.mesh,
            in_specs=(P(dp_ax, None, None), P(None, n_ax), P(None, n_ax)),
            out_specs=P(dp_ax, None, n_ax), check_vma=False)
    return fn(x, w.scales, w.codes)


# dlint: static-fn (env/platform/shape gate; w may carry ShapeDtypeStruct leaves)
def pallas_mode_gate(fast: bool, x_shape: tuple[int, ...] | None = None,
                     w: QuantizedWeight | None = None) -> dict | None:
    """The ONE mode/numerics gate for every Pallas kernel dispatch.
    ``DLLAMA_TPU_QUANT_KERNEL`` = ``xla`` (the XLA dequant + dot reference,
    also the kill switch for every kernel this gate guards), ``pallas``
    (force the tiled kernel; interpret mode off-TPU, the test path),
    ``fused`` (force the full-K fused kernel where it fits — up to 16
    rows, or a fast-mode chunk of up to ``CHUNK_MAX_M`` — and the tiled
    kernel where it does not), or ``auto``, resolved from what
    the dispatch shows:

    * off a TPU: no kernel.
    * exact mode (f32 graphs, the goldens): the tiled kernel, whose
      HIGHEST-precision dots match the host oracle.
    * fast mode (bf16 graphs, serving): the fused full-K kernel where
      :func:`fused_path` finds a regime for the dispatch — ``x_shape``
      flattens to 1..``FUSED_MAX_M`` rows (a decode step: the dequant-GEMV)
      or to ``CHUNK_MAX_M`` at most (a prefill chunk, alone or with a
      tick's decode rows joined to it: the same body at chunk width, so
      the dequantized plane stays in VMEM and never crosses HBM), ``w``
      is ONE 2-D Q40 plane pair whose stripe fits VMEM
      and no mesh plan is active — and NO kernel for anything else (wider,
      a plan, stacked expert planes, a width off the lane grid): those keep
      the XLA dequant + dot and never land on the tiled kernel (130 GB/s
      against XLA's 450-750, tools/gemv_sweep.py). There is no lower row
      bound: on the chip the kernel beats XLA's dequant-then-dot at every
      M from 1 to 16 (PERF.md section 6, PR 28) and at 32 to 256 (PR 35).
      Callers that pass no shape (the sharded entry, the overlapped merge,
      wire pricing: all under a plan) resolve as before: no kernel in fast
      mode.

    Returns the :func:`quant_matmul` kwargs (``interpret``, optionally
    ``fused``) or None. Consulted by ops.linear's single-device and sharded
    dispatch, the overlapped merge's :func:`pallas_local_choice`, the
    ragged paged attention entry (ops.paged_attention.kernel_choice), and
    the engine's wire pricing — one rule, so none of them can drift from
    what linear() dispatches (dlint rule ``pallas-gate`` machine-checks the
    routing)."""
    from .linear import _kernel_mode  # lazy: linear imports us

    mode = _kernel_mode()
    if mode == "xla":
        return None
    if mode == "fused":
        return {"interpret": not on_tpu(), "fused": True}
    if mode == "pallas":
        return {"interpret": not on_tpu()}
    if not on_tpu():
        return None
    if not fast:
        return {"interpret": False}
    if (x_shape is not None and w is not None and current_plan() is None
            and fused_path(tuple(x_shape), w, True)):
        return {"interpret": False, "fused": True}
    return None


def wants_fused(kw: dict | None) -> bool:  # dlint: static-fn
    """Whether a :func:`pallas_mode_gate` result selects the full-K fused
    kernel (trace-time env config, never a traced value)."""
    return kw is not None and kw.get("fused", False) is True


# dlint: static-fn (shape gate; w may carry ShapeDtypeStruct leaves)
def pallas_local_choice(x_shape: tuple[int, ...], w: QuantizedWeight,
                        fast: bool) -> dict | None:
    """:func:`pallas_mode_gate` + the shard-shape ``supports`` check —
    the per-shard kernel rule for the overlapped col-split merge
    (models.llama._overlapped_col_linear) and host-side pricing probes.
    ``w`` may carry ShapeDtypeStruct leaves."""
    kw = pallas_mode_gate(fast)
    if kw is None:
        return None
    if not (supports(tuple(x_shape), w)
            or (wants_fused(kw) and fused_path(tuple(x_shape), w, fast))):
        return None
    return kw


# Largest M the un-tiled batch axis may take: x block + out block + dequant
# scratch must fit VMEM (~16MB) alongside double-buffered weight tiles.
MAX_M = 512


def supports(x_shape: tuple[int, ...], w: QuantizedWeight) -> bool:  # dlint: static-fn
    """Whether the kernel's tile grid covers these shapes."""
    K = x_shape[-1]
    M = 1
    for d in x_shape[:-1]:
        M *= d
    return (w.codes.ndim == 2
            and w.in_features == K
            and M <= MAX_M
            and _pick_block(w.out_features, BN_CANDIDATES, min_align=8) is not None
            and _pick_block(K, BK_CANDIDATES, min_align=Q40_BLOCK_SIZE) is not None)
