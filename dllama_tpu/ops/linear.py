"""Linear/matmul ops over dense or Q40-quantized weights.

The quantized path replaces the reference's Q80×Q40 integer-dot kernels
(reference: matmul_Q80_Q40_F32, src/nn/nn-cpu-ops.cpp:229-447 and the
llamafile sgemm prefill path): weights stay in the Q40 block domain (separated
scale/code planes from :func:`dllama_tpu.formats.quants.unpack_q40`), and the
matmul dequantizes on the fly. On TPU the XLA path below lets the compiler
fuse dequantization into the MXU matmul; a hand-tiled Pallas kernel lives in
:mod:`dllama_tpu.ops.quant_matmul` for the cases XLA schedules poorly.

``fake_quant_q80`` mirrors the reference's activation-quantization ("sync
type" Q80 casts, llm.cpp:258-265): quantize-dequantize in-graph so the
numerical effect of the wire quantization is reproduced even though TPU
collectives move bf16/f32.
"""

from __future__ import annotations

import os
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.quants import Q40_BLOCK_SIZE, Q80_BLOCK_SIZE


class QuantizedWeight(NamedTuple):
    """Q40 weight as TPU-friendly planes, K-major.

    ``scales``: ``[in // 32, out]`` block scales (f16 on disk; never f16 on
    device — narrow f16 blocks don't lower on the TPU Mosaic toolchain).
    Exact configs store f32 (0.125 B/weight; the host-oracle bit goldens
    are tied to the f32 dequant); fast configs store bf16 (0.0625 B/weight
    — halves scale HBM traffic; runtime.weights picks at load via
    ops.linear.fast_numerics_resolved).
    ``codes``: int8 ``[in, out]`` centered 4-bit codes in [-8, 7].

    Logical value: ``w[o, i] = codes[i, o] * scales[i // 32, o]``
    (reference block layout: NnBlockQ40, src/nn/nn-quants.hpp:64-67; the
    on-disk layout is out-major and gets transposed once at load). K-major
    means ``y = x @ codes``-style dots feed the MXU with no transpose, and
    every Pallas block spec indexes both planes natively.
    """

    scales: jax.Array
    codes: jax.Array

    @property
    def out_features(self) -> int:
        return self.codes.shape[-1]

    @property
    def in_features(self) -> int:
        return self.codes.shape[-2]


class LayerSlice(NamedTuple):
    """Layer ``index`` of a Q40 weight whose leading axis is the LAYER
    stack (``stack.codes [L, in, out]``, ``stack.scales [L, in/32, out]``).

    The call site that scans the layers builds it (models/llama.py), and by
    building it says what the leading axis means — :func:`linear` never
    guesses that from ``codes.ndim == 3``, which is also how MoE experts
    are stacked. It lets the fused decode kernel read layer ``index``'s
    stripes out of the stack where it lies (quant_matmul's ``layer``
    entry); every other path takes the plain slice (:meth:`take`)."""

    stack: QuantizedWeight
    index: jax.Array  # int32 scalar, traced

    def take(self) -> QuantizedWeight:
        return QuantizedWeight(*(
            jax.lax.dynamic_index_in_dim(p, self.index, 0, keepdims=False)
            for p in self.stack))

    def one_layer(self) -> QuantizedWeight:
        """One layer's planes as shapes (what the shape gates look at)."""
        return QuantizedWeight(*(
            jax.ShapeDtypeStruct(p.shape[1:], p.dtype) for p in self.stack))


Weight = Union[jax.Array, QuantizedWeight, LayerSlice]


def quantize_weight_q40(w: np.ndarray) -> QuantizedWeight:
    """Quantize a dense ``[out, in]`` float32 weight to Q40 planes (host-side)."""
    from ..formats.quants import quantize_q40, unpack_q40

    out, in_ = w.shape
    buf = quantize_q40(np.ascontiguousarray(w, dtype=np.float32).reshape(-1))
    scales, codes = unpack_q40(buf, out * in_)
    return QuantizedWeight(
        scales=jnp.asarray(
            scales.reshape(out, in_ // Q40_BLOCK_SIZE).T.astype(np.float32)),
        codes=jnp.asarray(np.ascontiguousarray(codes.reshape(out, in_).T)),
    )


def dequantize_weight(w: QuantizedWeight, dtype=jnp.float32) -> jax.Array:
    """Expand Q40 planes to a dense K-major ``[..., in, out]`` array."""
    scales = jnp.repeat(w.scales.astype(dtype), Q40_BLOCK_SIZE, axis=-2)
    return w.codes.astype(dtype) * scales


def _kernel_mode() -> str:
    # read per call so tests/debug sessions can flip it after import
    # (auto|pallas|fused|xla — see quant_matmul.pallas_mode_gate, the ONE
    # place the value turns into a kernel choice)
    return os.environ.get("DLLAMA_TPU_QUANT_KERNEL", "auto")


def _fast_mode(x: jax.Array) -> bool:  # dlint: static-fn (dtype/env gate)
    """Exact vs fast quant-matmul numerics (SURVEY §7.4's exact/fast split).

    ``DLLAMA_TPU_QUANT_MODE``: ``exact`` = f32 dequant + HIGHEST-precision
    dots (parity with the host oracle and the committed goldens); ``fast`` =
    bf16 dequant, one default-precision MXU pass, f32 accumulation (serving
    mode — the TPU analogue of the reference's int8-dot-plus-scale-epilogue
    kernels, nn-cpu-ops.cpp:229-447). ``auto`` (default) keys off the
    activation dtype: a bf16 compute graph (`--compute-dtype bf16`) already
    accepted bf16 rounding at every op boundary, so it gets the fast kernel;
    f32 graphs keep exact.
    """
    return fast_numerics_resolved(
        "bfloat16" if x.dtype == jnp.bfloat16 else "float32")


QUANT_MODES = ("auto", "exact", "fast")


def quant_mode() -> str:
    """``DLLAMA_TPU_QUANT_MODE`` as set, refused when it is not one of
    QUANT_MODES. The ONE reader of the variable: a value this build does
    not know must not fall through to ``auto`` and serve an operator other
    numerics than the ones they exported."""
    mode = os.environ.get("DLLAMA_TPU_QUANT_MODE", "auto")
    if mode not in QUANT_MODES:
        raise ValueError(
            f"DLLAMA_TPU_QUANT_MODE={mode!r} is not a quant mode: "
            f"use one of {', '.join(QUANT_MODES)}")
    return mode


def fast_numerics_resolved(compute_dtype: str) -> bool:
    """The load-time fast/exact resolution (same rule as _fast_mode, keyed
    on the config's compute dtype instead of a live activation): decides
    stored scale dtype and the dense-logits default in runtime.weights."""
    mode = quant_mode()
    if mode == "auto":
        return compute_dtype == "bfloat16"
    return mode == "fast"


def _pallas_wanted(x: jax.Array, w: QuantizedWeight, fast: bool) -> dict | None:  # dlint: static-fn (shape/env gate)
    """quant_matmul kwargs when the plain (no-plan) Pallas path applies,
    else None. The mode rule is quant_matmul.pallas_mode_gate — the ONE
    gate, which is shown the dispatch's shapes; this adds only the forced
    modes' shape check and the plan-free requirement. ``w`` may carry
    ShapeDtypeStruct leaves.

    What ``auto`` comes to on a TPU: exact mode takes the tiled kernel
    (HIGHEST-precision dots that match the host oracle); fast mode takes
    the fused full-K kernel over a 2-D plane pair for 1..16 flattened rows
    (a decode step: the dequant-GEMV) and for 17..320 (a prefill chunk:
    the same dequant into VMEM in front of one MXU pass), and the XLA
    dequant + dot for everything else: wider, stacked expert planes, a
    width off the lane grid (the tiled kernel streams codes at ~130 GB/s
    where XLA reaches 450-750: tools/gemv_sweep.py). Under a mesh plan the
    sharded entry in linear() handles dispatch; this plain path must stay
    out of GSPMD-partitioned graphs (the auto-sharder can't split a
    pallas_call)."""
    from .quant_matmul import pallas_mode_gate, supports

    kw = pallas_mode_gate(fast, tuple(x.shape), w)
    if kw is None:
        return None
    if not (supports(tuple(x.shape), w) or _fused_path(kw, x, w, fast)):
        return None
    if _kernel_mode() in ("pallas", "fused"):
        return kw  # forced: replicated operands are fine under a plan
    from ..parallel.api import current_plan

    return kw if current_plan() is None else None


def _pallas_sharded(x: jax.Array, w: QuantizedWeight, out_axis: str | None,
                    in_axis: str | None, fast: bool):
    """Try the shard_map-wrapped kernel under the active plan; None → caller
    falls back to XLA dequant+dot (auto-sharded via constraints). The
    mode/numerics gate is quant_matmul.pallas_mode_gate — the ONE rule
    this, the overlapped merge, and the engine's wire pricing share
    (it is shown no shape here, so fast mode's ``auto`` resolves to no
    kernel under a plan, as it always did: the tp cell decides that)."""
    from .quant_matmul import pallas_mode_gate, quant_matmul_sharded

    kw = pallas_mode_gate(fast)
    if kw is None:
        return None
    if x.ndim != 3 or w.codes.ndim != 2:
        return None  # stacked (scan-external) or 2-D activations: XLA path
    from ..parallel.api import current_plan

    return quant_matmul_sharded(
        current_plan(), x, w, out_axis=out_axis, in_axis=in_axis,
        interpret=kw["interpret"], fast=fast,
        fused=kw.get("fused", False))


# dlint: static-fn (shape/env gate)
def _fused_path(kw: dict | None, x: jax.Array, w: QuantizedWeight,
                fast: bool) -> str | None:
    """The path quant_matmul, given these gate kwargs, runs the full-K
    fused kernel under on this dispatch (``fused`` at 1..16 rows, ``chunk``
    at 17..``CHUNK_MAX_M``), or None where it runs the tiled one."""
    from .quant_matmul import fused_path, wants_fused

    return fused_path(tuple(x.shape), w, fast) if wants_fused(kw) else None


def _layer_slice_fused(x: jax.Array, w: LayerSlice) -> jax.Array | None:
    """The fused kernel over the layer stack and an index, where the gate
    resolves it for one layer's shapes (no plan: the stack entry has no
    sharded twin); None sends the caller to the plain slice."""
    from ..parallel.api import current_plan
    from ..runtime.introspection import note_q40_path
    from .quant_matmul import quant_matmul

    if current_plan() is not None:
        return None
    fast = _fast_mode(x) or w.stack.scales.dtype == jnp.bfloat16
    one = w.one_layer()
    kw = _pallas_wanted(x, one, fast)
    path = _fused_path(kw, x, one, fast)
    if path is None:
        return None
    note_q40_path(path)
    return quant_matmul(x, w.stack, fast=fast, layer=w.index, **kw)


def linear(x: jax.Array, w: Weight, *, out_axis: str | None = None,
           in_axis: str | None = None) -> jax.Array:
    """``y[..., out] = x[..., in] @ w.T`` with dense or Q40 weight.

    Dense weights use the reference's on-disk ``[out, in]`` orientation
    (row-major, llm.cpp matmul weights); Q40 planes are K-major ``[in, out]``
    (see QuantizedWeight). ``out_axis``/``in_axis`` name the weight's logical
    TP shard axis (row-split = shard ``out``, col-split = shard ``in`` — the
    reference's sliceRowMatmul/sliceColMatmul split): under a mesh plan they
    route Q40 weights to the shard_map-wrapped Pallas kernel
    (quant_matmul_sharded); single-device Q40 dispatches the plain kernel.
    DLLAMA_TPU_QUANT_KERNEL=auto|pallas|fused|xla; the ONE resolution rule
    is quant_matmul.pallas_mode_gate. On a TPU ``auto`` means: exact (f32)
    graphs take the tiled kernel; fast (bf16) graphs take the fused full-K
    kernel over a 2-D plane pair with no plan, for a decode-shaped
    dispatch (1..16 flattened rows) and for a prefill chunk (17..320: the
    plane is dequantized in VMEM, not in passes through HBM), and the XLA
    dequant + dot for everything else. A :class:`LayerSlice` hands that
    kernel the layer stack and an index; unsupported shapes fall back to
    XLA dequant + dot with identical dequant values. Each Q40 dispatch
    notes the path it took (``fused`` / ``chunk`` / ``tiled`` / ``xla``)
    for the program being traced (runtime.introspection.note_q40_path).
    """
    out_dtype = x.dtype
    if isinstance(w, LayerSlice):
        y = _layer_slice_fused(x, w)
        if y is not None:
            return y
        w = w.take()
    if isinstance(w, QuantizedWeight):
        from ..parallel.api import current_plan
        from ..runtime.introspection import note_q40_path

        # the stored scale dtype wins over the ambient env: bf16 scales were
        # written by a fast-mode load, and an "exact" f32 dequant over them
        # would be fake exactness (ADVICE r4 drift finding)
        fast = _fast_mode(x) or w.scales.dtype == jnp.bfloat16
        if current_plan() is not None and (out_axis or in_axis):
            y = _pallas_sharded(x, w, out_axis, in_axis, fast)
            if y is not None:
                # what the gate asked for: a shard too wide for the decode
                # kernel runs tiled inside quant_matmul_sharded
                note_q40_path("fused" if _kernel_mode() == "fused"
                              else "tiled")
                return y.astype(x.dtype)
        else:
            kernel_kw = _pallas_wanted(x, w, fast)
            if kernel_kw is not None:
                from .quant_matmul import quant_matmul

                note_q40_path(_fused_path(kernel_kw, x, w, fast) or "tiled")
                return quant_matmul(x, w, fast=fast, **kernel_kw)
        note_q40_path("xla")
        # XLA fallback: in fast mode the dense dequant lands in bf16 (half the
        # HBM traffic of f32) and the dot takes one MXU pass; exact mode
        # dequantizes at the activation dtype as before
        wd = dequantize_weight(w, dtype=jnp.bfloat16 if fast else x.dtype)
        if fast and x.dtype != jnp.bfloat16:
            x = x.astype(jnp.bfloat16)
        contract = wd.ndim - 2  # K-major: contract the `in` axis
    else:
        wd = w.astype(x.dtype)
        contract = wd.ndim - 1
    return jax.lax.dot_general(
        x, wd,
        dimension_numbers=(((x.ndim - 1,), (contract,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)


def q80_quantize_planes(x: jax.Array):
    """In-graph Q80 block quantization of the trailing axis: int8 codes
    ``[..., n/32, 32]`` + f16 scales ``[..., n/32, 1]``. The ONE
    implementation of the reference's activation-quantization math — both
    :func:`fake_quant_q80` (numerics emulation at sync points) and the
    quantized-wire collective (parallel.qcollectives) build on it, so their
    bit-identity can't drift."""
    *lead, n = x.shape
    assert n % Q80_BLOCK_SIZE == 0, n
    g = x.astype(jnp.float32).reshape(*lead, n // Q80_BLOCK_SIZE,
                                      Q80_BLOCK_SIZE)
    amax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    d = amax / 127.0
    inv = jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1.0), 0.0)
    codes = jnp.round(g * inv).astype(jnp.int8)  # half-to-even, in [-127,127]
    return codes, d.astype(jnp.float16)


def q80_dequant(codes: jax.Array, scales: jax.Array, shape) -> jax.Array:
    """The ONE dequant convention pairing :func:`q80_quantize_planes` (f32
    multiply of int8 codes by the f16 scales) — used by fake_quant_q80 and
    the quantized-wire collectives alike, so their bit-identity can't
    drift."""
    return (codes.astype(jnp.float32)
            * scales.astype(jnp.float32)).reshape(shape)


def fake_quant_q80(x: jax.Array) -> jax.Array:
    """In-graph Q80 quantize→dequantize of the trailing axis.

    Numerically mirrors the reference *runtime* path quantizeF32toQ80 +
    dequantizeQ80toF32: the int8 code is ``round(x / d)`` with the UNROUNDED
    f32 scale ``d = absmax/127``, while the dequant multiply uses the
    f16-rounded stored scale. Used when the engine runs in "sync q80" parity
    mode so activations passing a sync point carry the same quantization the
    reference's wire format applies.

    Rounding mode: the reference is ISA-inconsistent — its AVX2 path rounds
    half-to-EVEN (_MM_FROUND_TO_NEAREST_INT, nn-quants.cpp:139) while the
    NEON (+0.5-then-truncate, :97-100) and scalar roundf (:169) paths round
    half-away-from-zero; the repo's own macbeth.sh:6 flags this CPU
    dependence. We round half-to-even: it matches the x86 build the committed
    goldens were generated with, and it's IEEE/TPU-native (XLA lowers
    jnp.round to round_nearest_even directly).
    """
    codes, d16 = q80_quantize_planes(x)
    return q80_dequant(codes, d16, x.shape).astype(x.dtype)
