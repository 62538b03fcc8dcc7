"""Turbo quant mode: the reference's integer-dot philosophy on the MXU.

The reference computes Q80 activations x Q40 weights with int8 multiplies
and per-block f32 scale epilogues (matmul_Q80_Q40_F32,
src/nn/nn-cpu-ops.cpp:229-447).  The round-4 on-chip profile showed this
repo's fast path (XLA-fused bf16 dequant) running VPU-limited: the
convert+scale work per code caps effective weight streaming at ~450-750
GB/s of the chip's 819.  Turbo mode removes the per-element dequant from
the hot loop the same way the reference does — integer dots, scales
applied at the output:

* at load, each Q40 plane requantizes to **per-column int8**
  (``w8[k, n] = round(dense[k, n] / scale[n])``, ``scale[n] =
  colmax/127``): same 1 B/weight HBM footprint, no per-element scale work
  left in the matmul;
* ``a8`` activations quantize per row to int8 (the Q80 idea at row
  granularity) and the dot runs s8 x s8 -> s32 on the MXU, with one
  ``sx * scale[n]`` f32 multiply per OUTPUT element;
* ``a16`` keeps bf16 activations (no activation quantization error): the
  dot still skips the scale multiply per element (one s8->bf16 convert
  remains), halving the VPU work of the fast path.

Numerics: per-column 8-bit requantization of 4-bit block codes adds
bounded drift (abs error <= colmax/254 per weight; tests bound the output
RMS drift) — turbo is OPT-IN via ``DLLAMA_TPU_QUANT_MODE=turbo`` (a8) /
``turbo16`` (a16) and never the default. Exact/fast modes are unaffected.
The a8/a16 choice is captured IN the weight at derivation time (pytree aux
data), so later env changes cannot silently flip serving numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .linear import QuantizedWeight


@jax.tree_util.register_pytree_node_class
class TurboWeight:
    """Per-column-requantized int8 weight, K-major like QuantizedWeight.

    ``w8``: int8 ``[..., in, out]``; ``scale``: f32 ``[..., out]`` with
    ``dense[k, n] ~= w8[k, n] * scale[n]``; ``a8`` (static aux data):
    whether the matmul quantizes activations to int8 for an s8 x s8 MXU
    dot, fixed when the weight was derived."""

    def __init__(self, w8, scale, a8: bool):
        self.w8 = w8
        self.scale = scale
        self.a8 = bool(a8)

    def tree_flatten(self):
        return (self.w8, self.scale), self.a8

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    @property
    def out_features(self) -> int:
        return self.w8.shape[-1]

    @property
    def in_features(self) -> int:
        return self.w8.shape[-2]

    def __repr__(self) -> str:  # debugging / test failure messages
        return (f"TurboWeight(w8={getattr(self.w8, 'shape', self.w8)}, "
                f"scale={getattr(self.scale, 'shape', self.scale)}, "
                f"a8={self.a8})")


def _derive_one(qw: QuantizedWeight):
    """One [K, N] plane -> per-column int8 (jittable; bf16/f32 scales ok)."""
    from .linear import dequantize_weight

    dense = dequantize_weight(qw, dtype=jnp.float32)  # [K, N]
    amax = jnp.max(jnp.abs(dense), axis=-2)  # [N]
    scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    w8 = jnp.clip(jnp.round(dense / scale[None, :]), -127, 127).astype(jnp.int8)
    return w8, scale


def derive_turbo(qw: QuantizedWeight, a8: bool = True,
                 free_source: bool = False) -> TurboWeight:
    """Requantize a (possibly layer/expert-stacked) Q40 weight to TurboWeight.

    Stacked planes convert one (layer[, expert]) plane at a time
    (``lax.map`` over the flattened leading axes) so the dense f32
    intermediate is bounded by ONE plane, not the whole stack (an 8B stack
    would need ~30 GB dense).  ``free_source`` deletes the source plane
    buffers right after the derived arrays materialize, so a whole-tree
    conversion transiently holds at most one extra leaf, not a second copy
    of the model (runtime.hbm charges that bound)."""
    if qw.codes.ndim == 2:
        w8, scale = jax.jit(_derive_one)(qw)
    else:
        lead = qw.codes.shape[:-2]  # [L] or [L, E] (MoE expert stacks)

        def one(args):
            return _derive_one(QuantizedWeight(scales=args[0], codes=args[1]))

        def mapped(s, c):
            s = s.reshape((-1,) + s.shape[len(lead):])
            c = c.reshape((-1,) + c.shape[len(lead):])
            w8_f, scale_f = jax.lax.map(one, (s, c))
            return (w8_f.reshape(lead + w8_f.shape[1:]),
                    scale_f.reshape(lead + scale_f.shape[1:]))

        w8, scale = jax.jit(mapped)(qw.scales, qw.codes)
    if free_source:
        # fetch-forced sync: device_get of a value that data-depends on w8
        # cannot return until the derivation actually ran, whatever
        # block_until_ready does on the transport in use (the 2026-07-31
        # capture saw it return early; chip_smoke.py re-checks it per chip).
        # Otherwise tree_map could enqueue the next leaf's derivation while
        # this one's dense f32 intermediate is still in flight — breaking
        # the one-extra-leaf transient HBM bound runtime.hbm charges.
        jax.device_get(w8[(0,) * w8.ndim])
        qw.codes.delete()
        qw.scales.delete()
    else:
        jax.block_until_ready(w8)
    return TurboWeight(w8, scale, a8)


def turbo_params(params, a8: bool = True, free_source: bool = True):
    """Convert every QuantizedWeight leaf of a Params tree to TurboWeight.

    Leaves convert one at a time with their source buffers freed as soon as
    each derived leaf lands (see derive_turbo) — the caller must treat the
    INPUT tree as consumed."""
    return jax.tree_util.tree_map(
        lambda leaf: (derive_turbo(leaf, a8=a8, free_source=free_source)
                      if isinstance(leaf, QuantizedWeight) else leaf),
        params, is_leaf=lambda x: isinstance(x, QuantizedWeight))


def quantize_activations_a8(x: jax.Array):
    """Per-row int8 activation quantization (the Q80 idea at row
    granularity): returns ``(xq int8, sx f32[..., 1])`` with
    ``x ~= xq * sx``. The ONE implementation of the a8 prologue — both the
    dense turbo matmul and the MoE gather-regime dot share it."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    sx = jnp.where(amax > 0.0, amax / 127.0, 1.0)
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    return xq, sx


def turbo_matmul(x: jax.Array, w: TurboWeight) -> jax.Array:
    """``y[..., N] = x[..., K] @ (w8 * scale)`` without per-element dequant.

    The a8/a16 choice rides ON the weight (aux data — a static under jit):
    a8 = row-quantized int8 activations + s8 x s8 -> s32 MXU dot (the
    reference's integer-dot shape); a16 = bf16 x s8->bf16 with the scale in
    the f32 epilogue."""
    out_dtype = x.dtype
    if w.a8:
        xq, sx = quantize_activations_a8(x)
        acc = jax.lax.dot_general(
            xq, w.w8,
            dimension_numbers=(((xq.ndim - 1,), (w.w8.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.int32)
        out = acc.astype(jnp.float32) * sx * w.scale
    else:
        wd = w.w8.astype(jnp.bfloat16)
        acc = jax.lax.dot_general(
            x.astype(jnp.bfloat16), wd,
            dimension_numbers=(((x.ndim - 1,), (wd.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = acc * w.scale
    return out.astype(out_dtype)
