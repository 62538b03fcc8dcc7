"""RoPE frequency caches and application.

Numerically matches the reference's precomputed-cache approach (reference:
fullfillRopeLlamaCache / fullfillRopeFalconCache, src/nn/nn-core.cpp:329-370;
apply kernels ropeLlama_F32 / ropeFalcon_F32, src/nn/nn-cpu-ops.cpp:836-878):

* **llama style** — adjacent interleaved pairs ``(x[2j], x[2j+1])`` within each
  head, frequency ``theta^(-2j/head_dim)``. Used by Llama 2/3 together with the
  converter's Q/K head permutation (convert-hf.py:12-15).
* **llama3.1** — llama pairing with Meta's wavelength-banded frequency scaling
  (scaleFrequencyLlama3, nn-core.cpp:313-327).
* **falcon (neox) style** — half-split pairs ``(x[j], x[j + head_dim/2])``,
  same frequencies. Used by Qwen3.

Unlike the reference, the cache here is global per model (``[seq_len,
head_dim/2]``), not per-TP-shard: the TP shard always holds whole heads, and
every head uses identical frequencies, so slicing the cache per node
(sliceRope, nn-core.cpp:232-263) is unnecessary under SPMD sharding.
"""

from __future__ import annotations

import math

import numpy as np
import jax.numpy as jnp

from ..formats.mfile import RopeType
from .config import ModelConfig


def _scale_frequency_llama3(freq: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    """Meta's llama3.1 rope scaling (reference: nn-core.cpp:313-327)."""
    wave_len = 2.0 * np.pi / freq
    high_freq_wavelen = cfg.rope_scaling_orig_max_seq_len / cfg.rope_scaling_high_freq_factor
    low_freq_wavelen = cfg.rope_scaling_orig_max_seq_len / cfg.rope_scaling_low_freq_factor
    smooth = (cfg.rope_scaling_orig_max_seq_len / wave_len - cfg.rope_scaling_low_freq_factor) / (
        cfg.rope_scaling_high_freq_factor - cfg.rope_scaling_low_freq_factor)
    smoothed = (1.0 - smooth) * freq / cfg.rope_scaling_factor + smooth * freq
    out = np.where(wave_len < high_freq_wavelen, freq,
                   np.where(wave_len > low_freq_wavelen,
                            freq / cfg.rope_scaling_factor, smoothed))
    return out


import functools


@functools.lru_cache(maxsize=16)
def build_rope_cache(cfg: ModelConfig) -> tuple[np.ndarray, np.ndarray]:
    """cos/sin caches of shape ``[seq_len, head_dim // 2]`` in float32.

    Memoized per config (frozen dataclass): the host-side trig tables are
    computed once per model, not per trace. Returns plain numpy arrays —
    callers may be inside a jit trace, where caching a ``jnp`` constant would
    leak a tracer; numpy constants embed safely."""
    half = cfg.head_dim // 2
    j = np.arange(half, dtype=np.float32)
    # llama: pair index j covers dims (2j, 2j+1), h = 2j in the reference loop.
    # falcon: freq exponent is 2j/head_dim as well (nn-core.cpp:354) — the two
    # styles share frequencies and differ only in pairing layout.
    freqs = 1.0 / np.power(cfg.rope_theta, 2.0 * j / cfg.head_dim, dtype=np.float32)
    if cfg.rope_type == RopeType.LLAMA3_1 and cfg.rope_scaling_factor != 1.0:
        freqs = _scale_frequency_llama3(freqs.astype(np.float64), cfg).astype(np.float32)
    pos = np.arange(cfg.seq_len, dtype=np.float32)[:, None]
    angles = pos * freqs[None, :]
    return np.cos(angles), np.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray, rope_type: RopeType) -> jnp.ndarray:
    """Rotate ``x: [B, T, n_heads, head_dim]`` at ``positions: [B, T]``."""
    dtype = x.dtype
    c = jnp.asarray(cos)[positions]  # [B, T, half] float32
    s = jnp.asarray(sin)[positions]
    c = c[:, :, None, :]  # broadcast over heads
    s = s[:, :, None, :]
    xf = x.astype(jnp.float32)  # rotate in f32, cast back (parity + no promotion)
    if rope_type in (RopeType.LLAMA, RopeType.LLAMA3_1):
        x0 = xf[..., 0::2]
        x1 = xf[..., 1::2]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        # re-interleave: stack on a new trailing axis then flatten
        return jnp.stack([r0, r1], axis=-1).reshape(x.shape).astype(dtype)
    elif rope_type == RopeType.FALCON:
        half = x.shape[-1] // 2
        x0 = xf[..., :half]
        x1 = xf[..., half:]
        r0 = x0 * c - x1 * s
        r1 = x0 * s + x1 * c
        return jnp.concatenate([r0, r1], axis=-1).astype(dtype)
    raise ValueError(f"unsupported rope type {rope_type}")


def yarn_inv_freq(theta: float, rot_dim: int, factor: float,
                  orig_max: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's banded frequencies over ``rot_dim`` rotating lanes (Peng et
    al., arXiv:2309.00071, as the published configs parametrise it): pair
    ``i`` of ``rot_dim / 2`` keeps ``e_i = theta^(-2i/rot_dim)`` where it
    turns more than ``beta_fast`` times inside the original context, takes
    ``e_i / factor`` where it turns fewer than ``beta_slow`` times, and a
    linear ramp between::

        dim(n)  = rot_dim ln(orig_max / (2 pi n)) / (2 ln theta)
        low     = max(floor(dim(beta_fast)), 0)
        high    = min(ceil(dim(beta_slow)), rot_dim - 1)
        ramp_i  = clip((i - low) / (high - low), 0, 1)
        inv_i   = (e_i / factor) ramp_i + e_i (1 - ramp_i)

    float64 throughout; the caller rounds once."""
    half = rot_dim // 2
    i = np.arange(half, dtype=np.float64)
    e = np.power(theta, -2.0 * i / rot_dim)

    def dim(n: float) -> float:
        return (rot_dim * math.log(orig_max / (2.0 * math.pi * n))
                / (2.0 * math.log(theta)))

    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), rot_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (e / factor) * ramp + e * (1.0 - ramp)


def yarn_attention_factor(factor: float) -> float:
    """What YaRN multiplies cos and sin by: ``0.1 ln(factor) + 1``."""
    return 0.1 * math.log(factor) + 1.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where nothing is stretched): the
    DeepSeek-style configs' parametrisation, whose tables take the ratio of
    two of these and whose scores take the square of the second
    (``ModelConfig.attn_scale``)."""
    return 0.1 * mscale * math.log(max(factor, 1.0)) + 1.0


@functools.lru_cache(maxsize=16)
def build_partial_rope_cache(seq_len: int, rot_dim: int, theta: float,
                             yarn: tuple | None = None,
                             table_scale: float | None = None
                             ) -> tuple[np.ndarray, np.ndarray]:
    """cos/sin ``[seq_len, rot_dim // 2]`` float32 for a rotary embedding
    over the first ``rot_dim`` lanes of a head (half-split pairing,
    :func:`apply_rope_partial`). ``yarn = (factor, orig_max, beta_fast,
    beta_slow)`` selects :func:`yarn_inv_freq` and scales both tables by
    :func:`yarn_attention_factor`, or by ``table_scale`` where one is given
    (latent attention's tables take the ratio of two mscales, 1 where they
    are equal: models/axk1.py); None is the plain ``theta^(-2i/rot_dim)``.
    numpy, memoized: see :func:`build_rope_cache`."""
    half = rot_dim // 2
    if yarn is None:
        inv = np.power(theta, -2.0 * np.arange(half, dtype=np.float64) / rot_dim)
        scale = 1.0
    else:
        factor, orig_max, beta_fast, beta_slow = yarn
        inv = yarn_inv_freq(theta, rot_dim, factor, orig_max, beta_fast,
                            beta_slow)
        scale = (yarn_attention_factor(factor) if table_scale is None
                 else table_scale)
    angles = (np.arange(seq_len, dtype=np.float64)[:, None]
              * inv[None, :]).astype(np.float32)
    return ((np.cos(angles) * scale).astype(np.float32),
            (np.sin(angles) * scale).astype(np.float32))


def apply_rope_partial(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                       positions: jnp.ndarray) -> jnp.ndarray:
    """Rotate the first ``2 * cos.shape[-1]`` lanes of every head of ``x
    [B, T, n_heads, head_dim]`` at ``positions [B, T]``, pairing lane ``j``
    with lane ``j + r/2`` (half-split); the lanes past ``r`` pass through."""
    half = cos.shape[-1]
    c = jnp.asarray(cos)[positions][:, :, None, :]
    s = jnp.asarray(sin)[positions][:, :, None, :]
    xf = x.astype(jnp.float32)
    x0, x1, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c, rest],
                           axis=-1).astype(x.dtype)
