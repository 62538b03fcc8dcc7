"""The Mamba-2 (SSD) mixer two decoder families share: beside attention in
every layer (``models/falcon_h1.py``) and as a layer of its own
(``models/nemotron_h.py``). For its input ``u`` (normed)::

    [z | xBC | dt] = (W_inproj u) * mup      # mup: ssm_z ssm_x ssm_b ssm_c ssm_dt
    xBC = silu(causal_conv(xBC) + conv_bias);  x_ B C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y  = SSD(x_, dt, A, B, C) + D x_
    W_outproj group_rmsnorm(y * silu(z); w_norm)     # the gate first, then
                                                     # an RMS norm a group

``ops/ssd.py`` has the recurrence in both forms. The multipliers are
``cfg.mult`` (:class:`~dllama_tpu.models.config.Multipliers`; all 1 where the
architecture has none). ``lp`` is one layer of any stack that names its
leaves ``w_in`` (the ``z x B C`` rows, one Q40 plane), ``w_dt`` (the ``dt``
rows, float32), ``conv_w conv_b a_log d_skip dt_bias norm_ssm`` and ``w_out``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.causal_conv import causal_conv
from ..ops.linear import linear
from ..runtime.introspection import note_ssd_path
from .config import ModelConfig


def mixer_inputs(cfg: ModelConfig, u: jax.Array, lp,
                  tail: jax.Array, n_valid):
    """Everything of the SSD mixer in front of the recurrence, for ``u [B,
    T, dim]`` and the convolution's ``tail [B, K - 1, C]``: float32 ``x [B,
    T, H, P]``, ``dt [B, T, H]`` (after its softplus), the groups' ``Bm, Cm
    [B, T, G, N]``, the gate ``z [B, T, d_ssm]`` and the new tail."""
    B, T, _ = u.shape
    m = cfg.mult
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_dim
    d_ssm, gn = cfg.ssm_inner_dim, G * N
    proj = linear(u, lp.w_in)
    dt = jnp.einsum("btd,hd->bth", u.astype(jnp.float32), lp.w_dt,
                    precision=jax.lax.Precision.HIGHEST) * m.ssm_dt
    z = proj[..., :d_ssm].astype(jnp.float32) * m.ssm_z
    # the x, B and C lanes of the in-projection, each under its multiplier
    lanes = jnp.concatenate([jnp.full((d_ssm,), m.ssm_x, jnp.float32),
                             jnp.full((gn,), m.ssm_b, jnp.float32),
                             jnp.full((gn,), m.ssm_c, jnp.float32)])
    # folded into the taps (the convolution is linear a channel), so the
    # tail keeps the projection's own values, exact in its dtype
    xbc, tail = causal_conv(proj[..., d_ssm:], tail, lp.conv_w * lanes,
                            n_valid, bias=lp.conv_b)
    x = xbc[..., :d_ssm].reshape(B, T, H, P)
    Bm = xbc[..., d_ssm:d_ssm + gn].reshape(B, T, G, N)
    Cm = xbc[..., d_ssm + gn:].reshape(B, T, G, N)
    return x, jax.nn.softplus(dt + lp.dt_bias), Bm, Cm, z, tail


def mixer_output(cfg: ModelConfig, y: jax.Array, x: jax.Array, z: jax.Array,
                  lp, dtype) -> jax.Array:
    """``W_out group_rmsnorm((y + D x) * silu(z))`` from float32 ``y, x [B,
    T, H, P]``: the gate first, then an RMS norm over each group's lanes."""
    B, T = y.shape[:2]
    y = (y + lp.d_skip[:, None] * x).reshape(B, T, -1) * jax.nn.silu(z)
    grouped = y.reshape(B, T, cfg.ssm_groups, -1)
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_epsilon)
    return linear((normed.reshape(B, T, -1) * lp.norm_ssm).astype(dtype),
                  lp.w_out)


def mixer_chunk(cfg, u, lp, s_l, conv_l, n_valid):
    """The mixer over a chunk: ``s_l [B, H, P, N]`` in and out."""
    T = u.shape[1]
    x, dt, Bm, Cm, z, conv_l = mixer_inputs(cfg, u, lp, conv_l, n_valid)
    real = (jnp.arange(T) < n_valid)[None, :, None]
    note_ssd_path("chunk", "xla")
    y, s_l = ssd.ssd_chunk(x, jnp.where(real, dt, 0.0), -jnp.exp(lp.a_log),
                           Bm, Cm, s_l, cfg.ssm_chunk)
    return mixer_output(cfg, y, x, z, lp, u.dtype), s_l, conv_l


def mixer_step(cfg, u, lp, l, rows, s_pool, conv_pool):
    """The mixer over one token a row, the pools in and out: row ``b``'s
    state and tail are ``[l, rows[b]]`` of them."""
    tail = jax.lax.dynamic_index_in_dim(conv_pool, l, 0, keepdims=False)[rows]
    x, dt, Bm, Cm, z, tail = mixer_inputs(cfg, u, lp, tail, None)
    conv_pool = conv_pool.at[l, rows].set(tail)
    kernel = ssd.step_kernel_choice()
    note_ssd_path("step", "xla" if kernel is None else "pallas")
    step = (ssd.ssd_step_xla if kernel is None
            else lambda *a: ssd.ssd_step(*a, **kernel))
    dt1 = dt[:, 0]
    y, s_pool = step(s_pool, l, rows, x[:, 0], dt1,
                     jnp.exp(-dt1 * jnp.exp(lp.a_log)), Bm[:, 0], Cm[:, 0])
    return mixer_output(cfg, y[:, None], x, z, lp, u.dtype), s_pool, conv_pool
