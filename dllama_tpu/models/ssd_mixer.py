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

The mixer is split where its rows stop being independent: :func:`mixer_project`
and :func:`mixer_output` work a row at a time (the two Q40 planes, the ``dt``
rows, the gate, the grouped norm), :func:`chunk_part` and :func:`step_part`
own a context (the convolution against a tail, the recurrence against a
state). :func:`mixer_chunk` and :func:`mixer_step` put ONE part between the
two; :func:`mixer_chunk_and_step` puts both, over rows joined along ``T``
(``falcon_h1.forward_and_step``: a tick's chunk and its decode rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops import ssd
from ..ops.causal_conv import causal_conv
from ..ops.linear import linear
from ..runtime.introspection import note_ssd_path
from .config import ModelConfig
from .llama import _by_row, _join


def mixer_project(cfg: ModelConfig, u: jax.Array, lp):
    """What the mixer does a ROW at a time in front of its convolution, for
    ``u [B, T, dim]``: the packed in-projection ``proj [B, T, ssm_in_dim]``
    (the ``z x B C`` lanes, ONE read of ``w_in`` for however many rows), the
    float32 ``dt [B, T, H]`` before its bias and softplus, and the gate ``z
    [B, T, d_ssm]``. Rows of different sequences may be joined along ``T``:
    nothing here looks across them."""
    m = cfg.mult
    proj = linear(u, lp.w_in)
    dt = jnp.einsum("btd,hd->bth", u.astype(jnp.float32), lp.w_dt,
                    precision=jax.lax.Precision.HIGHEST) * m.ssm_dt
    z = proj[..., :cfg.ssm_inner_dim].astype(jnp.float32) * m.ssm_z
    return proj, dt, z


def mixer_conv(cfg: ModelConfig, proj: jax.Array, dt: jax.Array, lp,
               tail: jax.Array, n_valid):
    """What owns a context in front of the recurrence, for ONE sequence a
    batch row: :func:`mixer_project`'s ``proj`` and ``dt`` ``[B, T, ...]``
    through the convolution against ``tail [B, K - 1, C]``. Returns float32
    ``x [B, T, H, P]``, ``dt [B, T, H]`` (after its softplus), the groups'
    ``Bm, Cm [B, T, G, N]`` and the new tail."""
    B, T, _ = proj.shape
    m = cfg.mult
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_dim
    d_ssm, gn = cfg.ssm_inner_dim, G * N
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
    return x, jax.nn.softplus(dt + lp.dt_bias), Bm, Cm, tail


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


def chunk_part(cfg, proj, dt, lp, s_l, conv_l, n_valid):
    """Convolution and recurrence over ONE sequence's chunk, from
    :func:`mixer_project`'s rows of it: against the tail ``conv_l [B, K - 1,
    C]`` and the state ``s_l [B, H, P, N]``, ``dt`` zeroed at and past
    ``n_valid``. Returns float32 ``y, x [B, T, H, P]``, the state and the
    tail."""
    T = proj.shape[1]
    x, dt, Bm, Cm, conv_l = mixer_conv(cfg, proj, dt, lp, conv_l, n_valid)
    real = (jnp.arange(T) < n_valid)[None, :, None]
    note_ssd_path("chunk", "xla")
    y, s_l = ssd.ssd_chunk(x, jnp.where(real, dt, 0.0), -jnp.exp(lp.a_log),
                           Bm, Cm, s_l, cfg.ssm_chunk)
    return y, x, s_l, conv_l


def pool_tails(conv_pool, l, rows):
    """The rows' tails of layer ``l``: ``[B, K - 1, C]``."""
    return jax.lax.dynamic_index_in_dim(conv_pool, l, 0, keepdims=False)[rows]


def step_part(cfg, proj, dt, lp, l, rows, tail, s_pool, conv_pool):
    """Convolution and recurrence over one token a row, from
    :func:`mixer_project`'s rows ``[B, 1, ...]`` and the rows' ``tail``
    (:func:`pool_tails`): row ``b``'s state and tail are ``[l, rows[b]]`` of
    the pools, written in place. Returns float32 ``y, x [B, 1, H, P]`` and
    the pools."""
    x, dt, Bm, Cm, tail = mixer_conv(cfg, proj, dt, lp, tail, None)
    conv_pool = conv_pool.at[l, rows].set(tail)
    kernel = ssd.step_kernel_choice()
    note_ssd_path("step", "xla" if kernel is None else "pallas")
    step = (ssd.ssd_step_xla if kernel is None
            else lambda *a: ssd.ssd_step(*a, **kernel))
    dt1 = dt[:, 0]
    y, s_pool = step(s_pool, l, rows, x[:, 0], dt1,
                     jnp.exp(-dt1 * jnp.exp(lp.a_log)), Bm[:, 0], Cm[:, 0])
    return y[:, None], x, s_pool, conv_pool


def mixer_chunk(cfg, u, lp, s_l, conv_l, n_valid):
    """The mixer over a chunk: ``s_l [B, H, P, N]`` in and out."""
    proj, dt, z = mixer_project(cfg, u, lp)
    y, x, s_l, conv_l = chunk_part(cfg, proj, dt, lp, s_l, conv_l, n_valid)
    return mixer_output(cfg, y, x, z, lp, u.dtype), s_l, conv_l


def mixer_step(cfg, u, lp, l, rows, s_pool, conv_pool):
    """The mixer over one token a row, the pools in and out: row ``b``'s
    state and tail are ``[l, rows[b]]`` of them."""
    tail = pool_tails(conv_pool, l, rows)
    proj, dt, z = mixer_project(cfg, u, lp)
    y, x, s_pool, conv_pool = step_part(cfg, proj, dt, lp, l, rows, tail,
                                        s_pool, conv_pool)
    return mixer_output(cfg, y, x, z, lp, u.dtype), s_pool, conv_pool


def mixer_chunk_and_step(cfg, u, lp, l, T, s_l, conv_l, n_valid, rows,
                         s_pool, conv_pool):
    """A chunk and the tick's decode rows through ONE pass over the mixer's
    planes: ``u [1, T + R, dim]`` is the chunk's ``T`` rows and then one row
    a slot. The in-projection, the ``dt`` rows, the gate, the grouped norm
    and ``w_out`` run once over the joined rows; the convolution and the
    recurrence run a part at a time, the chunk's as :func:`mixer_chunk` has
    them (column layer ``s_l, conv_l``), the rows' as :func:`mixer_step`
    (the pools at ``[l, rows[b]]``). Returns ``y [1, T + R, dim]``, the
    column's layer and the pools."""
    tail = pool_tails(conv_pool, l, rows)
    proj, dt, z = mixer_project(cfg, u, lp)
    y_c, x_c, s_l, conv_l = chunk_part(cfg, proj[:, :T], dt[:, :T], lp, s_l,
                                       conv_l, n_valid)
    y_r, x_r, s_pool, conv_pool = step_part(
        cfg, _by_row(proj, T), _by_row(dt, T), lp, l, rows, tail, s_pool,
        conv_pool)
    return (mixer_output(cfg, _join(y_c, y_r), _join(x_c, x_r), z, lp,
                         u.dtype),
            (s_l, conv_l), (s_pool, conv_pool))
