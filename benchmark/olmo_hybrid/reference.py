"""The plain reference of a hybrid decoder: gated delta-rule (linear-attention)
layers and full softmax-attention layers in a periodic pattern. The
``reference`` module of ``olmo-hybrid-7b`` (README, "A layer equation").

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, nothing imported from ``dllama_tpu``; it reads the same
Q40 planes the engine holds and dequantizes one layer at a time.

**The equations.** ``layer_types`` is whole periods of ``P - 1`` linear layers
closed by one full layer. Every layer is two sublayers with a residual, the
norm on the sublayer's OUTPUT: ``x + rmsnorm(f(x))``; the second ``f`` is SwiGLU
in both kinds of layer.

A linear layer's mixer is the gated delta rule (Yang, Kautz, Hatamizadeh,
"Gated Delta Networks", ICLR 2025). For its input ``u_t`` (``hidden_size`` wide),
``H`` heads, keys ``dk`` and values ``dv`` wide:

* ``[q~ k~ v~ z] = W_in u`` (``H dk, H dk, H dv, H dv`` wide, one packed plane),
  ``[a b] = W_ab u`` (``H`` each, float32 rows); no bias.
* a causal depthwise convolution of ``K`` taps over time on every channel of
  ``q~ k~ v~``, then SiLU: ``q'_t = silu(sum_{j<K} c_j q~_{t-(K-1)+j})``, zeros
  before the sequence's start; no bias.
* per head ``q = l2norm(q') / sqrt(dk)``, ``k = l2norm(k')`` (``l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)``), ``v = v'``; ``beta = sigmoid(b)``, doubled where
  ``linear_allow_neg_eigval``; ``g = -exp(A_log) softplus(a + dt_bias)``, ``alpha
  = exp(g)``.
* the state, per head ``S`` in R^{dk x dv}, ``S_0 = 0``, one token after another,
  exactly as written (NOT the chunkwise form the program's prefill uses)::

      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t

* ``y = W_out (rmsnorm_dv(o_t; w_n) * silu(z_t))`` per head.

A full layer: ``q, k, v = W_q h, W_k h, W_v h``; q and k carry an RMS norm over
the WHOLE projection before the heads are split; no rotary embedding; plain
causal softmax attention; ``W_o``.

**Departures from the published model, each deliberate:** weights are random
from the seed (``weights.py`` beside this file says how the mixer's are drawn).
Three conventions are not in the published config and are taken from the Olmo
2/3 family; the architecture implies them, in the program and here: norms on a
sublayer's output, the q/k norm over the whole projection, no rotary embedding
(``rope_parameters.rope_theta`` is null). Head width 128 = 3840 / 30 is not in
the config either. The convolution
carries no bias. Key heads equal value heads (30 = 30): the mixer pairs them
one to one.

**Controls** (all made in the reference only): the dense decoders' ``droplayer``
(the middle layer left out) and ``dropblock`` (the emitted rows do not see the
middle 16 prompt positions in the full layers: a cache block lost), and three
of this equation's own: ``dropstate`` (every linear layer's state zeroed at
every 256th position: what a carry lost between prefill chunks looks like),
``nodecay`` (``alpha`` = 1: the decay left out of the rule) and ``bf16state``
(``S`` rounded to bfloat16 after every token: the nearest precision below the
float32 the configuration states for the state). The dense decoders' ``shift``
is not among them: nothing in this model reads a position.

**Two limits, one comparison.** ``run.py`` holds the largest entry of ``gap``
to the tolerance. A lost layer, carry or block moves single positions by whole
standard deviations and the worst position shows it. A state in bfloat16 does
not: it raises the NOISE of every logit, which flips a few more near-ties by a
little each, and the worst position of 200 hardly moves. The mean gap does: it
grows with the square of the noise. So every call appends ONE entry to ``gap``
behind the request's positions: the mean gap pooled over every position this
engine's requests have shown so far, scaled by ``tolerance / mean_tolerance``
so that the same comparison holds it to ``mean_tolerance``
(``gap_tolerance.json`` has both, with their readings). A mean over few
positions is noisy: the entry is 0 until ``POOL_MIN`` positions are pooled and
shrunk by ``sqrt(n / POOL_FULL)`` below ``POOL_FULL``, so a run is judged by the
mean over all it checked (a run of the cell checks 180-260 positions).
``margin`` and ``std`` keep one entry a position. The mean gap is also all
that tokens alone can say about noise: a likelihood ratio over margins and
flips does no better in simulation.
"""

import functools
import json
import os

import numpy as np

from reference import _attention, _dequant, _planes, _rms_norm, swiglu, teacher_force, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
CONTROLS = ("none", "droplayer", "dropblock", "dropstate", "nodecay", "bf16state")
MIXER_VARIANTS = ("dropstate", "nodecay", "bf16state")     # the controls made inside the mixer
LOST_CARRY_EVERY = 256      # dropstate: the program's widest prefill chunk
POOL_MIN, POOL_FULL = 128, 200    # positions pooled before the mean gap counts at all, and in full
L2_EPS = 1e-6

LINEAR_LEAVES = ("w_in", "w_ab", "conv_w", "a_log", "dt_bias", "norm_o", "w_out",
                 "w1", "w2", "w3", "norm_att", "norm_ffn")
FULL_LEAVES = ("wq", "wk", "wv", "wo", "norm_q", "norm_k", "w1", "w2", "w3", "norm_att", "norm_ffn")


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def mean_tolerance(compute_dtype: str) -> float:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return float(json.load(f)["mean_tolerance"][compute_dtype])


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def mixer(m: dict, u, lp, variant: str = "none"):
    """The gated delta-rule mixer over a whole sequence ``u [T, dim]``, one
    token after another. ``variant``: ``none`` or one of ``MIXER_VARIANTS``."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, dk, dv = m["linear_num_value_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    K, C = m["linear_conv_kernel_dim"], H * (2 * dk + dv)
    proj = u @ _dequant(lp["w_in"])
    qkv, z = proj[:, :C], proj[:, C:]
    ab = u @ lp["w_ab"].astype(jnp.float32).T
    seq = jnp.concatenate([jnp.zeros((K - 1, C), jnp.float32), qkv], axis=0)
    taps = lp["conv_w"].astype(jnp.float32)
    y = jax.nn.silu(sum(taps[j] * seq[j:j + T] for j in range(K)))
    q = _l2norm(y[:, :H * dk].reshape(T, H, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _l2norm(y[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = y[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(ab[:, H:]) * (2.0 if m["linear_allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(lp["a_log"]) * jax.nn.softplus(ab[:, :H] + lp["dt_bias"]))
    if variant == "nodecay":
        alpha = jnp.ones_like(alpha)
    t = jnp.arange(T)
    lost = (t % LOST_CARRY_EVERY == 0) & (t > 0) & (variant == "dropstate")

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t, lost_t = xs
        S = jnp.where(lost_t, 0.0, S)
        S = a_t[:, None, None] * S                                   # alpha_t S_{t-1}
        delta = b_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * S, axis=1))
        S = S + k_t[:, :, None] * delta[:, None, :]
        if variant == "bf16state":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)   # a convert pair may be elided
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)                # S_t^T q_t

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta, lost))
    gated = _rms_norm(o, lp["norm_o"], float(m["norm_epsilon"])) * jax.nn.silu(z.reshape(T, H, dv))
    return gated.reshape(T, H * dv) @ _dequant(lp["w_out"])


def full_attention(m: dict, h, lp, hide):
    T = h.shape[0]
    Hq, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = float(m["norm_epsilon"])
    q = _rms_norm(h @ _dequant(lp["wq"]), lp["norm_q"], eps)       # over the whole projection
    k = _rms_norm(h @ _dequant(lp["wk"]), lp["norm_k"], eps)
    v = h @ _dequant(lp["wv"])
    return _attention(q.reshape(T, Hq, hd), k.reshape(T, KV, hd), v.reshape(T, KV, hd), hide) @ _dequant(lp["wo"])


def period_of(m: dict) -> int:
    return m["layer_types"].index("full_attention") + 1


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The two stacks in their pattern, with ``reference.layers_program``'s
    signature: ``(tokens[T], embedding, layers, keep[L], shift, shift_from,
    hide) -> x[T, dim]``; ``layers`` is ``{"lin": .., "full": ..}``, ``keep``
    runs over the layers in the model's order; ``shift`` and ``shift_from``
    are taken and not read (no layer here reads a position)."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps, P = float(m["norm_epsilon"]), period_of(m)

    def sublayer(x, w, f):
        return x + _rms_norm(f(x), w, eps)

    def ffn(lp):
        return lambda h: swiglu(h, lp["w1"], lp["w2"], lp["w3"])

    def linear_layer(x, lp):
        x = sublayer(x, lp["norm_att"], lambda h: mixer(m, h, lp, variant))
        return sublayer(x, lp["norm_ffn"], ffn(lp))

    def full_layer(x, lp, hide):
        x = sublayer(x, lp["norm_att"], lambda h: full_attention(m, h, lp, hide))
        return sublayer(x, lp["norm_ffn"], ffn(lp))

    def run(tokens, embedding, layers, keep, _shift, _shift_from, hide):
        x = embedding[tokens].astype(jnp.float32)
        n_periods = keep.shape[0] // P
        by_period = lambda a: a.reshape((n_periods, a.shape[0] // n_periods) + a.shape[1:])

        def body(x, xs):
            lin_p, full_p, keep_p = xs
            for j in range(P - 1):      # three layers of one period, not the depth
                x = x + keep_p[j] * (linear_layer(x, jax.tree.map(lambda a: a[j], lin_p)) - x)
            return x + keep_p[P - 1] * (full_layer(x, full_p, hide) - x), None

        x, _ = jax.lax.scan(body, x, (jax.tree.map(by_period, layers["lin"]), layers["full"], by_period(keep)))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    lin, full = params.layers.lin, params.layers.full
    return {"lin": {n: _planes(getattr(lin, n)) for n in LINEAR_LEAVES},
            "full": {n: _planes(getattr(full, n)) for n in FULL_LEAVES}}


_pool = {"of": None, "gaps": []}    # the gaps one engine's requests have shown under one control


def pooled_mean_entry(params, control: str, gap, compute_dtype: str) -> float:
    """The pooled mean gap as the one extra entry of ``gap`` (module
    docstring, "Two limits"). A pool belongs to one ``params`` object and one
    control: another engine, or another control, starts it anew."""
    if _pool["of"] is None or _pool["of"][0] is not params or _pool["of"][1] != control:
        _pool.update(of=(params, control), gaps=[])
    _pool["gaps"].append(np.asarray(gap, dtype=np.float64))
    pooled = np.concatenate(_pool["gaps"])
    if len(pooled) < POOL_MIN:
        return 0.0
    shrink = min(1.0, len(pooled) / POOL_FULL) ** 0.5
    return float(pooled.mean()) * shrink * tolerance(compute_dtype) / mean_tolerance(compute_dtype)


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none") -> dict:
    variant = control if control in MIXER_VARIANTS else "none"
    r = teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                      layers_fn=_layers_fn(json.dumps(model, sort_keys=True), variant),
                      layers=layer_tree(params))
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    r["gap"] = np.append(r["gap"], pooled_mean_entry(params, control, r["gap"], dtype))
    return r
