"""The plain reference of a decoder whose every layer runs a Mamba-2 (SSD)
mixer and grouped-query attention SIDE BY SIDE over one normed input. The
``reference`` module of ``falcon-h1-34b`` (README, "A layer equation").

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, no chunk form, nothing imported from ``dllama_tpu``; it
reads the same planes the engine holds and dequantizes one layer at a time.

**The equations**, for a layer's input ``x`` (every multiplier is read from
the configuration and applied where it stands here)::

    h = rmsnorm(x; w_in)
    a = attn(h * attention_in_multiplier) * attention_out_multiplier
    m = mamba(h * ssm_in_multiplier)      * ssm_out_multiplier
    x = x + a + m
    g = rmsnorm(x; w_ff)
    x = x + W_down(silu(W_gate g * mlp_multipliers[0]) * (W_up g)) * mlp_multipliers[1]

    (u is each mixer's scaled input)
    attn: q k v = W_q u, W_k u, W_v u; k = k * key_multiplier; rotary over the
          whole head, half-split pairing, theta rope_theta; causal GQA softmax
          at 1/sqrt(head_dim); W_o.
    mamba: [z | xBC | dt] = (W_inproj u) * mup_vector     # ssm_multipliers[0..4]
                                                          # over the z, x, B, C, dt lanes
          xBC = silu(causal_depthwise_conv(xBC) + conv_bias);  x_ B C = split(xBC)
          dt  = softplus(dt + dt_bias);  A = -exp(A_log)
          per head j (group j // (H / G)), per token t, one token after another:
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S [d_head, d_state], S_0 = 0
              y_t = S_t C_t + D x_t
          y = group_rmsnorm(y * silu(z); w_norm);  W_outproj y
    model: embed(ids) * embedding_multiplier ... final rmsnorm,
           logits = (W_head x) * lm_head_multiplier

The program holds ``W_inproj`` as two planes, the ``z x B C`` rows (Q40) and
the ``dt`` rows (float32); the reference reads both and joins them back.

**Departures from the published model, each deliberate:** weights are random
from the seed (``weights.py`` beside this file says at what scale each plane
is drawn and why). What the catalog's ``config`` does not settle is written
under ``assumed`` in the configuration's file and is data of THIS module:
the order of ``mup_vector``'s segments (z, x, B, C, dt), the norm's grouping
(``mamba_n_groups`` groups, the gate first as ``mamba_norm_before_gate``
false says), no clamp on ``dt``, ``mamba_use_mlp`` read as "the layer has its
feed-forward".

**Controls** (all made in the reference only): the dense decoders' ``shift``
(the emitted rows one position late), ``droplayer`` (the middle layer left
out) and ``dropblock`` (the emitted rows do not see the middle 16 prompt
positions: a cache block lost), and four of this equation's own:
``dropstate`` (every layer's state zeroed at every 256th position: a carry
lost between prefill chunks), ``nodecay`` (``exp(dt A)`` = 1), ``dropssm``
(``m`` = 0: one of the two side-by-side mixers missing, which a sum hides
more easily than a missing layer) and ``bf16state`` (``S`` rounded to
bfloat16 after every token by ``lax.reduce_precision``: the nearest precision
below the float32 the configuration states for the state).

**What no control or gap can see:** ``lm_head_multiplier`` scales every logit
alike, so it moves no greedy token and no gap (a gap is a difference of
logits over their spread). :func:`reference_logits` gives the logits
themselves, and ``tests/test_falcon_h1.py`` holds the program's to them on
the CPU; ``gap_tolerance.json`` names the multiplier as not caught.

**Two limits, one comparison**, as ``olmo_hybrid/reference.py`` has them and
for the same reason: a state in bfloat16 raises the NOISE of every logit and
hardly moves the worst position, so every call appends ONE entry to ``gap``
behind the request's positions, the mean gap pooled over every position this
engine's requests have shown so far, scaled by ``tolerance / mean_tolerance``
(``gap_tolerance.json`` has both, with their readings).
"""

import functools
import json
import os

import numpy as np

from reference import (BLOCK_Q, _attention, _dequant, _planes, _rms_norm, _rope, control_handles,
                       teacher_force, tolerance_from)

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
CONTROLS = ("none", "shift", "droplayer", "dropblock", "dropstate", "nodecay", "dropssm", "bf16state")
MIXER_VARIANTS = ("dropstate", "nodecay", "dropssm", "bf16state")     # the controls made inside the mixer
LOST_CARRY_EVERY = 256      # dropstate: the program's widest prefill chunk
POOL_MIN, POOL_FULL = 128, 200    # positions pooled before the mean gap counts at all, and in full

LEAVES = ("wq", "wk", "wv", "wo", "w_in", "w_dt", "conv_w", "conv_b", "a_log", "d_skip", "dt_bias",
          "norm_ssm", "w_out", "w1", "w2", "w3", "norm_att", "norm_ffn")


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def mean_tolerance(compute_dtype: str) -> float:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return float(json.load(f)["mean_tolerance"][compute_dtype])


def mixer(m: dict, u, lp, variant: str = "none"):
    """The SSD mixer over a whole sequence ``u [T, dim]``, one token after
    another. ``variant``: ``none`` or one of ``MIXER_VARIANTS``."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, G, N, K = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"], m["mamba_d_state"], m["mamba_d_conv"]
    d_ssm, gn = m["mamba_d_ssm"], G * N
    mz, mx, mb, mc, mdt = m["ssm_multipliers"]
    # W_inproj as published, [dim, d_ssm + (d_ssm + 2 G N) + H], and mup_vector over its lanes
    w_inproj = jnp.concatenate([_dequant(lp["w_in"]), lp["w_dt"].astype(jnp.float32).T], axis=1)
    mup = jnp.concatenate([jnp.full((d_ssm,), mz), jnp.full((d_ssm,), mx), jnp.full((gn,), mb),
                           jnp.full((gn,), mc), jnp.full((H,), mdt)]).astype(jnp.float32)
    proj = (u @ w_inproj) * mup
    z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn], proj[:, 2 * d_ssm + 2 * gn:]
    seq = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    taps = lp["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(taps[j] * seq[j:j + T] for j in range(K)) + lp["conv_b"])
    x = xbc[:, :d_ssm].reshape(T, H, P)
    per_head = lambda g: jnp.repeat(g.reshape(T, G, N), H // G, axis=1)      # a group's B or C for each of its heads
    Bm, Cm = per_head(xbc[:, d_ssm:d_ssm + gn]), per_head(xbc[:, d_ssm + gn:])
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(lp["a_log"]))
    if variant == "nodecay":
        decay = jnp.ones_like(decay)
    t = jnp.arange(T)
    lost = (t % LOST_CARRY_EVERY == 0) & (t > 0) & (variant == "dropstate")

    def token(S, xs):
        x_t, dt_t, a_t, b_t, c_t, lost_t = xs
        S = jnp.where(lost_t, 0.0, S)
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if variant == "bf16state":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)   # a convert pair may be elided
        return S, jnp.sum(S * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (x, dt, decay, Bm, Cm, lost))
    y = (y + lp["d_skip"][:, None] * x).reshape(T, d_ssm) * jax.nn.silu(z)
    grouped = y.reshape(T, G, d_ssm // G)
    eps = float(m["norm_epsilon"])
    normed = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    out = (normed.reshape(T, d_ssm) * lp["norm_ssm"]) @ _dequant(lp["w_out"])
    return jnp.zeros_like(out) if variant == "dropssm" else out


def attention(m: dict, u, lp, positions, hide):
    T = u.shape[0]
    Hq, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    theta = float(m["rope_theta"])
    q = (u @ _dequant(lp["wq"])).reshape(T, Hq, hd)
    k = (u @ _dequant(lp["wk"])).reshape(T, KV, hd) * m["key_multiplier"]
    v = (u @ _dequant(lp["wv"])).reshape(T, KV, hd)
    q, k = _rope(q, positions, theta, "half_split"), _rope(k, positions, theta, "half_split")
    return _attention(q, k, v, hide) @ _dequant(lp["wo"])


def layer(m: dict, x, lp, positions, hide, variant: str = "none"):
    """One layer with its two residual adds."""
    import jax

    eps = float(m["norm_epsilon"])
    gate_mult, down_mult = m["mlp_multipliers"]
    h = _rms_norm(x, lp["norm_att"], eps)
    a = attention(m, h * m["attention_in_multiplier"], lp, positions, hide) * m["attention_out_multiplier"]
    s = mixer(m, h * m["ssm_in_multiplier"], lp, variant) * m["ssm_out_multiplier"]
    x = x + a + s
    g = _rms_norm(x, lp["norm_ffn"], eps)
    ffn = (jax.nn.silu((g @ _dequant(lp["w1"])) * gate_mult) * (g @ _dequant(lp["w3"]))) @ _dequant(lp["w2"])
    return x + ffn * down_mult


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The stack, with ``reference.layers_program``'s signature: ``(tokens[T],
    embedding, layers, keep[L], shift, shift_from, hide) -> x[T, dim]``. Its
    own program because the embedding carries a multiplier."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32) * m["embedding_multiplier"]

        def body(x, xs):
            lp, keep_l = xs
            return x + keep_l * (layer(m, x, lp, positions, hide, variant) - x), None

        x, _ = jax.lax.scan(body, x, (layers, keep))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    return {n: _planes(getattr(params.layers, n)) for n in LEAVES}


def reference_logits(model: dict, params, tokens) -> np.ndarray:
    """Float32 logits ``[T, vocab]`` of the whole forward pass over
    ``tokens``, ``lm_head_multiplier`` included: what the CPU tests hold the
    program's logits to (a gap cannot see a common scale). Small sizes only:
    the head is dequantized whole."""
    import jax
    import jax.numpy as jnp

    T = -(-len(tokens) // BLOCK_Q) * BLOCK_Q
    padded = np.zeros(T, dtype=np.int32)
    padded[:len(tokens)] = tokens
    x = _layers_fn(json.dumps(model, sort_keys=True), "none")(
        jnp.asarray(padded), params.embedding, layer_tree(params),
        *control_handles(model["num_hidden_layers"], len(tokens), T, "none"))
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x[:len(tokens)], params.final_norm, float(model["norm_epsilon"]))
        return np.asarray((h @ _dequant(_planes(params.logits))) * model["lm_head_multiplier"])


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
