"""The plain reference of a decoder whose every layer is ONE pre-norm block of a
pattern: an SSD (Mamba-2) mixer, grouped-query attention without positions, or
a routed feed-forward of ungated experts in a latent space beside a shared one.
The ``reference`` module of ``nemotron-3-super-120b-a12b`` (``nemotron_h/README.md``).

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, no chunk form, nothing imported from ``dllama_tpu``; it
reads the same planes the engine holds and dequantizes one layer (one expert)
at a time. It is given the same share: the router scores ``router_width``
experts, the sum runs over the ``n_routed_experts`` held from ``first_expert``.
The multi-token-prediction head (``num_nextn_predict_layers``) is left out, as
the program leaves it out: it never enters the next-token logits.

**The equations** (``x <- x + Block_l(rmsnorm(x; w_l))``, eps ``norm_epsilon``; a
final RMS norm and an untied head; ``hybrid_override_pattern[l]`` names the block):

* ``M``, input ``u``: ``[z | xBC | dt] = W_in u``; ``xBC = silu(causal_conv(xBC) +
  conv_bias)`` over the WHOLE sequence with zeros in front; ``x_, B, C =
  split(xBC)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head ``j``
  (group ``j // (H / G)``), one token after another: ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T`` (``S_0 = 0``), ``y_t = S_t C_t + D x_t``; ``W_out group_rmsnorm(y *
  silu(z); w_norm)``. The program holds ``W_in`` as two planes (the ``z x B C``
  rows, Q40; the ``dt`` rows, float32): the reference joins them back.
* ``*``: ``q, k, v = W_q u, W_k u, W_v u``, causal softmax at ``head_dim ** -0.5``
  over a dense mask, query head ``j`` on K/V head ``floor(j / G)``, ``W_o``. NO
  positional embedding.
* ``E``, input ``h``: ``s = sigmoid(W_r h)`` in float32 over ``router_width``; the
  ``num_experts_per_tok`` experts are ``top_k(s + b)``; weights ``s_e / (sum of the
  chosen s + 1e-20) x routed_scaling_factor``; ``z = W_lat_in h``; ``r = sum_{e
  chosen, e held} w_e W2_e relu(W1_e z)^2``, every HELD expert over every row,
  one after another (a scan), weighted 0 where the row did not choose it;
  ``Block = W_lat_out r + Ws2 relu(Ws1 h)^2``.

What the published config does not state is one value each in the
configuration's ``program`` (``weights.ASSUMED`` has the list), read HERE from
the model: no clamp on ``dt``; the ``z x B C dt`` order of the in-projection; the
gate before the grouped norm; no positions in attention; the router and the
shared expert on ``h``; the renormalisation's 1e-20.

**Controls** (all made in the reference only): the dense decoders' ``shift``
(positions one late: this model HAS none, so it cannot be caught and the
tolerance file says so), ``droplayer``, ``dropblock`` (16 prompt positions hidden
from the emitted rows in the attention layers); falcon's ``dropstate`` (the
state zeroed at every 256th position), ``nodecay`` (``exp(dt A)`` = 1), ``bf16state``
(``S`` rounded to bfloat16 after every token); the routed decoders' ``misroute``
(the experts the router likes least), ``noshared`` (no shared expert),
``bf16router`` (the router's input, rows, logits and sigmoid rounded to bfloat16);
and four of this equation's own: ``nolatent`` (the experts fed ``h``'s first
``moe_latent_size`` lanes), ``gated`` (SiLU in place of the squared ReLU),
``nobias`` (selection without ``b``), ``rope`` (a rotary embedding at ``rope_theta``,
half-split, in the attention layers).

**Near-ties.** The 22nd and 23rd of 512 scores lie closer than the program's
bfloat16 stream resolves in a good share of (row, layer) pairs, and the other
choice is another function, not an error. Three devices, each measured
(``gap_tolerance.json``): the down-projection's gain (``weights.py``); a SECOND
pass that takes every near-tie (the 22nd and 23rd selection scores within
``near_tie``) the other way, a position's gap the smaller of the two; and two
limits under ``run.py``'s one comparison: the worst position against
``tolerance``, and ONE entry appended behind a request's positions, the SHARE of
the positions this engine's requests have shown so far whose gap is over
``share_over``, scaled by ``tolerance / share_tolerance``; 0 until ``POOL_MIN``
positions are pooled, scaled by ``n / POOL_FULL`` below ``POOL_FULL``.
"""

import functools
import json
import os

import numpy as np

from reference import _attention, _dequant, _planes, _rms_norm, _rope, teacher_force, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("dropstate", "nodecay", "bf16state", "misroute", "noshared", "bf16router", "nolatent", "gated",
            "nobias", "rope")                      # made inside a block
CONTROLS = ("none", "shift", "droplayer", "dropblock") + VARIANTS
LOST_CARRY_EVERY = 256      # dropstate: the program's widest prefill chunk
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full
KINDS = "M*E"

MIXER_LEAVES = ("w_in", "w_dt", "conv_w", "conv_b", "a_log", "d_skip", "dt_bias", "norm_ssm", "w_out", "norm")
ATTN_LEAVES = ("wq", "wk", "wv", "wo", "norm")
ROUTED_LEAVES = ("norm_moe", "moe_gate", "moe_bias", "w_lat_in", "w_lat_out", "we1", "we2", "ws1", "ws2")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def stack_indices(pattern: str) -> list[int]:
    """Layer ``l``'s index within its own kind's stack."""
    return [pattern[:l].count(kind) for l, kind in enumerate(pattern)]


def mixer(m: dict, u, lp, variant: str = "none"):
    """The SSD mixer over a whole sequence ``u [T, dim]``, one token after
    another."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, G, N, K = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"], m["conv_kernel"]
    d_ssm, gn = H * P, G * N
    # W_in as published, [dim, d_ssm + (d_ssm + 2 G N) + H]: z, xBC, dt
    proj = u @ jnp.concatenate([_dequant(lp["w_in"]), lp["w_dt"].astype(jnp.float32).T], axis=1)
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
    normed = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + float(m["norm_epsilon"]))
    return (normed.reshape(T, d_ssm) * lp["norm_ssm"]) @ _dequant(lp["w_out"])


def attention(m: dict, u, lp, positions, hide, variant: str = "none"):
    T = u.shape[0]
    Hq, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (u @ _dequant(lp["wq"])).reshape(T, Hq, hd)
    k = (u @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (u @ _dequant(lp["wv"])).reshape(T, KV, hd)
    if variant == "rope":
        theta = float(m["rope_theta"])
        q, k = _rope(q, positions, theta, "half_split"), _rope(k, positions, theta, "half_split")
    return _attention(q, k, v, hide) @ _dequant(lp["wo"])


def route(m: dict, h, gate, bias, variant: str, ties: bool, near_tie: float):
    """``[T, held]``: a row's weight for each held expert, 0 where unchosen.
    ``ties``: a row whose k-th and (k+1)-th selection scores lie within
    ``near_tie`` takes the (k+1)-th."""
    import jax
    import jax.numpy as jnp

    k, first, held = m["num_experts_per_tok"], m["first_expert"], m["n_routed_experts"]
    gate = gate.astype(jnp.float32)
    if variant == "bf16router":
        round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        s = round16(jax.nn.sigmoid(round16(round16(h) @ round16(gate).T)))
    else:
        s = jax.nn.sigmoid(h @ gate.T)                           # [T, router_width]
    chosen_by = s if variant == "nobias" else s + bias.astype(jnp.float32)
    if variant == "misroute":
        chosen_by = -chosen_by
    best, idx = jax.lax.top_k(chosen_by, k + 1)
    near = (best[:, k - 1] - best[:, k] < near_tie) & ties
    idx = idx[:, :k].at[:, k - 1].set(jnp.where(near, idx[:, k], idx[:, k - 1]))
    top = jnp.take_along_axis(s, idx, axis=-1)
    if m["norm_topk_prob"]:
        top = top / (top.sum(axis=-1, keepdims=True) + float(m["norm_topk_eps"]))
    top = top * m["routed_scaling_factor"]
    return (jax.nn.one_hot(idx - first, held, dtype=jnp.float32) * top[..., None]).sum(axis=-2)


def routed_block(m: dict, h, lp, variant: str, ties: bool, near_tie: float):
    """``W_lat_out sum_{e chosen, e held} w_e E_e(W_lat_in h) + S(h)``."""
    import jax
    import jax.numpy as jnp

    act = jax.nn.silu if variant == "gated" else (lambda a: jnp.square(jax.nn.relu(a)))
    weight = route(m, h, lp["moe_gate"], lp["moe_bias"], variant, ties, near_tie)
    z = h[:, :m["moe_latent_size"]] if variant == "nolatent" else h @ _dequant(lp["w_lat_in"])

    def expert(y, xs):
        planes, w_e = xs
        return y + w_e[:, None] * (act(z @ _dequant(planes["we1"])) @ _dequant(planes["we2"])), None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(z), ({n: lp[n] for n in ("we1", "we2")}, weight.T))
    out = r @ _dequant(lp["w_lat_out"])
    if variant == "noshared":
        return out
    return out + act(h @ _dequant(lp["ws1"])) @ _dequant(lp["ws2"])


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str, ties: bool, near_tie: float):
    """The stack in its pattern, with ``reference.layers_program``'s signature:
    ``(tokens[T], embedding, layers, keep[L], shift, shift_from, hide) -> x[T,
    dim]``; ``layers`` is ``{"mixer", "attn", "routed"}``, three stacks each over
    its own layers, ``keep`` runs over the layers in the model's order. ONE scan
    over the layers, the block chosen by the layer's kind."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    pattern = m["hybrid_override_pattern"]
    kinds = jnp.asarray([KINDS.index(c) for c in pattern], jnp.int32)
    index = jnp.asarray(stack_indices(pattern), jnp.int32)

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

        def mixer_block(x, i):
            lp = at(layers["mixer"], i)
            return mixer(m, _rms_norm(x, lp["norm"], eps), lp, variant)

        def attn_block(x, i):
            lp = at(layers["attn"], i)
            return attention(m, _rms_norm(x, lp["norm"], eps), lp, positions, hide, variant)

        def moe_block(x, i):
            lp = at(layers["routed"], i)
            return routed_block(m, _rms_norm(x, lp["norm_moe"], eps), lp, variant, ties, near_tie)

        def layer(x, l):
            y = jax.lax.switch(kinds[l], [mixer_block, attn_block, moe_block], x, index[l])
            return x + keep[l] * y, None

        x, _ = jax.lax.scan(layer, x, jnp.arange(len(pattern)))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    lp = params.layers
    return {"mixer": {n: _planes(getattr(lp.mixer, n)) for n in MIXER_LEAVES},
            "attn": {n: _planes(getattr(lp.attn, n)) for n in ATTN_LEAVES},
            "routed": {n: _planes(getattr(lp, n)) for n in ROUTED_LEAVES}}


def _forced(model: dict, params, prompt, emitted, control: str, ties: bool, near_tie: float) -> dict:
    variant = control if control in VARIANTS else "none"
    return teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                         layers_fn=_layers_fn(json.dumps(model, sort_keys=True), variant, ties, near_tie),
                         layers=layer_tree(params))


def reference_logits(model: dict, params, tokens) -> np.ndarray:
    """Float32 logits ``[T, vocab]`` of the whole forward pass over ``tokens``:
    what the CPU tests hold the program's logits to. Small sizes only: the head
    is dequantized whole."""
    import jax
    import jax.numpy as jnp

    from reference import BLOCK_Q, control_handles

    T = -(-len(tokens) // BLOCK_Q) * BLOCK_Q
    padded = np.zeros(T, dtype=np.int32)
    padded[:len(tokens)] = tokens
    x = _layers_fn(json.dumps(model, sort_keys=True), "none", False, 0.0)(
        jnp.asarray(padded), params.embedding, layer_tree(params),
        *control_handles(model["num_hidden_layers"], len(tokens), T, "none"))
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x[:len(tokens)], params.final_norm, float(model["norm_epsilon"]))
        return np.asarray(h @ _dequant(_planes(params.logits)))


_pool = {"of": None, "gaps": []}    # the gaps one engine's requests have shown under one control


def pooled_share_entry(params, control: str, gap, compute_dtype: str) -> float:
    """The share of pooled positions over ``share_over`` as the one extra
    entry of ``gap`` (module docstring, "Near-ties"). A pool belongs to one
    ``params`` object and one control."""
    if _pool["of"] is None or _pool["of"][0] is not params or _pool["of"][1] != control:
        _pool.update(of=(params, control), gaps=[])
    _pool["gaps"].append(np.asarray(gap, dtype=np.float64))
    pooled = np.concatenate(_pool["gaps"])
    if len(pooled) < POOL_MIN:
        return 0.0
    lim = _limits()
    share = float(np.mean(pooled > lim["share_over"][compute_dtype])) * min(1.0, len(pooled) / POOL_FULL)
    return share * tolerance(compute_dtype) / lim["share_tolerance"][compute_dtype]


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none") -> dict:
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    near_tie = float(_limits()["near_tie"][dtype])
    r = _forced(model, params, prompt, emitted, control, False, near_tie)
    if control != "misroute":               # the other side of every near-tie: a token is held against both
        other = _forced(model, params, prompt, emitted, control, True, near_tie)
        r = {**r, "gap": np.minimum(r["gap"], other["gap"]), "finite": r["finite"] and other["finite"]}
    r["gap"] = np.append(r["gap"], pooled_share_entry(params, control, r["gap"], dtype))
    return r
