"""The plain reference of a decoder of gated short-convolution layers beside
grouped-query attention layers, routed experts behind leading dense layers.
The ``reference`` module of ``lfm2-24b-a2b`` (``lfm2/README.md``).

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, nothing imported from ``dllama_tpu``; it reads the same
Q40 planes the engine holds and dequantizes one layer (one expert) at a time.

**The equations.** Width ``hidden_size``; every layer is pre-norm: ``h = x +
Op_l(rmsnorm(x; w_o))``, ``out = h + Ffn_l(rmsnorm(h; w_f))``; after the last layer
``rmsnorm``, then the head.

* ``layer_types[l] == "conv"``: ``[B | C | X] = W_in u`` (``hidden -> 3 hidden``, split in
  that order), ``v_t = B_t * X_t``, ``c_t = sum_j w_j v_{t-(K-1)+j}`` over the WHOLE
  sequence with zeros in front of it (depthwise, causal, ``K = conv_L_cache`` taps
  a channel, no bias, no activation), ``y_t = C_t * c_t``, ``Op = W_out y``. No
  state: the sequence is there.
* ``layer_types[l] == "full_attention"``: ``q, k, v = W_q u, W_k u, W_v u``, an RMS
  norm over each head's lanes of ``q`` and of ``k``, rotary positions (theta
  ``rope_parameters.rope_theta``, lane ``j`` paired with ``j + head/2``), causal
  softmax at ``head ** -0.5`` over a dense ``[T, T]`` mask, query head ``j`` on K/V
  head ``floor(j / G)``, ``W_o``.
* the first ``num_dense_layers`` layers: ``Ffn = W2 (silu(W1 h) * W3 h)``.
* every other: ``s = sigmoid(W_g h)`` over all ``router_width`` in float32; the
  ``num_experts_per_tok`` experts are the ``top_k`` of ``s + b`` (the layer's learned
  bias: the SELECTION only); ``w_e = s_e / (sum_chosen s + 1e-6)`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``Ffn = sum_{e chosen, e held} w_e E_e(h)``, ``E_e`` a
  SwiGLU expert. Every held expert is computed for every row, one expert after
  another (a scan: one expert's three planes dequantized at a time, so ~1000
  positions x 18 layers fit beside the engine), and weighted by the row's ``w_e``,
  0 where the row did not choose it.

**Departures from the published model, each deliberate:** weights are random
from the seed (``weights.py`` beside this file says how the router's rows, its
bias, the taps and the q/k planes are drawn). What the published config does
not state is one value each in the configuration's ``program``, read HERE from the
model so that a correction is one line there and one branch here:
``norm_placement`` pre; ``rope_pairing`` half_split; ``in_proj_order`` B_C_X;
``router_score`` sigmoid; ``expert_bias`` selection_only; ``norm_topk_eps`` 1e-6;
``conv_activation`` none; ``qk_norm`` rms_per_head. The embedding is tied in the
family and held twice by the program: the reference reads the two the program
holds.

**Controls** (all made in the reference only): the dense decoders' ``shift``
(rotary positions of the emitted rows one late: the attention layers alone see
it), ``droplayer``, ``dropblock`` (16 prompt positions hidden from the emitted rows
in the attention layers: a cache block lost), and the equation's own: ``nobias``
(selection without ``b``), ``biasweight`` (weights from ``s + b``), ``convsilu`` (a SiLU
after the convolution), ``notail`` (the convolution sees zeros before each
position's own input: a lost tail), ``noqknorm`` (q and k unnormed), ``bf16router``
(the router's input, rows, logits and sigmoid rounded to bfloat16 with
``lax.reduce_precision``, which XLA does not elide: the nearest precision below
the float32 the configuration states for it).

**Two limits, one comparison**, as ``laguna/reference.py`` carries its second:
``run.py`` holds the largest entry of ``gap`` to ``tolerance``. A routed model at
depth flips an expert at a near-tie in some layer of some rows, which is
another function and not an error, so the widest gap alone may not part honest
runs from the controls. Every call appends ONE entry behind the request's
positions: the SHARE of the positions this engine's requests have shown so far
whose gap is over ``share_over`` (``gap_tolerance.json``), scaled by ``tolerance /
share_tolerance`` so that the same comparison holds it to ``share_tolerance``; 0
until ``POOL_MIN`` positions are pooled, scaled by ``n / POOL_FULL`` below
``POOL_FULL``.
"""

import functools
import json
import os

import numpy as np

from reference import _attention, _dequant, _planes, _rms_norm, _rope, swiglu, teacher_force, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("nobias", "biasweight", "convsilu", "notail", "noqknorm", "bf16router")   # made inside a layer
CONTROLS = ("none", "shift", "droplayer", "dropblock") + VARIANTS
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full

CONV_LEAVES = ("w_in", "conv_w", "w_out", "norm_att")
ATTN_LEAVES = ("wq", "wk", "wv", "wo", "norm_q", "norm_k", "norm_att")
DENSE_LEAVES = ("w1", "w2", "w3")
ROUTED_LEAVES = ("moe_gate", "moe_bias", "we1", "we2", "we3")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def pattern(m: dict) -> tuple[int, int]:
    """``(leading conv layers, period)`` of ``layer_types``."""
    kinds, lead = list(m["layer_types"]), int(m["num_dense_layers"])
    behind = kinds[lead:]
    P = behind.index("full_attention", 1) if "full_attention" in behind[1:] else len(behind)
    want = ["conv"] * lead + ["full_attention" if i % P == 0 else "conv" for i in range(len(behind))]
    if kinds != want or not lead or not behind:
        raise ValueError("layer_types is not num_dense_layers leading conv layers and then periods of a "
                         "full_attention layer and conv ones")
    return lead, P


def conv_half(m: dict, x, cp, variant: str):
    """A conv layer's operator over the whole sequence, residual added."""
    import jax
    import jax.numpy as jnp

    T, d = x.shape
    h = _rms_norm(x, cp["norm_att"], float(m["norm_epsilon"]))
    proj = h @ _dequant(cp["w_in"])                              # [T, 3 d]: B, C, X
    gate_b, gate_c, inner = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    v = gate_b * inner
    taps = cp["conv_w"].astype(jnp.float32)                      # [K, d], tap K - 1 on the current position
    K = taps.shape[0]
    if variant == "notail":
        c = taps[K - 1] * v
    else:
        seq = jnp.concatenate([jnp.zeros((K - 1, d), jnp.float32), v], axis=0)
        c = sum(taps[j] * seq[j:j + T] for j in range(K))
    if variant == "convsilu":
        c = jax.nn.silu(c)
    return x + (gate_c * c) @ _dequant(cp["w_out"])


def attention_half(m: dict, x, ap, positions, hide, variant: str):
    """An attention layer's operator, residual added: q/k/v, the per-head q/k
    norm, rotary positions, causal GQA attention, ``wo``."""
    T = x.shape[0]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // H
    eps, theta = float(m["norm_epsilon"]), float(m["rope_parameters"]["rope_theta"])
    h = _rms_norm(x, ap["norm_att"], eps)
    q = (h @ _dequant(ap["wq"])).reshape(T, H, hd)
    k = (h @ _dequant(ap["wk"])).reshape(T, KV, hd)
    v = (h @ _dequant(ap["wv"])).reshape(T, KV, hd)
    if variant != "noqknorm":
        q, k = _rms_norm(q, ap["norm_q"], eps), _rms_norm(k, ap["norm_k"], eps)
    q, k = _rope(q, positions, theta, m["rope_pairing"]), _rope(k, positions, theta, m["rope_pairing"])
    return x + _attention(q, k, v, hide) @ _dequant(ap["wo"])


def route(m: dict, h, gate, bias, variant: str):
    """``[T, held]``: a row's weight for each held expert, 0 where unchosen."""
    import jax
    import jax.numpy as jnp

    k, first, held = m["num_experts_per_tok"], m["first_expert"], m["num_experts"]
    gate = gate.astype(jnp.float32)
    if variant == "bf16router":
        round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        s = round16(jax.nn.sigmoid(round16(round16(h) @ round16(gate).T)))
    else:
        s = jax.nn.sigmoid(h @ gate.T)                           # [T, router_width]
    b = bias.astype(jnp.float32) if m["use_expert_bias"] else jnp.zeros_like(s[0])
    _, idx = jax.lax.top_k(s if variant == "nobias" else s + b, k)
    top = jnp.take_along_axis(s + b if variant == "biasweight" else s, idx, axis=-1)
    if m["norm_topk_prob"]:
        top = top / (top.sum(axis=-1, keepdims=True) + float(m["norm_topk_eps"]))
    top = top * m["routed_scaling_factor"]
    return (jax.nn.one_hot(idx - first, held, dtype=jnp.float32) * top[..., None]).sum(axis=-2)


def routed_ffn(m: dict, h, lp, variant: str):
    """``sum_{e chosen, e held} w_e E_e(h)``. Plain: every HELD expert over
    every row, one expert after another, weighted by the row's router weight
    for it; no sorting, no grouping, no gather of planes."""
    import jax
    import jax.numpy as jnp

    weight = route(m, h, lp["moe_gate"], lp["moe_bias"], variant)

    def expert(y, xs):
        planes, w_e = xs
        return y + w_e[:, None] * swiglu(h, planes["we1"], planes["we2"], planes["we3"]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), ({n: lp[n] for n in ("we1", "we2", "we3")}, weight.T))
    return y


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The stack in its pattern, with ``reference.layers_program``'s signature:
    ``(tokens[T], embedding, layers, keep[L], shift, shift_from, hide) -> x[T,
    dim]``; ``layers`` is ``{"conv", "attn", "norm_ffn", "dense", "routed"}``, ``keep``
    runs over the layers in the model's order."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    lead, P = pattern(m)
    for key, want in (("norm_placement", "pre"), ("in_proj_order", "B_C_X"), ("router_score", "sigmoid"),
                      ("expert_bias", "selection_only"), ("conv_activation", "none"), ("qk_norm", "rms_per_head")):
        if m[key] != want:
            raise ValueError(f"this reference writes program.{key} = {want!r}, not {m[key]!r}")

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)
        L = keep.shape[0]
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

        def layer(x, l, conv_i, attn_i):
            """Model layer ``l``: conv layer ``conv_i`` or attention layer ``attn_i``."""
            if attn_i is None:
                y = conv_half(m, x, at(layers["conv"], conv_i), variant)
            else:
                y = attention_half(m, x, at(layers["attn"], attn_i), positions, hide, variant)
            h = _rms_norm(y, layers["norm_ffn"][l], eps)
            if isinstance(l, int) and l < lead:
                y = y + swiglu(h, *(at(layers["dense"], l)[n] for n in ("w1", "w2", "w3")))
            else:
                y = y + routed_ffn(m, h, at(layers["routed"], l - lead), variant)
            return x + keep[l] * (y - x)

        def period(x, p, n_conv):
            l0 = lead + p * P
            x = layer(x, l0, None, p)
            for j in range(n_conv):
                x = layer(x, l0 + 1 + j, lead + p * (P - 1) + j, None)
            return x

        for l in range(lead):                       # the leading conv layers, unrolled
            x = layer(x, l, l, None)
        whole, rest = divmod(L - lead, P)
        x, _ = jax.lax.scan(lambda x, p: (period(x, p, P - 1), None), x, jnp.arange(whole))
        if rest:                                    # a last period the depth cuts short
            x = period(x, whole, rest - 1)
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    lp = params.layers
    return {"conv": {n: _planes(getattr(lp.conv, n)) for n in CONV_LEAVES},
            "attn": {n: _planes(getattr(lp.attn, n)) for n in ATTN_LEAVES},
            "norm_ffn": lp.norm_ffn,
            "dense": {n: _planes(getattr(lp, n)) for n in DENSE_LEAVES},
            "routed": {n: _planes(getattr(lp, n)) for n in ROUTED_LEAVES if getattr(lp, n) is not None}}


_pool = {"of": None, "gaps": []}    # the gaps one engine's requests have shown under one control


def pooled_share_entry(params, control: str, gap, compute_dtype: str) -> float:
    """The share of pooled positions over ``share_over`` as the one extra
    entry of ``gap`` (module docstring, "Two limits"). A pool belongs to one
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
    variant = control if control in VARIANTS else "none"
    tree = layer_tree(params)
    if "moe_bias" not in tree["routed"]:            # a model without the bias: a zero row a layer, never read
        import jax.numpy as jnp

        tree["routed"]["moe_bias"] = jnp.zeros(tree["routed"]["moe_gate"].shape[:2], jnp.float32)
    r = teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                      layers_fn=_layers_fn(json.dumps(model, sort_keys=True), variant), layers=tree)
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    r["gap"] = np.append(r["gap"], pooled_share_entry(params, control, r["gap"], dtype))
    return r
