"""The plain reference of a decoder whose feed-forward is routed: the worked
case of README's "A layer equation" (the self-test's ``tiny-qwen3-moe``).

The attention half is the dense decoders' (``reference.attention_half``). The
feed-forward, as Qwen3-MoE publishes it: router logits ``h W_g^T`` over
``num_experts``, softmax, the ``num_experts_per_tok`` largest, renormalised to
sum to 1 where ``norm_topk_prob``; the output is the sum over the chosen
experts of weight x SwiGLU_e(h), each expert ``moe_intermediate_size`` wide.
Plain float32: every expert is computed for every row and the unchosen ones
are weighted 0; no sorting, no gather. Imports nothing of the program.

**A router's near-tie is not an error.** Where a row's k-th and (k+1)-th
experts lie within NEAR_TIE of each other, the program's bf16 arithmetic takes
either, and the row's output is then another function: the gap of such a row
reads of order 1 in an honest run (found at the tiny size: one row of 95 at
2.1, seed 108, where the other 1,000 read under 0.02). So the reference runs
twice, once with its own choice and once with every near-tie taken the other
way, and a position's gap is the smaller of the two: a token is held against
both sides of a tie, and against nothing else.
"""

import functools
import json
import os

import numpy as np

from reference import ATTENTION_LEAVES, _rms_norm, attention_half, layer_tree, layers_program, swiglu, teacher_force
from reference import CONTROLS as DENSE_CONTROLS
from reference import tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
# the dense decoders' controls, and one of this equation's own: the reference
# routes every row to the experts its router likes LEAST (a wrong gather)
CONTROLS = DENSE_CONTROLS + ("misroute",)
NEAR_TIE = 0.05     # router logits; the tiny size's logits have a spread of 1 (gap_tolerance.json has the readings)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(os.path.join(_HERE, "gap_tolerance.json"), compute_dtype)


def routed_ffn(m: dict, h, lp, route: str = "top"):
    """``route``: ``top`` (the router's own choice), ``ties`` (the same, but a
    row whose k-th and (k+1)-th experts are a near-tie takes the (k+1)-th) or
    ``misroute`` (the control: the k experts the router likes least)."""
    import jax
    import jax.numpy as jnp

    E, k = m["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ lp["moe_gate"].astype(jnp.float32).T, axis=-1)       # [T, E]
    top, idx = jax.lax.top_k(probs, k + 1)
    near = (jnp.log(top[:, k - 1]) - jnp.log(top[:, k]) < NEAR_TIE) & (route == "ties")
    top = top[:, :k].at[:, k - 1].set(jnp.where(near, top[:, k], top[:, k - 1]))
    idx = idx[:, :k].at[:, k - 1].set(jnp.where(near, idx[:, k], idx[:, k - 1]))
    if route == "misroute":
        idx = jax.lax.top_k(-probs, k)[1]
    if m["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, E, dtype=jnp.float32) * top[..., None]).sum(axis=-2)   # [T, E], 0 where unchosen
    expert = lambda w, e: jax.tree.map(lambda a: a[e], w)      # noqa: E731  one expert's planes of an [E, ..] stack
    return sum(weight[:, e:e + 1] * swiglu(h, expert(lp["we1"], e), expert(lp["we2"], e), expert(lp["we3"], e))
               for e in range(E))


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, route: str):
    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])

    def layer(x, lp, positions, hide):
        x1 = attention_half(m, x, lp, positions, hide)
        return x1 + routed_ffn(m, _rms_norm(x1, lp["norm_ffn"], eps), lp, route)

    return layers_program(layer)


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none") -> dict:
    names = ATTENTION_LEAVES + ("moe_gate", "we1", "we2", "we3")
    if params.layers.norm_q is not None:
        names += ("norm_q", "norm_k")
    key, layers = json.dumps(model, sort_keys=True), layer_tree(params, names)

    def forced(route: str) -> dict:
        return teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                             layers_fn=_layers_fn(key, route), layers=layers)

    if control == "misroute":
        return forced("misroute")
    own, other = forced("top"), forced("ties")
    return {**own, "gap": np.minimum(own["gap"], other["gap"]), "finite": own["finite"] and other["finite"]}
