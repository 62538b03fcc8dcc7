"""The plain reference of a decoder of window and full attention layers with
routed experts of which a SHARE is held. The ``reference`` module of
``laguna-s-2.1`` (README, "A layer equation").

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, nothing imported from ``dllama_tpu``; it reads the same
Q40 planes the engine holds and dequantizes one layer at a time.

**The equations.** Layer ``l``, input ``x`` (``hidden_size`` wide), ``H_l`` query
heads (``num_attention_heads_per_layer[l]``), ``G_l = H_l / num_key_value_heads``:

* ``h = rmsnorm(x; w_a)``. ``q = Wq h`` (``H_l`` x ``head_dim``), ``k = Wk h``, ``v =
  Wv h``, ``g = sigmoid(Wg h)`` (``H_l`` numbers).
* rotary, half-split pairing, on the first ``r`` lanes of every q and k head.
  Sliding layer: ``r = head_dim``, ``inv_freq_i = theta_s^(-2i/r)``. Full layer:
  ``r = head_dim * partial_rotary_factor``, YaRN: ``e_i = theta^(-2i/r)``,
  ``dim(n) = r ln(orig / (2 pi n)) / (2 ln theta)``, ``low = max(floor(dim(beta_fast)),
  0)``, ``high = min(ceil(dim(beta_slow)), r - 1)``, ``ramp_i = clip((i - low) / (high
  - low), 0, 1)``, ``inv_freq_i = (e_i / factor) ramp_i + e_i (1 - ramp_i)``, and
  ``cos``, ``sin`` both multiplied by ``attention_factor``.
* causal softmax attention at scale ``1/sqrt(head_dim)``, query head ``j`` on K/V
  head ``floor(j / G_l)``, over a dense ``[T, T]`` mask; in a sliding layer the
  query at position ``i`` sees keys ``i - window + 1 .. i`` only. ``o_j <- g_j
  o_j``; ``x <- x + Wo concat_j(o_j)``.
* ``h2 = rmsnorm(x; w_f)``. A layer of ``mlp_only_layers``: ``x <- x + W2 (silu(W1
  h2) * W3 h2)``. Every other: ``p = softmax(Wr h2)`` over all ``router_width`` in
  float32, ``T`` = the ``num_experts_per_tok`` largest, ``w_e = p_e / sum_T p``,
  ``x <- x + moe_routed_scaling_factor sum_{e in T} w_e E_e(h2) + S(h2)``, ``E_e``
  and ``S`` SwiGLU experts. Every held expert is computed for every row and
  weighted by the row's ``w_e``, 0 where the row did not choose it (the issue
  asked for a gather of each pair's planes; at 3,000 rows that is 50 TB a
  request, and this is as plain).
* after the last layer ``rmsnorm``, then the head.

**The share.** The planes hold ``num_experts`` experts, ``first_expert ..
first_expert + num_experts - 1`` of the ``router_width`` the router scores. A
chosen expert that is not held adds nothing, here as in the program: no chip
stands in for the others, and that partial sum is what goes on to the next
layer. Fewer heads and fewer rows of the vocabulary are simply a smaller
attention and a smaller head.

**Departures from the published model, each deliberate:** weights are random
from the seed (``weights.py`` beside this file says how the router's rows are
drawn). Six conventions are not in the published config; they are taken from
the family whose key names it uses (``decoder_sparse_step``, ``mlp_only_layers``,
``norm_topk_prob``, ``shared_expert_intermediate_size``: the Qwen2/3-MoE layout)
and from the headwise gate of "Gated Attention for Large Language Models"
(arXiv:2505.06708), one value each in the configuration's ``program``, read
HERE from the model so that a correction is one line there and one branch
here: ``norm_placement`` pre; ``qk_norm`` false; ``router_score`` softmax;
``shared_expert_gate`` false (the shared expert is added ungated);
``attention_gate``: a sigmoid of a projection of the layer's normed input,
multiplying each head's attention output before ``Wo``;
``window_counts_current_token`` true (a window of 512 is the current token and
the 511 before it). The YaRN ``attention_factor`` in the config, 1.4852030263919618,
is ``0.1 ln(128) + 1``; the header carries the factor and the reference reads
the config's number.

**Controls** (all made in the reference only): the dense decoders' ``shift``,
``droplayer``, ``dropblock``, and four of this equation's own: ``misroute`` (the
top ``num_experts_per_tok`` taken over the HELD experts only: a router that
scores its share and not the deployment), ``noshared`` (the shared expert left
out), ``nogate`` (``g = 1``), ``nowindow`` (a sliding layer sees the whole
prefix), and the nearest precision below the float32 the configuration states
for the router: ``bf16router`` (the router's input and rows rounded to bfloat16
before its float32 softmax; rounded with ``lax.reduce_precision``, which XLA
does not elide: PERF.md, PR 30). Tokens see it because of how the router's
rows are drawn (``weights.py``, "How the router's rows are drawn", part 2: a
direction every row of a layer shares, which cancels in a float32 softmax and
whose rounding does not): it reads like ``misroute``, here and where the
PROGRAM's router is the one that falls to bfloat16
(``tests/test_laguna.py::test_a_bfloat16_router_in_the_program_fails``).

**Two limits, one comparison** (as ``olmo_hybrid/reference.py`` carries its
second): ``run.py`` holds the largest entry of ``gap`` to ``tolerance``. A routed
model at depth flips an expert at a near-tie in some layer of most rows, which
is another function and not an error: single positions then read high in an
honest run, and the widest gap alone may not part honest runs from the
controls. So every call appends ONE entry behind the request's positions: the
SHARE of the positions this engine's requests have shown so far whose gap is
over ``share_over`` (``gap_tolerance.json``), scaled by ``tolerance /
share_tolerance`` so that the same comparison holds it to ``share_tolerance``.
A share over few positions is noisy, and ``run.py`` takes the largest entry of
a run, the early ones too: the entry is 0 until ``POOL_MIN`` positions are
pooled and scaled by ``n / POOL_FULL`` below ``POOL_FULL`` (four positions of
115 over the limit read 3.5% in an honest traced run and 1.6% so scaled; a run
of the cell checks 198-383). ``gap_tolerance.json`` has all three numbers and
the readings behind them.
"""

import functools
import json
import math
import os

import numpy as np

from reference import BLOCK_Q, _dequant, _planes, _rms_norm, swiglu, teacher_force, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
CONTROLS = ("none", "shift", "droplayer", "dropblock", "misroute", "noshared", "nogate", "nowindow", "bf16router")
VARIANTS = ("misroute", "noshared", "nogate", "nowindow", "bf16router")     # the controls made inside a layer
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "wg", "norm_att")
DENSE_LEAVES = ("w1", "w2", "w3")
ROUTED_LEAVES = ("moe_gate", "we1", "we2", "we3", "ws1", "ws2", "ws3")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def inv_freq(m: dict, kind: str):
    """``(inv_freq [r/2], scale)`` of a layer kind's rotary table."""
    rp = m["rope_parameters"][kind]
    r = int(round(m["head_dim"] * rp["partial_rotary_factor"]))
    i = np.arange(r // 2, dtype=np.float64)
    e = float(rp["rope_theta"]) ** (-2.0 * i / r)
    if rp["rope_type"] == "default":
        return e, 1.0
    dim = lambda n: r * math.log(rp["original_max_position_embeddings"] / (2 * math.pi * n)) \
        / (2 * math.log(rp["rope_theta"]))
    low, high = max(math.floor(dim(rp["beta_fast"])), 0), min(math.ceil(dim(rp["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (e / rp["factor"]) * ramp + e * (1.0 - ramp), float(rp["attention_factor"])


def rope(x, positions, inv, scale):
    """Rotate the first ``2 len(inv)`` lanes of ``x [T, heads, hd]``, lane ``j``
    paired with lane ``j + r/2``; the rest pass through."""
    import jax.numpy as jnp

    half = len(inv)
    ang = (positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :])
    c, s = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x0, x1, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c, rest], axis=-1)


def attention_half(m: dict, x, lp, positions, hide, kind: str, variant: str):
    """One layer's attention half over the whole sequence, residual added: a
    dense ``[T, T]`` mask a K/V head, in blocks of BLOCK_Q query rows."""
    import jax
    import jax.numpy as jnp

    T, hd, KV = x.shape[0], m["head_dim"], m["num_key_value_heads"]
    H = lp["wg"].shape[0]
    h = _rms_norm(x, lp["norm_att"], float(m["norm_epsilon"]))
    q = (h @ _dequant(lp["wq"])).reshape(T, H, hd)
    k = (h @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (h @ _dequant(lp["wv"])).reshape(T, KV, hd)
    gate = jax.nn.sigmoid(h @ lp["wg"].astype(jnp.float32).T)        # [T, H]
    if variant == "nogate":
        gate = jnp.ones_like(gate)
    table = inv_freq(m, kind)
    q, k = rope(q, positions, *table), rope(k, positions, *table)
    window = m["sliding_window"] if kind == "sliding_attention" and variant != "nowindow" else T + 1
    if not m["window_counts_current_token"]:
        window += 1
    qg = q.reshape(T // BLOCK_Q, BLOCK_Q, KV, H // KV, hd)
    key_pos = jnp.arange(T)

    def block(args):
        qb, b = args
        scores = jnp.einsum("tkmh,skh->kmts", qb, k) / jnp.sqrt(jnp.float32(hd))
        q_pos = (b * BLOCK_Q + jnp.arange(BLOCK_Q))[:, None]
        seen = (key_pos[None, :] <= q_pos) & (key_pos[None, :] > q_pos - window)
        lost = (q_pos >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
        scores = jnp.where((seen & ~lost)[None, None, :, :], scores, -jnp.inf)
        return jnp.einsum("kmts,skh->tkmh", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (qg, jnp.arange(T // BLOCK_Q))).reshape(T, H, hd)
    return x + (out * gate[..., None]).reshape(T, H * hd) @ _dequant(lp["wo"])


def routed_ffn(m: dict, h, lp, variant: str):
    """``scale sum_{e in T, e held} w_e E_e(h) + S(h)``. Plain: every HELD
    expert is computed for every row, one expert after another, and weighted
    by the row's router weight for it, 0 where the row did not choose it; no
    sorting, no grouping, no gather of planes (a gather of every pair's three
    3 MB planes for a request of 3,000 rows would move 50 TB)."""
    import jax
    import jax.numpy as jnp

    k, first, held = m["num_experts_per_tok"], m["first_expert"], m["num_experts"]
    gate = lp["moe_gate"].astype(jnp.float32)
    hr = h
    if variant == "bf16router":
        round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        hr, gate = round16(h), round16(gate)
    logits = hr @ gate.T                                             # [T, router_width]
    probs = jax.nn.softmax(logits, axis=-1) if m["router_score"] == "softmax" else jax.nn.sigmoid(logits)
    if variant == "misroute":
        top, idx = jax.lax.top_k(probs[:, first:first + held], k)
        idx = idx + first
    else:
        top, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    top = top * m["moe_routed_scaling_factor"]
    # [T, held]: a row's weight for each held expert, 0 where unchosen; an absent expert has no column
    weight = (jax.nn.one_hot(idx - first, held, dtype=jnp.float32) * top[..., None]).sum(axis=-2)

    def expert(y, xs):
        planes, w_e = xs
        return y + w_e[:, None] * swiglu(h, planes["we1"], planes["we2"], planes["we3"]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        ({n: lp[n] for n in ("we1", "we2", "we3")}, weight.T))
    if variant != "noshared":
        y = y + swiglu(h, lp["ws1"], lp["ws2"], lp["ws3"])
    return y


def pattern(m: dict):
    """``(period, leading dense layers)``."""
    kinds = m["layer_types"]
    P = kinds.index("full_attention", 1) if "full_attention" in kinds[1:] else len(kinds)
    return P, len(m.get("mlp_only_layers") or [])


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The stack in its pattern, with ``reference.layers_program``'s signature:
    ``(tokens[T], embedding, layers, keep[L], shift, shift_from, hide) -> x[T,
    dim]``; ``layers`` is ``{"full", "slide", "norm_ffn", "dense", "routed"}``,
    ``keep`` runs over the layers in the model's order."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    P, nd = pattern(m)
    if m["norm_placement"] != "pre" or m["qk_norm"] or m["shared_expert_gate"]:
        raise ValueError("this reference writes the pre-norm, no q/k norm, ungated shared expert conventions")

    def layer(x, ap, kind, norm_ffn, ffn, positions, hide):
        x = attention_half(m, x, ap, positions, hide, kind, variant)
        return x + ffn(_rms_norm(x, norm_ffn, eps))

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)
        L = keep.shape[0]
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
        dense_ffn = lambda i: (lambda h: swiglu(h, *(at(layers["dense"], i)[n] for n in ("w1", "w2", "w3"))))
        routed = lambda i: (lambda h: routed_ffn(m, h, at(layers["routed"], i), variant))

        def one(x, l, ap, kind, ffn):
            y = layer(x, ap, kind, layers["norm_ffn"][l], ffn, positions, hide)
            return x + keep[l] * (y - x)

        # the leading dense layers and the rest of their period, unrolled (one period); then a scan over periods
        first = -(-nd // P) * P if nd else 0
        for l in range(first):
            kind = "full_attention" if l % P == 0 else "sliding_attention"
            ap = at(layers["full"], l // P) if l % P == 0 else at(layers["slide"], l - l // P - 1)
            x = one(x, l, ap, kind, dense_ffn(l) if l < nd else routed(l - nd))

        def period(x, p):
            x = one(x, p * P, at(layers["full"], p), "full_attention", routed(p * P - nd))
            for j in range(1, P):
                l = p * P + j
                x = one(x, l, at(layers["slide"], l - p - 1), "sliding_attention", routed(l - nd))
            return x, None

        x, _ = jax.lax.scan(period, x, jnp.arange(first // P, L // P))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    lp = params.layers
    return {"full": {n: _planes(getattr(lp.full, n)) for n in ATTN_LEAVES},
            "slide": {n: _planes(getattr(lp.slide, n)) for n in ATTN_LEAVES},
            "norm_ffn": lp.norm_ffn,
            "dense": {n: _planes(getattr(lp, n)) for n in DENSE_LEAVES},
            "routed": {n: _planes(getattr(lp, n)) for n in ROUTED_LEAVES}}


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
    r = teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                      layers_fn=_layers_fn(json.dumps(model, sort_keys=True), variant),
                      layers=layer_tree(params))
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    r["gap"] = np.append(r["gap"], pooled_share_entry(params, control, r["gap"], dtype))
    return r
