"""The plain reference of a decoder whose period is sliding-window layers CLOSED
by a full one, with a per-head q/k norm and every layer routed: the
``reference`` module of ``mellum2-12b-a2.5b`` (README, "A layer equation").

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, nothing imported from ``dllama_tpu/models`` or
``dllama_tpu/ops``; it reads the same Q40 planes the engine holds and
dequantizes one layer at a time. Attention runs in blocks of ``BLOCK_ROWS``
query rows against ALL keys under a dense mask, so that 11,776 positions fit
beside the engine on one chip (a block's scores are 190 MB).

**The equations.** Layer ``l`` of ``layer_types``, input ``x`` (``hidden_size``
wide), ``H`` query heads on ``KV`` K/V heads, ``G = H / KV``:

* ``h = rmsnorm(x; w_a, eps)``. ``q = Wq h`` (``H`` x ``head_dim``), ``k = Wk h``,
  ``v = Wv h``, no bias. ``q_j <- rmsnorm(q_j; w_q)``, ``k_j <- rmsnorm(k_j; w_k)``
  over the lanes of each head, in front of the rotary embedding.
* rotary over the WHOLE head, half-split pairing (lane ``i`` with lane ``i +
  head_dim / 2``). Sliding layer: ``inv_freq_i = theta^(-2i/d)``. Full layer,
  YaRN: ``e_i = theta^(-2i/d)``, ``dim(n) = d ln(orig / (2 pi n)) / (2 ln theta)``,
  ``low = max(floor(dim(beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), d -
  1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = (e_i /
  factor) ramp_i + e_i (1 - ramp_i)``, ``cos`` and ``sin`` both multiplied by
  ``attention_factor``.
* causal softmax attention at scale ``head_dim^-0.5``, query head ``j`` on K/V
  head ``floor(j / G)``; in a sliding layer the query at ``i`` sees keys ``i -
  window + 1 .. i``. ``x <- x + Wo concat_j(o_j)``. No gate.
* ``h2 = rmsnorm(x; w_f)``; ``p = softmax(Wr h2)`` over all ``num_experts`` in
  float32; ``T`` = the ``num_experts_per_tok`` largest; ``w_e = p_e / sum_T p``
  (``norm_topk_prob``); ``x <- x + sum_{e in T} w_e W2_e (silu(W1_e h2) * W3_e
  h2)``. Every expert is computed for every row and weighted by the row's
  ``w_e``, 0 where the row did not choose it: no sorting, no grouping, no
  gather of planes.
* after the last layer ``rmsnorm``, then the untied head.

**Departures from the published description, each deliberate.** Weights are
random from the seed (``weights.py`` beside this file). THE Q/K NORM IS
ASSUMED: the config has no key for it; its key set (``max_window_layers``,
``use_sliding_window``, ``norm_topk_prob``, ``moe_intermediate_size``,
``num_experts``, no attention bias, no shared expert) is Qwen3-MoE's, whose
attention always has the two norms. THE MTP HEAD IS LEFT OUT: the catalog's
``described_as.other`` names one, the config has no key for it and its
equations are not in the row, so the next-token path alone is computed, here
and in the program. Four more conventions the config does not state are one
value each in the configuration's ``program``, read HERE from the model so that
a correction is one line there and one branch here: ``norm_placement`` pre;
``qk_norm`` true; ``rope_pairing`` half_split; ``window_counts_current_token``
true (a window of 1024 is the current token and the 1023 before it). The cell
holds layers 0-15 of 28 (``reduced``); every layer held is whole.

**Controls** (all made in the reference only): ``shift`` (emitted rows one
position late), ``droplayer`` (the middle layer left out), ``nowindow`` (a
sliding layer sees the whole prefix), ``ropeswap`` (the two layer kinds' rotary
tables exchanged), ``noqknorm`` (q and k not normed), ``rawtopk`` (the chosen
weights not renormalised), ``bf16router`` (the router's input and rows rounded
to bfloat16 before its float32 softmax: the nearest precision below the one
stated; ``weights.py`` says why tokens see it), and ``dropwindowblock``: 16
positions inside the last window before a prefix boundary hidden from the
SLIDING layers' queries at and behind that boundary, which is what a stale or
missing parked window block looks like to a request admitted behind a match
(runtime/kvblocks.py, "Window layers": the full layers' blocks are whole, the
sliding layers read another function). ``reference_gaps(..., boundary=m)`` hides
them before the boundary it is given (the tests do, behind a real match). The
whole command gives the reference a prompt and no more, and the boundary the
program matched is not in it: there the boundary is taken a quarter of a window
in front of the prompt's end, and the hidden block half a window in front of
that. In the cell a turn adds 320-1,216 tokens to the session, so the block
lies in the matched prefix's last window (a parked block) where the turn added
under 760 tokens and in the request's own rows otherwise; either way every
emitted row's window reads across it, as it reads across a block that went
stale.

**Two limits, one comparison**, as ``laguna/reference.py`` carries them: every
call appends ONE entry behind the request's positions, the share of the
positions this engine's requests have shown so far whose gap is over
``share_over``, scaled by ``tolerance / share_tolerance``, 0 until ``POOL_MIN``
positions are pooled and scaled by ``n / POOL_FULL`` below ``POOL_FULL``.
``gap_tolerance.json`` has the numbers and the readings behind them.
"""

import functools
import json
import math
import os

import numpy as np

from reference import BLOCK_Q, _dequant, _planes, _rms_norm, control_handles, head_gaps, swiglu, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("nowindow", "ropeswap", "noqknorm", "rawtopk", "bf16router")     # the controls made inside a layer
CONTROLS = ("none", "shift", "droplayer", "dropwindowblock") + VARIANTS
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full
BLOCK_ROWS = 128                 # query rows an attention block (BLOCK_Q, the sequence's padding, is 4 of them)
LOST_BLOCK = 16                  # positions dropwindowblock hides

ATTN_LEAVES = ("wq", "wk", "wv", "wo", "norm_att", "norm_q", "norm_k")
ROUTED_LEAVES = ("moe_gate", "we1", "we2", "we3")
KINDS = ("sliding_attention", "full_attention")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def inv_freq(m: dict, kind: str):
    """``(inv_freq [d/2], scale)`` of a layer kind's rotary table, over the whole head."""
    rp, d = m["rope_parameters"][kind], m["head_dim"]
    i = np.arange(d // 2, dtype=np.float64)
    e = float(rp["rope_theta"]) ** (-2.0 * i / d)
    if rp["rope_type"] == "default":
        return e, 1.0
    dim = lambda n: d * math.log(rp["original_max_position_embeddings"] / (2 * math.pi * n)) \
        / (2 * math.log(rp["rope_theta"]))
    low, high = max(math.floor(dim(rp["beta_fast"])), 0), min(math.ceil(dim(rp["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (e / rp["factor"]) * ramp + e * (1.0 - ramp), float(rp["attention_factor"])


def rope(x, positions, inv, scale):
    """Rotate ``x [T, heads, d]``, lane ``j`` paired with lane ``j + d/2``."""
    import jax.numpy as jnp

    half = len(inv)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    c, s = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x0, x1 = x[..., :half], x[..., half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


def attention_half(m: dict, x, lp, positions, hide, kind: str, variant: str):
    """One layer's attention half over the whole sequence, residual added: a
    dense ``[rows, T]`` mask a K/V head, in blocks of BLOCK_ROWS query rows.
    ``hide = (from_row, lo, hi)``: queries at or behind ``from_row`` do not see
    keys ``lo .. hi - 1`` (of this layer: the caller passes none to a layer the
    control leaves alone)."""
    import jax
    import jax.numpy as jnp

    T, hd, KV, H = x.shape[0], m["head_dim"], m["num_key_value_heads"], m["num_attention_heads"]
    eps = float(m["norm_epsilon"])
    h = _rms_norm(x, lp["norm_att"], eps)
    q = (h @ _dequant(lp["wq"])).reshape(T, H, hd)
    k = (h @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (h @ _dequant(lp["wv"])).reshape(T, KV, hd)
    if variant != "noqknorm":
        q, k = _rms_norm(q, lp["norm_q"], eps), _rms_norm(k, lp["norm_k"], eps)
    table_of = kind if variant != "ropeswap" else KINDS[1 - KINDS.index(kind)]
    table = inv_freq(m, table_of)
    q, k = rope(q, positions, *table), rope(k, positions, *table)
    window = m["sliding_window"] if kind == "sliding_attention" and variant != "nowindow" else T + 1
    if not m["window_counts_current_token"]:
        window += 1
    qg = q.reshape(T // BLOCK_ROWS, BLOCK_ROWS, KV, H // KV, hd)
    key_pos = jnp.arange(T)

    def block(args):
        qb, b = args
        scores = jnp.einsum("tkmh,skh->kmts", qb, k) / jnp.sqrt(jnp.float32(hd))
        q_pos = (b * BLOCK_ROWS + jnp.arange(BLOCK_ROWS))[:, None]
        seen = (key_pos[None, :] <= q_pos) & (key_pos[None, :] > q_pos - window)
        lost = (q_pos >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
        scores = jnp.where((seen & ~lost)[None, None, :, :], scores, -jnp.inf)
        return jnp.einsum("kmts,skh->tkmh", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (qg, jnp.arange(T // BLOCK_ROWS))).reshape(T, H * hd)
    return x + out @ _dequant(lp["wo"])


def routed_ffn(m: dict, h, lp, variant: str):
    """``sum_{e in T} w_e E_e(h)``: every expert over every row, one expert
    after another, weighted by the row's router weight for it, 0 where the row
    did not choose it."""
    import jax
    import jax.numpy as jnp

    k, E = m["num_experts_per_tok"], m["num_experts"]
    gate = lp["moe_gate"].astype(jnp.float32)
    hr = h
    if variant == "bf16router":
        round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        hr, gate = round16(h), round16(gate)
    probs = jax.nn.softmax(hr @ gate.T, axis=-1)                     # [T, E]
    top, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"] and variant != "rawtopk":
        top = top / top.sum(axis=-1, keepdims=True)
    weight = (jax.nn.one_hot(idx, E, dtype=jnp.float32) * top[..., None]).sum(axis=-2)      # [T, E]

    def expert(y, xs):
        planes, w_e = xs
        return y + w_e[:, None] * swiglu(h, planes["we1"], planes["we2"], planes["we3"]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), ({n: lp[n] for n in ("we1", "we2", "we3")}, weight.T))
    return y


def period_of(m: dict) -> int:
    """The period: sliding layers closed by a full one."""
    kinds = m["layer_types"]
    P = kinds.index("full_attention") + 1
    if kinds != (["sliding_attention"] * (P - 1) + ["full_attention"]) * (len(kinds) // P) \
            or set(m["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("layer_types is not whole periods of sliding layers closed by a full one, all sparse")
    return P


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The stack in its pattern: ``(tokens[T], embedding, layers, keep[L], shift,
    shift_from, hide, hide_slide) -> x[T, dim]``; ``layers`` is ``{"full", "slide",
    "norm_ffn", "routed"}``, ``keep`` runs over the layers in the model's order,
    ``hide`` is every layer's, ``hide_slide`` the sliding layers' alone."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    P = period_of(m)
    if m["norm_placement"] != "pre" or not m["qk_norm"] or m["rope_pairing"] != "half_split":
        raise ValueError("this reference writes the pre-norm, q/k-normed, half-split conventions")

    def run(tokens, embedding, layers, keep, shift, shift_from, hide, hide_slide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
        both = lambda a, b: jnp.where(b[2] > b[1], b, a)      # at most one of the two hides anything

        def one(x, l, ap, kind):
            hidden = both(hide, hide_slide) if kind == "sliding_attention" else hide
            y = attention_half(m, x, ap, positions, hidden, kind, variant)
            y = y + routed_ffn(m, _rms_norm(y, layers["norm_ffn"][l], eps), at(layers["routed"], l), variant)
            return x + keep[l] * (y - x)

        def period(x, p):
            for j in range(P - 1):
                x = one(x, p * P + j, at(layers["slide"], p * (P - 1) + j), "sliding_attention")
            return one(x, p * P + P - 1, at(layers["full"], p), "full_attention"), None

        x, _ = jax.lax.scan(period, x, jnp.arange(keep.shape[0] // P))
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
            "routed": {n: _planes(getattr(lp, n)) for n in ROUTED_LEAVES}}


def window_block_hidden(m: dict, n_prompt: int, T: int, boundary: int | None):
    """``dropwindowblock``'s ``(from_row, lo, hi)``: the 16 positions half a
    window in front of ``boundary`` hidden from the rows at and behind it. With
    no boundary given, one a quarter of a window in front of the prompt's end
    (module docstring)."""
    W = int(m["sliding_window"])
    if boundary is None:
        boundary = max(0, n_prompt - 1 - W // 4) // LOST_BLOCK * LOST_BLOCK
    lo = max(0, boundary - W // 2 - LOST_BLOCK // 2)
    return (boundary, lo, min(lo + LOST_BLOCK, max(boundary, lo)))


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


def stack_output(model: dict, params, tokens, n_prompt: int, control: str = "none", boundary: int | None = None):
    """The stack's output ``x [T, dim]`` over ``tokens`` (padded to BLOCK_Q by
    the caller) under ``control``."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    T = len(tokens)
    variant = control if control in VARIANTS else "none"
    hide_slide = window_block_hidden(model, n_prompt, T, boundary) if control == "dropwindowblock" else (T, 0, 0)
    fn = _layers_fn(json.dumps(model, sort_keys=True), variant)
    return fn(jnp.asarray(tokens), params.embedding, layer_tree(params),
              *control_handles(model["num_hidden_layers"], n_prompt, T, control),
              jnp.asarray(hide_slide, dtype=jnp.int32))


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none",
                   boundary: int | None = None) -> dict:
    seq = list(prompt) + list(emitted[:-1])
    T = -(-len(seq) // BLOCK_Q) * BLOCK_Q
    tokens = np.zeros(T, dtype=np.int32)
    tokens[:len(seq)] = seq
    x = stack_output(model, params, tokens, len(prompt), control, boundary)
    r = head_gaps(model, params, x, len(prompt), emitted)
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    r["gap"] = np.append(r["gap"], pooled_share_entry(params, control, r["gap"], dtype))
    return r
