"""The plain reference of a decoder of delta-rule layers whose decay is a VECTOR
a head (Kimi Delta Attention, arXiv:2510.26692) beside gated full attention
without positions, routed experts and a shared one behind EVERY mixer. The
``reference`` module of ``solar-open2-250b`` (``solar_open2/README.md``).

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, **the per-token recurrence and nothing chunked**, nothing
imported from ``dllama_tpu``; it reads the same planes the engine holds and
dequantizes one layer (one expert) at a time. It is given the same share: the
router scores ``router_width`` experts, the sum runs over the ``n_routed_experts``
held from ``first_expert``.

**The equations** (``rms_norm_eps`` in every norm; ``gqa_layers`` are the first
layer of every period of ``gqa_interval + 1``)::

    h = x + Mixer_l(rmsnorm(x; w_l^att));  y = h + MoE_l(rmsnorm(h; w_l^ffn))

* a ``gqa_layers`` layer, input ``u``: ``q, k, v = W_q u, W_k u, W_v u``; causal
  softmax at ``head_dim ** -0.5`` over a dense mask in blocks of ``ATTN_BLOCK``
  query rows, query head ``j`` on K/V head ``floor(j / G)``; NO positional
  embedding (``use_rope`` false); ``W_o (sigmoid(W_g u) * attn)``, one gate a lane
  (``use_gqa_gate``).
* every other layer (KDA), ``H`` heads of ``d`` = ``linear_attn_config.head_dim``:
  ``q~ k~ v~ = W_q u, W_k u, W_v u``; a causal depthwise convolution of
  ``short_conv_kernel_size`` taps over each channel, zeros before the sequence's
  start, then SiLU; per head ``q = l2norm(q') / sqrt(d)``, ``k = l2norm(k')``
  (``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``); ``g = -exp(A_log[h]) softplus(W_f^up
  W_f^down u + dt_bias)``, ``d`` numbers a head, ``alpha = exp(g)``; ``beta = 2
  sigmoid(W_b u)`` (``kda_allow_neg_eigval``); the state, per head ``S`` in R^{d x
  d}, ``S_0 = 0``, one token after another, exactly as written::

      S' = Diag(alpha_t) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  ``y = W_o (rmsnorm_d(o; w_n) * sigmoid(W_g^up W_g^down u))``.
* ``MoE_l``, input ``h``: ``s = sigmoid(W_r h)`` in float32 over ``router_width``; the
  ``num_experts_per_tok`` experts are ``top_k(s + b)``; weights ``s_e / sum of the
  chosen s`` (``norm_topk_prob``) ``x routed_scaling_factor``; an expert is ``W2_e
  (silu(W1_e h) * W3_e h)``; ONLY THE CHOSEN HELD EXPERTS ARE COMPUTED: the
  (row, expert) pairs are sorted by expert, the absent ones last and weighted
  0, each held expert's run padded to whole blocks of ``PAIR_BLOCK`` rows, and
  a scan over the blocks dequantizes the ONE expert a block belongs to; the
  shared expert ``Ws2 (silu(Ws1 h) * Ws3 h)`` is added ungated.

What the published config does not state is one value each in the
configuration's ``program`` (``weights.ASSUMED``).

**Controls** (all made in the reference only): the dense decoders' ``shift``
(this model HAS no positions: it cannot be caught, the tolerance file says so),
``droplayer`` (the middle layer, a full one, and its experts), ``dropblock``; and
the equation's own: ``scalardecay`` (each head's log decay replaced by its
channels' mean: the gated delta rule under this model's name), ``nonegeig``
(``beta`` not doubled), ``nogate`` (the full layers' gate left out), ``misroute``
(the experts the router likes least), ``noshared``; and the nearest precision
below each float32 the configuration states: ``state16`` (``S`` rounded to
bfloat16 after every token) and ``bf16router`` (the router's input, rows, logits
and sigmoid rounded to bfloat16, as ``nemotron_h/reference.py`` has it).

**Near-ties.** The eighth and ninth of 320 scores lie closer than the
program's bfloat16 stream resolves in a good share of (row, layer) pairs, and
the other choice is another function, not an error. As ``nemotron_h/reference.py``
has it and for its reasons: a SECOND pass that takes every near-tie (the
eighth and ninth selection scores within ``near_tie``) the other way, a
position's gap the smaller of the two; and two limits under ``run.py``'s one
comparison: the worst position against ``tolerance``, and ONE entry appended
behind a request's positions, the SHARE of the positions this engine's
requests have shown so far whose gap is over ``share_over``, scaled by
``tolerance / share_tolerance``; 0 until ``POOL_MIN`` positions are pooled, scaled
by ``n / POOL_FULL`` below ``POOL_FULL``.
"""

import functools
import json
import os

import numpy as np

from reference import BLOCK_Q, _dequant, _planes, _rms_norm, control_handles, head_gaps, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("scalardecay", "nonegeig", "nogate", "misroute", "noshared", "state16", "bf16router")   # made inside a layer
CONTROLS = ("none", "shift", "droplayer", "dropblock") + VARIANTS
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full
LONG_BUCKET = 9216          # sequences past 1,024 positions pad to whole multiples of this, the cell's longest
ATTN_BLOCK = 128            # query rows an attention block: [8, 8, 128, 9216] float32 scores are 302 MB
PAIR_BLOCK = 256            # rows of one expert a block of the routed sum
L2_EPS = 1e-6

KDA_LEAVES = ("wq", "wk", "wv", "conv_w", "a_log", "w_f_down", "w_f_up", "dt_bias", "w_b", "w_g_down", "w_g_up",
              "norm_o", "w_out", "norm_att")
FULL_LEAVES = ("wq", "wk", "wv", "wo", "wg", "norm_att")
MOE_LEAVES = ("norm_ffn", "moe_gate", "moe_bias", "we1", "we2", "we3", "ws1", "ws2", "ws3")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def _l2norm(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def mixer(m: dict, u, lp, variant: str = "none"):
    """Kimi Delta Attention over a whole sequence ``u [T, dim]`` (normed), one
    token after another."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    lin = m["linear_attn_config"]
    H, d, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    qkv = jnp.concatenate([u @ _dequant(lp[n]) for n in ("wq", "wk", "wv")], axis=1)
    f32 = lambda n: lp[n].astype(jnp.float32)
    f = (u @ f32("w_f_down").T) @ f32("w_f_up").T                      # [T, H d]
    b = u @ f32("w_b").T                                               # [T, H]
    z = (u @ f32("w_g_down").T) @ f32("w_g_up").T                      # [T, H d]
    seq = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), jnp.float32), qkv], axis=0)
    taps = f32("conv_w")
    y = jax.nn.silu(sum(taps[j] * seq[j:j + T] for j in range(K)))
    q = _l2norm(y[:, :H * d].reshape(T, H, d)) / jnp.sqrt(jnp.float32(d))
    k = _l2norm(y[:, H * d:2 * H * d].reshape(T, H, d))
    v = y[:, 2 * H * d:].reshape(T, H, d)
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(f.reshape(T, H, d) + lp["dt_bias"].reshape(H, d))
    if variant == "scalardecay":
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    alpha = jnp.exp(g)                                                 # [T, H, d]: a decay a key channel
    beta = jax.nn.sigmoid(b) * (2.0 if m["kda_allow_neg_eigval"] and variant != "nonegeig" else 1.0)

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, :, None] * S                                        # Diag(alpha_t) S_{t-1}
        delta = b_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * S, axis=1))
        S = S + k_t[:, :, None] * delta[:, None, :]
        if variant == "state16":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)   # a convert pair may be elided
        return S, jnp.sum(q_t[:, :, None] * S, axis=1)                 # S_t^T q_t

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v, alpha, beta))
    gated = _rms_norm(o, lp["norm_o"], float(m["norm_epsilon"])) * jax.nn.sigmoid(z.reshape(T, H, d))
    return gated.reshape(T, H * d) @ _dequant(lp["w_out"])


def attention(m: dict, u, lp, hide, variant: str = "none"):
    """Causal grouped-query softmax attention over ``u [T, dim]`` (normed), no
    positions, gated a lane, in blocks of :data:`ATTN_BLOCK` query rows. ``hide =
    (from_row, lo, hi)``: query rows >= from_row do not see keys lo..hi-1."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (u @ _dequant(lp["wq"])).reshape(T, Hq, hd)
    k = (u @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (u @ _dequant(lp["wv"])).reshape(T, KV, hd)
    nb = T // ATTN_BLOCK
    qg = q.reshape(nb, ATTN_BLOCK, KV, Hq // KV, hd)
    key_pos = jnp.arange(T)

    def block(args):
        qb, b = args
        scores = jnp.einsum("tkmh,skh->kmts", qb, k) / jnp.sqrt(jnp.float32(hd))
        q_pos = b * ATTN_BLOCK + jnp.arange(ATTN_BLOCK)
        seen = key_pos[None, :] <= q_pos[:, None]
        lost = (q_pos[:, None] >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
        scores = jnp.where((seen & ~lost)[None, None, :, :], scores, -jnp.inf)
        return jnp.einsum("kmts,skh->tkmh", jax.nn.softmax(scores, axis=-1), v)

    att = jax.lax.map(block, (qg, jnp.arange(nb))).reshape(T, Hq * hd)
    if variant != "nogate":
        att = att * jax.nn.sigmoid(u @ _dequant(lp["wg"]))
    return att @ _dequant(lp["wo"])


def route(m: dict, h, gate, bias, variant: str, ties: bool, near_tie: float):
    """``(weights [T, k], experts [T, k])`` over the router's whole width.
    ``ties``: a row whose k-th and (k+1)-th selection scores lie within
    ``near_tie`` takes the (k+1)-th."""
    import jax
    import jax.numpy as jnp

    k = m["num_experts_per_tok"]
    gate = gate.astype(jnp.float32)
    if variant == "bf16router":
        round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        s = round16(jax.nn.sigmoid(round16(round16(h) @ round16(gate).T)))
    else:
        s = jax.nn.sigmoid(h @ gate.T)                                 # [T, router_width]
    chosen_by = s + bias.astype(jnp.float32)
    if variant == "misroute":
        chosen_by = -chosen_by
    best, idx = jax.lax.top_k(chosen_by, k + 1)
    near = (best[:, k - 1] - best[:, k] < near_tie) & ties
    idx = idx[:, :k].at[:, k - 1].set(jnp.where(near, idx[:, k], idx[:, k - 1]))
    top = jnp.take_along_axis(s, idx, axis=-1)
    if m["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    return top * m["routed_scaling_factor"], idx


def routed_sum(m: dict, h, weights, idx, stacks, l):
    """``sum_{j: idx[t, j] held} weights[t, j] Expert_{idx[t, j]}(h_t)``, the
    chosen HELD experts alone (module docstring): pairs sorted by held expert,
    the absent ones last, a run padded to whole blocks, one scan over the
    blocks. ``stacks[name]`` are the ``[L, held, in, out]`` planes, read at ``[l,
    e]`` where they lie."""
    import jax
    import jax.numpy as jnp

    T, k = idx.shape
    E, first, Bk = m["n_routed_experts"], m["first_expert"], PAIR_BLOCK
    local = idx.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < E), local, E)              # E: not held here
    order = jnp.argsort(local, stable=True)                              # pairs by held expert, the absent last
    counts = jnp.bincount(local, length=E + 1)[:E]
    blocks = (counts + Bk - 1) // Bk                                     # blocks a held expert
    first_block = jnp.cumsum(blocks) - blocks
    first_pair = jnp.cumsum(counts) - counts
    n_blocks = (T * k) // Bk + E                                         # static bound: every pair, each run's padding
    b = jnp.arange(n_blocks)
    owner = jnp.clip(jnp.searchsorted(jnp.cumsum(blocks), b, side="right"), 0, E - 1)
    within = (b - first_block[owner])[:, None] * Bk + jnp.arange(Bk)[None, :]          # [n_blocks, Bk]
    real = (within < counts[owner][:, None]) & (b < jnp.sum(blocks))[:, None]
    pair = order[jnp.clip(first_pair[owner][:, None] + within, 0, T * k - 1)]
    rows, w = pair // k, jnp.where(real, weights.reshape(-1)[pair], 0.0)

    def block(y, xs):
        e, rows_b, w_b = xs
        one = lambda name: _dequant(jax.tree.map(lambda a: a[l, e], stacks[name]))
        x = h[rows_b]
        out = (jax.nn.silu(x @ one("we1")) * (x @ one("we3"))) @ one("we2")
        return y.at[rows_b].add(out * w_b[:, None]), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h), (owner, rows, w))
    return y


def routed_block(m: dict, x, stacks, l, variant: str, ties: bool, near_tie: float):
    """``MoE_l(rmsnorm(x; w_l^ffn))``; ``stacks`` are the routed leaves over the
    model's layers."""
    import jax

    lp = {n: jax.tree.map(lambda a: a[l], stacks[n]) for n in ("norm_ffn", "moe_gate", "moe_bias", "ws1", "ws2", "ws3")}
    h = _rms_norm(x, lp["norm_ffn"], float(m["norm_epsilon"]))
    weights, idx = route(m, h, lp["moe_gate"], lp["moe_bias"], variant, ties, near_tie)
    out = routed_sum(m, h, weights, idx, stacks, l)
    if variant == "noshared":
        return out
    return out + (jax.nn.silu(h @ _dequant(lp["ws1"])) * (h @ _dequant(lp["ws3"]))) @ _dequant(lp["ws2"])


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str, ties: bool, near_tie: float):
    """The three stacks in their pattern, with ``reference.layers_program``'s
    signature: ``(tokens[T], embedding, layers, keep[L], shift, shift_from,
    hide) -> x[T, dim]``; ``layers`` is ``{"kda", "full", "moe"}``, ``keep`` runs
    over the layers in the model's order; ``shift`` and ``shift_from`` are taken
    and not read (no layer here reads a position). ONE scan over the periods,
    a period's layers (the full one, then the delta-rule ones) written out."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps, P = float(m["norm_epsilon"]), m["gqa_interval"] + 1

    def run(tokens, embedding, layers, keep, _shift, _shift_from, hide):
        x = embedding[tokens].astype(jnp.float32)
        n_periods = keep.shape[0] // P
        by_period = lambda a: a.reshape((n_periods, a.shape[0] // n_periods) + a.shape[1:])

        def layer(x, l, keep_l, norm_w, mix):
            h = x + keep_l * mix(_rms_norm(x, norm_w, eps))
            return h + keep_l * routed_block(m, h, layers["moe"], l, variant, ties, near_tie)

        def body(x, xs):
            p, kda_p, full_p, keep_p = xs
            x = layer(x, p * P, keep_p[0], full_p["norm_att"], lambda u: attention(m, u, full_p, hide, variant))
            for j in range(P - 1):      # the delta-rule layers of one period, not the depth
                lp = jax.tree.map(lambda a: a[j], kda_p)
                x = layer(x, p * P + 1 + j, keep_p[1 + j], lp["norm_att"], lambda u, lp=lp: mixer(m, u, lp, variant))
            return x, None

        x, _ = jax.lax.scan(body, x, (jnp.arange(n_periods), jax.tree.map(by_period, layers["kda"]),
                                      layers["full"], by_period(keep)))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def layer_tree(params) -> dict:
    lp = params.layers
    return {"kda": {n: _planes(getattr(lp.kda, n)) for n in KDA_LEAVES},
            "full": {n: _planes(getattr(lp.full, n)) for n in FULL_LEAVES},
            "moe": {n: _planes(getattr(lp.moe, n)) for n in MOE_LEAVES}}


def padded_length(n: int) -> int:
    """Positions a sequence of ``n`` is computed at: whole attention blocks of
    512 up to 1,024 positions, whole multiples of :data:`LONG_BUCKET` past them
    (ONE program for the cell's requests: ``granite_hybrid/reference.py`` says
    what a program a length cost). Padding lies BEHIND the sequence: no real
    position attends to it or carries a state from it."""
    bucket = BLOCK_Q if n <= 2 * BLOCK_Q else LONG_BUCKET
    return -(-n // bucket) * bucket


def _stack_output(model: dict, params, seq, n_prompt: int, control: str, variant: str, ties: bool, near_tie: float):
    import jax.numpy as jnp

    T = padded_length(len(seq))
    tokens = np.zeros(T, dtype=np.int32)
    tokens[:len(seq)] = seq
    return _layers_fn(json.dumps(model, sort_keys=True), variant, ties, near_tie)(
        jnp.asarray(tokens), params.embedding, layer_tree(params),
        *control_handles(model["num_hidden_layers"], n_prompt, T, control))


def reference_logits(model: dict, params, tokens, variant: str = "none") -> np.ndarray:
    """Float32 logits ``[T, vocab]`` of the whole forward pass over ``tokens``:
    what the CPU tests hold the program's logits to. Small sizes only: the head
    is dequantized whole."""
    import jax

    x = _stack_output(model, params, list(tokens), len(tokens), "none", variant, False, 0.0)
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x[:len(tokens)], params.final_norm, float(model["norm_epsilon"]))
        return np.asarray(h @ _dequant(_planes(params.logits)))


def _forced(model: dict, params, prompt, emitted, control: str, ties: bool, near_tie: float) -> dict:
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    variant = control if control in VARIANTS else "none"
    x = _stack_output(model, params, list(prompt) + list(emitted[:-1]), len(prompt), control, variant, ties, near_tie)
    return head_gaps(model, params, x, len(prompt), emitted)


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
