"""The plain reference of a decoder whose every layer is an SSD (Mamba-2) mixer
or grouped-query attention without positions, THEN gated routed experts beside
a gated shared one, under four scalar multipliers and a tied head. The
``reference`` module of ``granite-4.0-h-small`` (``granite_hybrid/README.md``).

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, no chunk form, nothing imported from ``dllama_tpu``; it
reads the same planes the engine holds and dequantizes one layer (one expert)
at a time.

**The equations** (published ``config.json`` keys in quotes; ``r`` =
``residual_multiplier``, RMS norms at ``rms_norm_eps``)::

    x_0 = embedding_multiplier * E[token]
    u = rmsnorm(x; w_l^in);    x <- x + r * Mixer_l(u)
    v = rmsnorm(x; w_l^post);  x <- x + r * (Routed_l(v) + Shared_l(v))
    logits = (E rmsnorm(x; w^final)) / logits_scaling          # the head IS E

* ``layer_types[l] == "mamba"``: ``[z | xBC | dt] = W_in u``; ``xBC = silu(causal_conv(xBC)
  + conv_bias)`` over the WHOLE sequence with zeros in front; ``x_, B, C =
  split(xBC)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per head, ONE
  TOKEN AFTER ANOTHER (a scan, the state float32): ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T`` (``S_0 = 0``), ``y_t = S_t C_t + D x_t``; ``W_out rmsnorm(y *
  silu(z); w^ssm)`` over each of the ``mamba_n_groups`` groups' lanes (ONE group:
  all 8192). The program holds ``W_in`` as two planes (the ``z x B C`` rows, Q40;
  the ``dt`` rows, float32): the reference joins them back.
* ``"attention"``: ``q, k, v = W_q u, W_k u, W_v u``, causal softmax of ``q . k *
  attention_multiplier`` over a dense mask in blocks of 256 query rows, query
  head ``j`` on K/V head ``floor(j / G)``, ``W_o``. NO positional embedding.
* ``Routed_l(v)``: ``s = W_r v`` (float32); the ``num_experts_per_tok`` LARGEST
  LOGITS; gates = softmax over THOSE logits (top-k, then softmax: the published
  order; the program takes a softmax over all 72 and renormalises the chosen,
  which is the same function); an expert is ``W_o^e (silu(W_a^e v) * W_b^e v)``.
  ONLY THE CHOSEN EXPERTS ARE COMPUTED: the (row, expert) pairs are sorted by
  expert, each expert's run padded to whole blocks of ``PAIR_BLOCK`` rows, and a
  scan over the blocks dequantizes the ONE expert a block belongs to and
  multiplies its rows: 10 / 72 of every-expert-over-every-row, exact (no
  capacity: every pair has a place), so that six requests of up to 8.6k
  positions end inside a window's length.
* ``Shared_l(v) = W_so (silu(W_sa v) * W_sb v)``.

What the published config does not state is one value each in the
configuration's ``program`` (``weights.ASSUMED``): no clamp on ``dt``; the ``z x B C
dt`` order of the in-projection; the gate before the grouped norm; the fused
``input_linear``'s FIRST half under ``silu``; top-k then softmax; heads of 128.

**Controls** (all made in the reference only): the dense decoders' ``shift``
(this model HAS no positions: it cannot be caught, the tolerance file says so),
``droplayer`` (published layer 5 of 10, the attention layer, and its experts),
``dropblock``; and this equation's own: ``noresmult`` (``r`` left at 1), ``noembmult``
(the embedding's multiplier left at 1), ``sqrtscale`` (scores times ``head_dim **
-0.5``), ``nologitscale`` (``logits_scaling`` left out: the gap is a ratio of logits,
so it cannot be caught; the CPU tests hold the logits themselves), ``rope`` (a
rotary embedding at ``rope_theta``, half-split, in the attention layers),
``bf16state`` (``S`` rounded to bfloat16 after every token), ``bf16router`` (the
router's input, rows and logits rounded to bfloat16), ``softmaxall`` (gates the
softmax over all 72 WITHOUT renormalising the chosen), ``misroute`` (the experts
the router likes least), ``noshared``, ``dropstate`` (the state zeroed at every
256th position), ``secondhalf`` (``silu`` on the fused ``input_linear``'s second
half).
"""

import functools
import json
import os

import numpy as np

from reference import BLOCK_Q, _dequant, _planes, _rms_norm, _rope, control_handles, head_gaps, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("noresmult", "noembmult", "sqrtscale", "nologitscale", "rope", "bf16state", "bf16router", "softmaxall",
            "misroute", "noshared", "dropstate", "secondhalf")      # made inside the stack
CONTROLS = ("none", "shift", "droplayer", "dropblock") + VARIANTS
LOST_CARRY_EVERY = 256      # dropstate: the program's widest prefill chunk
LONG_BUCKET = 8704          # sequences past 1,024 positions pad to whole multiples of this, the cell's context
ATTN_BLOCK = 256            # query rows an attention block: [8, 4, 256, 8704] float32 scores are 285 MB
PAIR_BLOCK = 256            # rows of one expert a block of the routed sum

MIXER_LEAVES = ("w_in", "w_dt", "conv_w", "conv_b", "a_log", "d_skip", "dt_bias", "norm_ssm", "w_out", "norm")
ATTN_LEAVES = ("wq", "wk", "wv", "wo", "norm")
ROUTED_LEAVES = ("norm_moe", "moe_gate", "we1", "we2", "we3", "ws1", "ws2", "ws3")


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def _round16(a):
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)   # a convert pair may be elided


def mixer(m: dict, u, lp, variant: str = "none"):
    """The SSD mixer over a whole sequence ``u [T, dim]``, one token after
    another."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    H, P, G, N, K = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_n_groups"], m["mamba_d_state"],
                     m["mamba_d_conv"])
    d_ssm, gn = H * P, G * N
    # W_in as published, [dim, d_ssm + (d_ssm + 2 G N) + H]: z, xBC, dt
    proj = u @ jnp.concatenate([_dequant(lp["w_in"]), lp["w_dt"].astype(jnp.float32).T], axis=1)
    z, xbc, dt = proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * gn], proj[:, 2 * d_ssm + 2 * gn:]
    seq = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    taps = lp["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(taps[j] * seq[j:j + T] for j in range(K)) + lp["conv_b"])
    x = xbc[:, :d_ssm].reshape(T, H, P)
    Bm, Cm = xbc[:, d_ssm:d_ssm + gn].reshape(T, G, N), xbc[:, d_ssm + gn:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    decay = jnp.exp(dt * -jnp.exp(lp["a_log"]))
    t = jnp.arange(T)
    lost = (t % LOST_CARRY_EVERY == 0) & (t > 0) & (variant == "dropstate")
    per_head = lambda g: jnp.repeat(g, H // G, axis=0)        # a group's B or C row for each of its heads

    def token(S, xs):
        x_t, dt_t, a_t, b_t, c_t, lost_t = xs
        S = jnp.where(lost_t, 0.0, S)
        S = a_t[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * per_head(b_t)[:, None, :]
        if variant == "bf16state":
            S = _round16(S)
        return S, jnp.sum(S * per_head(c_t)[:, None, :], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (x, dt, decay, Bm, Cm, lost))
    y = (y + lp["d_skip"][:, None] * x).reshape(T, d_ssm) * jax.nn.silu(z)
    grouped = y.reshape(T, G, d_ssm // G)
    normed = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, axis=-1, keepdims=True) + float(m["norm_epsilon"]))
    return (normed.reshape(T, d_ssm) * lp["norm_ssm"]) @ _dequant(lp["w_out"])


def attention(m: dict, u, lp, positions, hide, variant: str = "none"):
    """Causal grouped-query softmax attention over ``u [T, dim]`` at the STATED
    score scale, in blocks of :data:`ATTN_BLOCK` query rows. ``hide = (from_row,
    lo, hi)``: query rows >= from_row do not see keys lo..hi-1."""
    import jax
    import jax.numpy as jnp

    T = u.shape[0]
    Hq, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    scale = hd ** -0.5 if variant == "sqrtscale" else float(m["attention_multiplier"])
    q = (u @ _dequant(lp["wq"])).reshape(T, Hq, hd)
    k = (u @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (u @ _dequant(lp["wv"])).reshape(T, KV, hd)
    if variant == "rope":
        theta = float(m["rope_theta"])
        q, k = _rope(q, positions, theta, "half_split"), _rope(k, positions, theta, "half_split")
    nb = T // ATTN_BLOCK
    qg = q.reshape(nb, ATTN_BLOCK, KV, Hq // KV, hd)
    key_pos = jnp.arange(T)

    def block(args):
        qb, b = args
        scores = jnp.einsum("tkmh,skh->kmts", qb, k) * scale
        q_pos = b * ATTN_BLOCK + jnp.arange(ATTN_BLOCK)
        seen = key_pos[None, :] <= q_pos[:, None]
        lost = (q_pos[:, None] >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
        scores = jnp.where((seen & ~lost)[None, None, :, :], scores, -jnp.inf)
        return jnp.einsum("kmts,skh->tkmh", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (qg, jnp.arange(nb)))
    return out.reshape(T, Hq * hd) @ _dequant(lp["wo"])


def route(m: dict, h, gate, variant: str = "none"):
    """``(gates [T, k], experts [T, k])`` as published: the ``k`` largest logits,
    then a softmax over those."""
    import jax
    import jax.numpy as jnp

    k = m["num_experts_per_tok"]
    gate = gate.astype(jnp.float32)
    logits = _round16(_round16(h) @ _round16(gate).T) if variant == "bf16router" else h @ gate.T
    top, idx = jax.lax.top_k(-logits if variant == "misroute" else logits, k)
    top = jnp.take_along_axis(logits, idx, axis=-1)
    if variant == "softmaxall":
        return jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1), idx
    return jax.nn.softmax(top, axis=-1), idx


def routed_sum(m: dict, h, gates, idx, stacks, l, variant: str = "none"):
    """``sum_j gates[t, j] Expert_{idx[t, j]}(h_t)``, the chosen experts alone
    (module docstring): pairs sorted by expert, a run padded to whole blocks,
    one scan over the blocks. ``stacks[name]`` are the ``[L, E, in, out]`` planes,
    read at ``[l, e]`` where they lie: no layer's 72 experts are copied out."""
    import jax
    import jax.numpy as jnp

    T, k = idx.shape
    E, Bk = m["num_local_experts"], PAIR_BLOCK
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)                       # pairs by expert
    counts = jnp.bincount(flat, length=E)
    blocks = (counts + Bk - 1) // Bk                             # blocks an expert
    first_block = jnp.cumsum(blocks) - blocks
    first_pair = jnp.cumsum(counts) - counts
    n_blocks = (T * k) // Bk + E                                 # static bound: every pair and each run's padding
    b = jnp.arange(n_blocks)
    owner = jnp.clip(jnp.searchsorted(jnp.cumsum(blocks), b, side="right"), 0, E - 1)
    within = (b - first_block[owner])[:, None] * Bk + jnp.arange(Bk)[None, :]          # [n_blocks, Bk]
    real = (within < counts[owner][:, None]) & (b < jnp.sum(blocks))[:, None]
    pair = order[jnp.clip(first_pair[owner][:, None] + within, 0, T * k - 1)]
    rows, w = pair // k, jnp.where(real, gates.reshape(-1)[pair], 0.0)
    first, second = ("we3", "we1") if variant == "secondhalf" else ("we1", "we3")

    def block(y, xs):
        e, rows_b, w_b = xs
        one = lambda name: _dequant(jax.tree.map(lambda a: a[l, e], stacks[name]))
        x = h[rows_b]
        out = (jax.nn.silu(x @ one(first)) * (x @ one(second))) @ one("we2")
        return y.at[rows_b].add(out * w_b[:, None]), None

    y, _ = jax.lax.scan(block, jnp.zeros_like(h), (owner, rows, w))
    return y


def routed_block(m: dict, x, stacks, l, variant: str = "none"):
    """``Routed_l(v) + Shared_l(v)`` for ``v = rmsnorm(x; w_l^post)``; ``stacks`` are
    the routed leaves over the layers."""
    import jax

    lp = {n: jax.tree.map(lambda a: a[l], stacks[n]) for n in ("norm_moe", "moe_gate", "ws1", "ws2", "ws3")}
    h = _rms_norm(x, lp["norm_moe"], float(m["norm_epsilon"]))
    gates, idx = route(m, h, lp["moe_gate"], variant)
    out = routed_sum(m, h, gates, idx, stacks, l, variant)
    if variant == "noshared":
        return out
    first, second = ("ws3", "ws1") if variant == "secondhalf" else ("ws1", "ws3")
    return out + (jax.nn.silu(h @ _dequant(lp[first])) * (h @ _dequant(lp[second]))) @ _dequant(lp["ws2"])


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str, variant: str):
    """The stack with ``reference.layers_program``'s signature: ``(tokens[T],
    embedding, layers, keep[L], shift, shift_from, hide) -> x[T, dim]``;
    ``layers`` is ``{"mixer", "attn", "routed"}``, three stacks each over its own
    layers, ``keep`` runs over the PUBLISHED layers. ONE scan over them, the
    mixer half chosen by the layer's type."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    kinds = [("mamba", "attention").index(kind) for kind in m["layer_types"]]
    own = [kinds[:l].count(kind) for l, kind in enumerate(kinds)]        # a layer's index in its kind's stack
    r = 1.0 if variant == "noresmult" else float(m["residual_multiplier"])
    emb_mult = 1.0 if variant == "noembmult" else float(m["embedding_multiplier"])

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32) * emb_mult
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

        def mixer_half(u, i):
            lp = at(layers["mixer"], i)
            return mixer(m, _rms_norm(u, lp["norm"], eps), lp, variant)

        def attn_half(u, i):
            lp = at(layers["attn"], i)
            return attention(m, _rms_norm(u, lp["norm"], eps), lp, positions, hide, variant)

        def layer(x, xs):
            l, kind, i = xs
            x = x + keep[l] * r * jax.lax.switch(kind, [mixer_half, attn_half], x, i)
            return x + keep[l] * r * routed_block(m, x, layers["routed"], l, variant), None

        x, _ = jax.lax.scan(layer, x, (jnp.arange(len(kinds)), jnp.asarray(kinds, jnp.int32),
                                       jnp.asarray(own, jnp.int32)))
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


def reference_logits(model: dict, params, tokens, variant: str = "none") -> np.ndarray:
    """Float32 logits ``[T, vocab]`` of the whole forward pass over ``tokens``:
    what the CPU tests hold the program's logits to. Small sizes only: the head
    is read whole."""
    import jax
    import jax.numpy as jnp

    T = -(-len(tokens) // BLOCK_Q) * BLOCK_Q
    padded = np.zeros(T, dtype=np.int32)
    padded[:len(tokens)] = tokens
    x = _layers_fn(json.dumps(model, sort_keys=True), variant)(
        jnp.asarray(padded), params.embedding, layer_tree(params),
        *control_handles(model["num_hidden_layers"], len(tokens), T, "none"))
    scale = 1.0 if variant == "nologitscale" else 1.0 / float(model["logits_scaling"])
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x[:len(tokens)], params.final_norm, float(model["norm_epsilon"]))
        return np.asarray(h @ _dequant(params.embedding)) * scale          # the head IS the embedding


def padded_length(n: int) -> int:
    """Positions a sequence of ``n`` is computed at. ``reference.teacher_force``
    pads to whole attention blocks of 512, a program a length: four checked
    requests of 2k-8.6k positions were four compilations of 15-20 s each on the
    chip, 83 s of check behind a 45 s window (PERF.md, PR 54). Past 1,024
    positions a sequence pads to whole multiples of :data:`LONG_BUCKET` instead:
    ONE program for the cell's requests, 2.7 s a request at 8,704 positions
    where the longest took 2.6 and a mean one 1.6. Padding lies BEHIND the
    sequence: no real position attends to it or carries a state from it."""
    bucket = BLOCK_Q if n <= 2 * BLOCK_Q else LONG_BUCKET
    return -(-n // bucket) * bucket


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none") -> dict:
    """``reference.teacher_force`` over this stack at :func:`padded_length`. The
    gap is a ratio of logit differences, so ``logits_scaling`` drops out of it
    (``nologitscale`` reads what the honest run reads); ``reference.head_gaps``
    reads ``params.logits``, which IS ``params.embedding``."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    variant = control if control in VARIANTS else "none"
    seq = list(prompt) + list(emitted[:-1])
    T = padded_length(len(seq))
    tokens = np.zeros(T, dtype=np.int32)
    tokens[:len(seq)] = seq
    x = _layers_fn(json.dumps(model, sort_keys=True), variant)(
        jnp.asarray(tokens), params.embedding, layer_tree(params),
        *control_handles(model["num_hidden_layers"], len(prompt), T, control))
    return head_gaps(model, params, x, len(prompt), emitted)
