"""The plain reference of a decoder of latent attention (MLA) layers with a
sigmoid group-limited router over experts of which a SHARE is held. The
``reference`` module of ``a.x-k1`` (README, "A layer equation").

Float32 under ``jax.default_matmul_precision("highest")``, no cache, no
batching, no kernels, nothing of ``dllama_tpu`` in its equations (the pool
check alone finds the serving generator, "Three limits"); it reads the same
planes the engine holds and dequantizes one layer at a time. UNABSORBED:
per-head keys and values are expanded from the latent for every position,
which is what the program never does.

**The equations.** Layer ``l``, input ``x`` (``hidden_size`` wide), ``H`` heads::

    h = rmsnorm(x; w_in);  x = x + MLA(h);  g = rmsnorm(x; w_ff);  x = x + FFN_l(g)

    MLA:  c_q = rmsnorm(W_dq h; w_qa)                                    [q_lora_rank]
          [q_n | q_r]_i = (W_uq c_q)_i     head i: qk_nope_head_dim | qk_rope_head_dim;  q_r = rope(q_r)
          [c | k_r] = W_dkv h              kv_lora_rank | qk_rope_head_dim;  c = rmsnorm(c; w_kva);  k_r = rope(k_r)
          [k_n | v]_i,j = (W_ukv c_j)_i    head i of position j: qk_nope_head_dim | v_head_dim
          s_ij = (q_n,i . k_n,j + q_r,i . k_r,j) * scale      ONE k_r a position, shared by the heads
          o_i = sum_j softmax_j(s_ij) v_j;   out = W_o concat_heads(o)
    rope: half-split pairing (lane j with j + r/2) over the r = qk_rope_head_dim lanes, YaRN: e_i = theta^(-2i/r),
          dim(n) = r ln(orig / (2 pi n)) / (2 ln theta), low = max(floor(dim(beta_fast)), 0), high =
          min(ceil(dim(beta_slow)), r - 1), ramp_i = clip((i - low) / (high - low), 0, 1), inv_freq_i = (e_i /
          factor) ramp_i + e_i (1 - ramp_i); cos and sin times m(mscale) / m(mscale_all_dim), m(a) = 0.1 a ln(factor)
          + 1; scale = (nope + rope)^-0.5 * m(mscale_all_dim)^2, on the WHOLE score.
    FFN_l, l < first_k_dense_replace: W_down(silu(W_gate g) * W_up g).
    every other: s = sigmoid(W_r g) in float32 over all router_width; the experts in n_group groups; a group's score
          the sum of its num_experts_per_tok / topk_group largest s; the topk_group best groups; among their experts
          the num_experts_per_tok largest s (ties to the lower index, as lax.top_k breaks them); w = s / sum(chosen
          s) * routed_scaling_factor; y = sum_{chosen, held} w_e E_e(g) + S(g), E_e and S SwiGLU, S ungated.
    after the last layer rmsnorm, then the head (untied).

**The share.** The planes hold ``n_routed_experts`` experts, ``first_expert ..``
of the ``router_width`` the router scores; a chosen expert that is not held adds
nothing, here as in the program. Fewer rows of the vocabulary are a smaller head.

**What a check costs, and what is done about it.** The post-window check
teacher-forces requests of 5k-17k positions. Fair savings, all taken: the
attention walks the queries in blocks of ``BLOCK_Q`` and the heads in groups of
``HEAD_GROUP`` (a score block is ``HEAD_GROUP x BLOCK_Q x T`` float32, the expanded
keys and values of one group ``T x HEAD_GROUP x 256``); the wide matmuls walk the
rows in blocks of ``ROW_BLOCK``; a held expert is computed for the rows that CHOSE
it only (gathered up to a cap of an eighth of the rows, three times the mean;
past the cap the expert is computed for every row: a ``lax.cond``, never a
dropped row); the head runs at the scored positions only (``reference.py``'s).
Not taken, because it would not be this model: leaving out a layer, a position's
latent or a term.

**Departures from the published model, each deliberate:** weights are random
from the seed (``weights.py`` beside this file). What the published config does
not state is taken from the public ``axk1`` / DeepSeek-V3-style implementation
and is one value each in the configuration's ``program``, read HERE from the
model so that a correction is one line there and one branch here:
``norm_placement`` pre; ``latent_norms`` (an RMS norm on ``c_q`` and on ``c``);
``shared_expert_gate`` false; ``rope_pairing`` half_split (the published
checkpoints pair interleaved lanes and permute: the same scores, a converter
permutes the rows); ``router_bias`` none (``topk_method: "none"`` read as "no
score-correction bias"); ``router_group_score`` sum_of_top_2; the attention
scale's formula. ``seq_aux`` and ``ep_size`` say nothing of the forward pass.

**Controls** (all made in the reference only): the dense decoders' ``shift``,
``droplayer``, ``dropblock`` (a latent block lost: 16 prompt positions hidden from
the emitted rows), and this equation's own: ``nogroups`` (the plain top 8 of
192), ``bf16router`` (the router's input, rows, logits and sigmoid rounded to
bfloat16 with ``lax.reduce_precision``, which XLA does not elide), ``nomscale``
(``scale`` without ``m(mscale_all_dim)^2``), ``norope`` (``k_r`` left unrotated),
``nocnorm`` (the norm on ``c`` dropped), ``noshared`` (the shared expert left out),
``latent8`` (the cached ``c`` and ``k_r`` rounded to 8 bits, 4 of exponent and 3 of
mantissa: the nearest precision below the bfloat16 the configuration states
for the pool; it moves a logit by some 0.03 standard deviations, under the
floor that bfloat16 compute and expert flips set, so the emitted tokens do
not show it: the pool check below does).

**Three limits, one comparison**, as ``laguna/reference.py`` carries its
second: a routed model flips an expert at a near-tie in some layer of some
rows, which is another function and not an error, so beside ``tolerance`` for
the worst position every call appends the SHARE of the positions pooled so far
whose gap is over ``share_over``, scaled so that the same comparison holds it
to ``share_tolerance``. And the emitted tokens cannot tell a pool of bfloat16
rows from one of 8-bit rows, so every call also appends THE POOL CHECK: the
rows ``[c | k_r]`` that the server's latent pool holds for the request's
prompt (found through the prefix index of the generator that serves these
params, read back after the request has finished) against the rows these
equations give, as the lower quartile over rows and layers of ``|held - ref|
/ |ref|``, scaled so that the same comparison holds it to ``pool_tolerance``. It
reads the program's state, so it is tied to the pool's layout (``pkv.k [L,
blocks, 1, block_size, lanes]``, the first ``kv_lora_rank + qk_rope_head_dim``
lanes a row): a change that moves the pool owes this function the new
layout. ``gap_tolerance.json`` has the numbers and the readings of all three.
"""

import functools
import json
import math
import os

import numpy as np

from reference import BLOCK_Q, _dequant, _planes, _rms_norm, swiglu, teacher_force, tolerance_from

_HERE = os.path.dirname(os.path.abspath(__file__))
_TOLERANCES = os.path.join(_HERE, "gap_tolerance.json")
VARIANTS = ("nogroups", "bf16router", "nomscale", "norope", "nocnorm", "noshared", "latent8")
CONTROLS = ("none", "shift", "droplayer", "dropblock") + VARIANTS
POOL_MIN, POOL_FULL = 96, 250    # positions pooled before the share counts at all, and in full
HEAD_GROUP = 16                  # heads whose keys and values are expanded at once
ROW_BLOCK = 2048                 # rows a wide matmul takes at once

ATTN_LEAVES = ("wdq", "norm_qa", "wuq", "wdkv", "norm_kva", "wuk", "wuv", "wo", "norm_att")
DENSE_LEAVES = ("w1", "w2", "w3")
ROUTED_LEAVES = ("moe_gate", "we1", "we2", "we3", "ws1", "ws2", "ws3")


def _limits() -> dict:
    with open(_TOLERANCES, encoding="utf-8") as f:
        return json.load(f)


def tolerance(compute_dtype: str) -> float:
    return tolerance_from(_TOLERANCES, compute_dtype)


def mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(m: dict):
    """``(inv_freq [r/2], table scale)`` of the rotary table over the rope lanes."""
    rs, r = m["rope_scaling"], m["qk_rope_head_dim"]
    theta = float(m["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / r)
    dim = lambda n: r * math.log(rs["original_max_position_embeddings"] / (2 * math.pi * n)) / (2 * math.log(theta))
    low, high = max(math.floor(dim(rs["beta_fast"])), 0), min(math.ceil(dim(rs["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((e / rs["factor"]) * ramp + e * (1.0 - ramp),
            mscale(rs["factor"], rs["mscale"]) / mscale(rs["factor"], rs["mscale_all_dim"]))


def score_scale(m: dict, variant: str) -> float:
    rs = m["rope_scaling"]
    scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    return scale if variant == "nomscale" else scale * mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope(x, positions, inv, scale):
    """Rotate ``x [T, heads, r]`` whole, lane ``j`` paired with lane ``j + r/2``."""
    import jax.numpy as jnp

    half = len(inv)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    c, s = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x0, x1 = x[..., :half], x[..., half:]
    return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)


def by_rows(fn, x):
    """``fn`` over ``x [T, ...]`` in blocks of ROW_BLOCK rows (T pads to BLOCK_Q, which divides it or is it)."""
    import jax

    T = x.shape[0]
    block = next(b for b in (ROW_BLOCK, BLOCK_Q, T) if T % b == 0)
    if block == T:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(T // block, block, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def attention_half(m: dict, x, lp, positions, hide, variant: str):
    """One layer's latent attention over the whole sequence, residual added,
    unabsorbed: a dense ``[BLOCK_Q, T]`` mask a head, the heads in groups.
    Also the layer's cached rows ``[c | k_r]`` as the equations give them,
    ``[T, kv_lora_rank + qk_rope_head_dim]``: what the pool check holds the
    program's pool to."""
    import jax
    import jax.numpy as jnp

    T, H = x.shape[0], m["num_attention_heads"]
    nope, r, v, kvl = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"]
    eps = float(m["norm_epsilon"])
    h = _rms_norm(x, lp["norm_att"], eps)
    c_q = _rms_norm(by_rows(lambda a: a @ _dequant(lp["wdq"]), h), lp["norm_qa"], eps)
    q = by_rows(lambda a: a @ _dequant(lp["wuq"]), c_q).reshape(T, H, nope + r)
    kv = (h @ _dequant(lp["wdkv"]))[:, :kvl + r]       # the plane's zero columns past 576 are the loader's padding
    c = kv[:, :kvl] if variant == "nocnorm" else _rms_norm(kv[:, :kvl], lp["norm_kva"], eps)
    table = inv_freq(m)
    q_r = rope(q[..., nope:], positions, *table)
    k_r = kv[:, None, kvl:] if variant == "norope" else rope(kv[:, None, kvl:], positions, *table)
    k_r = k_r[:, 0]
    if variant == "latent8":
        round8 = lambda a: jax.lax.reduce_precision(a, exponent_bits=4, mantissa_bits=3)
        c, k_r = round8(c), round8(k_r)
    scale = score_scale(m, variant)
    G = min(HEAD_GROUP, H)
    key_pos = jnp.arange(T)
    wuk, wuv = lp["wuk"].astype(jnp.float32), lp["wuv"].astype(jnp.float32)          # [H, nope | v, kv_lora]

    def group(args):
        q_g, wuk_g, wuv_g = args                                    # [T, G, nope + r], [G, nope, kvl], [G, v, kvl]
        k_n = jnp.einsum("sc,gdc->sgd", c, wuk_g)                   # the expansion the program never makes
        val = jnp.einsum("sc,gvc->sgv", c, wuv_g)

        def block(args):
            qb, b = args
            s = (jnp.einsum("tgd,sgd->gts", qb[..., :nope], k_n) + jnp.einsum("tgr,sr->gts", qb[..., nope:], k_r))
            q_pos = (b * BLOCK_Q + jnp.arange(BLOCK_Q))[:, None]
            seen = key_pos[None, :] <= q_pos
            lost = (q_pos >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
            s = jnp.where((seen & ~lost)[None], s * scale, -jnp.inf)
            return jnp.einsum("gts,sgv->tgv", jax.nn.softmax(s, axis=-1), val)

        qb = q_g.reshape(T // BLOCK_Q, BLOCK_Q, G, nope + r)
        return jax.lax.map(block, (qb, jnp.arange(T // BLOCK_Q))).reshape(T, G, v)

    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    split = lambda a, axis: jnp.moveaxis(a.reshape(*a.shape[:axis], H // G, G, *a.shape[axis + 1:]), axis, 0)
    out = jax.lax.map(group, (split(q, 1), split(wuk, 0), split(wuv, 0)))      # [H / G, T, G, v]
    out = jnp.moveaxis(out, 0, 1).reshape(T, H * v)
    return x + by_rows(lambda a: a @ _dequant(lp["wo"]), out), jnp.concatenate([c, k_r], axis=-1)


def route(m: dict, h, gate, variant: str):
    """``(weights [T, k], experts [T, k])``: the sigmoid group-limited router
    over its whole width in float32."""
    import jax
    import jax.numpy as jnp

    k, G, kg = m["num_experts_per_tok"], m["n_group"], m["topk_group"]
    round16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    low = variant == "bf16router"
    gate = gate.astype(jnp.float32)
    logits = (round16(h) @ round16(gate).T) if low else h @ gate.T
    if low:
        logits = round16(logits)
    s = jax.nn.sigmoid(logits) if m["scoring_func"] == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    if low:
        s = round16(s)
    T, W = s.shape
    if G > 1 and variant != "nogroups":
        per_group = jax.lax.top_k(s.reshape(T, G, W // G), k // kg)[0].sum(axis=-1)
        _, best = jax.lax.top_k(per_group, kg)
        allowed = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], best].set(True)
        top, idx = jax.lax.top_k(jnp.where(jnp.repeat(allowed, W // G, axis=1), s, -jnp.inf), k)
    else:
        top, idx = jax.lax.top_k(s, k)
    if m["norm_topk_prob"]:
        top = top / top.sum(axis=-1, keepdims=True)
    return top * m["routed_scaling_factor"], idx


def routed_ffn(m: dict, h, lp, variant: str):
    """``sum_{e chosen, e held} w_e E_e(h) + S(h)``. A held expert is computed
    for the rows that chose it: gathered up to a cap (an eighth of the rows,
    three times the mean under uniform routing), for every row past the cap."""
    import jax
    import jax.numpy as jnp

    first, held = m["first_expert"], m["n_routed_experts"]
    T = h.shape[0]
    top, idx = route(m, h, lp["moe_gate"], variant)
    # [T, held]: a row's weight for each held expert, 0 where unchosen; an absent expert has no column
    weight = (jax.nn.one_hot(idx - first, held, dtype=jnp.float32) * top[..., None]).sum(axis=-2)
    cap = min(T, -(-max(T // 8, 1) // 128) * 128)

    def expert(y, xs):
        planes, w_e = xs
        chose = w_e > 0

        def some(y):
            # the rows that chose this expert first, in order; the tail is weighted 0
            rows = jnp.argsort(~chose, stable=True)[:cap]
            out = swiglu(h[rows], planes["we1"], planes["we2"], planes["we3"]) * w_e[rows][:, None]
            return y.at[rows].add(out)

        def every(y):
            return y + w_e[:, None] * by_rows(lambda a: swiglu(a, planes["we1"], planes["we2"], planes["we3"]), h)

        return jax.lax.cond(jnp.sum(chose) <= cap, some, every, y), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), ({n: lp[n] for n in ("we1", "we2", "we3")}, weight.T))
    if variant != "noshared":
        y = y + by_rows(lambda a: swiglu(a, lp["ws1"], lp["ws2"], lp["ws3"]), h)
    return y


@functools.lru_cache(maxsize=None)
def _stack_fn(model_key: str, variant: str):
    """The stack: ``(tokens[T], embedding, layers, keep[L], shift, shift_from,
    hide) -> (x[T, dim], rows[L, T, kv_lora_rank + qk_rope_head_dim])``, the
    arguments ``reference.layers_program``'s; ``layers`` is ``{"attn",
    "norm_ffn", "dense", "routed"}``; ``rows`` are every layer's cached rows."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])
    nd = m["first_k_dense_replace"]

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)
        L = keep.shape[0]
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)

        def one(x, l, ffn):
            x1, rows = attention_half(m, x, at(layers["attn"], l), positions, hide, variant)
            y = x1 + ffn(_rms_norm(x1, layers["norm_ffn"][l], eps))
            return x + keep[l] * (y - x), rows

        first = []
        for l in range(nd):
            dense = at(layers["dense"], l)
            x, rows = one(x, l, lambda g: by_rows(lambda a: swiglu(a, dense["w1"], dense["w2"], dense["w3"]), g))
            first.append(rows)

        def routed(x, l):
            return one(x, l, lambda g: routed_ffn(m, g, at(layers["routed"], l - nd), variant))

        x, rest = jax.lax.scan(routed, x, jnp.arange(nd, L))
        return x, jnp.concatenate([jnp.stack(first), rest]) if first else rest

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


def _layers_fn(model_key: str, variant: str):
    """``_stack_fn``'s ``x`` alone: ``reference.layers_program``'s signature."""
    fn = _stack_fn(model_key, variant)
    return lambda *args: fn(*args)[0]


def layer_tree(params) -> dict:
    lp = params.layers
    return {"attn": {n: _planes(getattr(lp, n)) for n in ATTN_LEAVES}, "norm_ffn": lp.norm_ffn,
            "dense": {n: _planes(getattr(lp, n)) for n in DENSE_LEAVES},
            "routed": {n: _planes(getattr(lp, n)) for n in ROUTED_LEAVES}}


_pool = {"of": None, "gaps": []}    # the gaps one engine's requests have shown under one control


def pooled_share_entry(params, control: str, gap, compute_dtype: str) -> float:
    """The share of pooled positions over ``share_over`` as the one extra
    entry of ``gap`` (``laguna/reference.py``, "Two limits"; here "Three limits"). A pool belongs to
    one ``params`` object and one control."""
    if _pool["of"] is None or _pool["of"][0] is not params or _pool["of"][1] != control:
        _pool.update(of=(params, control), gaps=[])
    _pool["gaps"].append(np.asarray(gap, dtype=np.float64))
    pooled = np.concatenate(_pool["gaps"])
    if len(pooled) < POOL_MIN:
        return 0.0
    lim = _limits()
    share = float(np.mean(pooled > lim["share_over"][compute_dtype])) * min(1.0, len(pooled) / POOL_FULL)
    return share * tolerance(compute_dtype) / lim["share_tolerance"][compute_dtype]


def serving(params) -> list:
    """The paged generators that serve ``params``: a block pool whose prefix
    index says where a prompt's rows lie, and ``pkv.k``, the latent pool.
    Found among the live objects (the harness hands a reference the engine's
    ``params`` and nothing else); a run has one, a test process may keep a
    closed scheduler's beside it."""
    import gc

    if _served["of"] is not params or not _served["gens"]:
        from dllama_tpu.runtime.serving import PagedGenerator      # the pool check reads the program's state: its one import

        _served.update(of=params, gens=[o for o in gc.get_objects() if isinstance(o, PagedGenerator)
                                        and o.eng.params is params and getattr(o, "pkv", None) is not None])
    return _served["gens"]


_served = {"of": None, "gens": []}


def pool_rows_gap(params, prompt, rows):
    """``[L, n]``: for the ``n`` prompt tokens whose blocks the prefix index
    still holds, the distance between the row the POOL holds and the
    reference's ``rows [L, T, lanes]``, over the reference row's norm. None
    where no generator serves ``params`` or no block of the prompt is indexed.

    Read on the caller's thread while the scheduler is idle (every call
    follows a finished request), through the index and not a table: a
    finished request's blocks stay where they were until they are evicted,
    and ``match_prefix`` takes no reference."""
    import jax.numpy as jnp

    found = [(gen, [b for b in gen.pool.match_prefix(list(prompt[:-1]))[0] if b < gen.pool.n_blocks])
             for gen in serving(params)]
    gen, bids = max(found, key=lambda f: len(f[1]), default=(None, []))
    if not bids:
        return None
    L, _T, lanes = rows.shape
    held = gen.pkv.k[:, jnp.asarray(bids, jnp.int32), 0, :, :lanes]        # [L, blocks, block_size, lanes]
    held = held.reshape(L, -1, lanes).astype(jnp.float32)
    want = rows[:, :held.shape[1]]
    gap = jnp.linalg.norm(held - want, axis=-1) / jnp.maximum(jnp.linalg.norm(want, axis=-1), 1e-30)
    return np.asarray(gap, dtype=np.float64)


def pool_entry(params, prompt, rows, compute_dtype: str) -> float:
    """The pool check as one more entry of ``gap`` ("Three limits"): the
    LOWER QUARTILE over the prompt's indexed rows of every layer of
    :func:`pool_rows_gap`, scaled so that the harness's one comparison holds
    it to ``pool_tolerance``; 0 where there is nothing to read. A pool of
    fewer bits moves EVERY row of EVERY layer by its rounding step at least,
    while what the program's own compute dtype adds grows with depth (on the
    chip 0.3% of a row's norm in layer 0 and 1.4-3% in layer 8), so the
    statistic is taken where the honest reading is small."""
    gap = pool_rows_gap(params, prompt, rows)
    if gap is None:
        return 0.0
    return float(np.quantile(gap, 0.25)) * tolerance(compute_dtype) / _limits()["pool_tolerance"][compute_dtype]


def reference_gaps(model: dict, params, prompt, emitted, *, control: str = "none") -> dict:
    variant = control if control in VARIANTS else "none"
    stack, held = _stack_fn(json.dumps(model, sort_keys=True), variant), {}

    def layers_fn(*args):
        x, held["rows"] = stack(*args)
        return x

    r = teacher_force(model, params, prompt, emitted, control=control, controls=CONTROLS,
                      layers_fn=layers_fn, layers=layer_tree(params))
    dtype = str(params.embedding.dtype)     # the engine's compute dtype: its embedding is held in it
    r["gap"] = np.append(r["gap"], [pooled_share_entry(params, control, r["gap"], dtype),
                                    pool_entry(params, prompt, held["rows"], dtype)])
    return r
