"""The plain reference and the reference gap: the one thing that decides `correct`.

Imports nothing from ``dllama_tpu.models`` or ``dllama_tpu.ops``. The forward
pass below is the architecture as published (pre-norm decoder, RMSNorm, rotary
positions, grouped-query softmax attention, SwiGLU; per-head q/k RMSNorm where
the configuration says ``qk_norm``) in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: no cache, no batching, no kernels.
It reads the SAME Q40 planes the engine serves (``w[o, i] = codes[i, o] *
scales[i // 32, o]``) and dequantizes one layer at a time inside a scan, so a
7B model never exists in float32.

Departures from the published models, each deliberate: weights are random from
the seed; Qwen3-4B ties its embedding and head and the program holds them as
two arrays (the reference reads the two the program holds).

For a finished request the reference is teacher-forced on the prompt and the
tokens the PROGRAM emitted. At emitted position i the gap is

    (max_v ref_logit[i, v] - ref_logit[i, emitted_i]) / std_v(ref_logit[i, :])

A near-tie that the program's bf16 arithmetic resolved the other way has a gap
near 0 whatever the batch composition was; a token decoded over a wrong cache,
at a wrong position or with part of the model missing has a gap of order 1.

This file is the DEFAULT ``reference`` module (README, "Adding things"): a
configuration whose layer equation is another names its own, and builds it
from the parts here that no equation changes: :class:`Planes`, :func:`_planes`,
:func:`_dequant`, :func:`_rms_norm`, :func:`_rope`, :func:`_attention`,
:func:`attention_half`, :func:`swiglu`, :func:`layers_program` (the scan, the
controls' handles, ``highest`` precision, the jit), :func:`head_gaps` (the
blocked head and the gap) and :func:`teacher_force` (the driver, which takes
the layer stack's program as ``layers_fn``).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, NamedTuple

import numpy as np

QUANT_BLOCK = 32
BLOCK_Q = 512        # query rows per attention block; sequence lengths pad to it
VOCAB_CHUNK = 16384  # head rows per logits call: bounds the f32 copy of the head
N_OUT_PAD = 64       # emitted positions pad to a multiple of this
CONTROLS = ("none", "shift", "droplayer", "dropblock")
LOST_BLOCK = 16      # positions the dropblock control hides

_HERE = os.path.dirname(os.path.abspath(__file__))


def tolerance_from(path: str, compute_dtype: str) -> float:
    """The gap tolerance for a compute dtype from a file shaped like
    ``gap_tolerance.json``: a module's own lies beside it, with its reason."""
    with open(path, encoding="utf-8") as f:
        table = json.load(f)["tolerance"]
    if compute_dtype not in table:
        raise KeyError(f"no gap tolerance measured for compute dtype {compute_dtype!r} in {path}")
    return float(table[compute_dtype])


def tolerance(compute_dtype: str) -> float:
    """The dense decoders' gap tolerance, from ``gap_tolerance.json``."""
    return tolerance_from(os.path.join(_HERE, "gap_tolerance.json"), compute_dtype)


class Planes(NamedTuple):
    """Q40 planes as the engine holds them: ``scales [.., in/32, out]``,
    ``codes [.., in, out]`` int8. The reference's own type, so this file
    needs none of the program's classes."""

    scales: Any
    codes: Any


def _planes(w):
    return Planes(w.scales, w.codes) if hasattr(w, "codes") else w


def _dequant(w):
    """One matrix as float32 ``[in, out]`` from whatever the engine holds:
    Q40 planes, or a dense ``[out, in]`` array."""
    import jax.numpy as jnp

    if isinstance(w, Planes):
        scales = jnp.repeat(w.scales.astype(jnp.float32), QUANT_BLOCK, axis=-2)
        return w.codes.astype(jnp.float32) * scales
    return jnp.swapaxes(w.astype(jnp.float32), -1, -2)


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta, convention):
    """Rotate ``x [T, heads, hd]`` at ``positions [T]``. ``interleaved`` pairs
    (x[2j], x[2j+1]); ``half_split`` pairs (x[j], x[j + hd/2]); both at
    frequency theta**(-2j/hd)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    j = jnp.arange(hd // 2, dtype=jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * (1.0 / theta ** (2.0 * j / hd))[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if convention == "interleaved":
        x0, x1 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1).reshape(x.shape)
    if convention == "half_split":
        x0, x1 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)
    raise ValueError(f"unknown rope convention {convention!r}")


def _attention(q, k, v, hide):
    """Causal GQA softmax attention, ``q [T, H, hd]``, ``k/v [T, KV, hd]``,
    in blocks of BLOCK_Q query rows so scores never hold T x T per head.
    ``hide = (from_row, lo, hi)``: query rows >= from_row do not see keys
    lo..hi-1 (the lost-block control; an honest run hides nothing)."""
    import jax
    import jax.numpy as jnp

    T, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(T // BLOCK_Q, BLOCK_Q, KV, H // KV, hd)
    key_pos = jnp.arange(T)

    def block(args):
        qb, b = args
        scores = jnp.einsum("tkmh,skh->kmts", qb, k) / jnp.sqrt(jnp.float32(hd))
        q_pos = b * BLOCK_Q + jnp.arange(BLOCK_Q)
        seen = key_pos[None, :] <= q_pos[:, None]
        lost = (q_pos[:, None] >= hide[0]) & (key_pos[None, :] >= hide[1]) & (key_pos[None, :] < hide[2])
        scores = jnp.where((seen & ~lost)[None, None, :, :], scores, -jnp.inf)
        return jnp.einsum("kmts,skh->tkmh", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (qg, jnp.arange(T // BLOCK_Q)))
    return out.reshape(T, H * hd)


def attention_half(m: dict, x, lp, positions, hide):
    """A layer's attention half, residual added: pre-norm, q/k/v, per-head q/k
    norm where ``qk_norm``, rotary positions, causal GQA attention, ``wo``.
    ``x [T, dim]`` float32; ``lp`` one layer's leaves."""
    T = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta, conv = float(m["norm_epsilon"]), float(m["rope_theta"]), m["rope_convention"]
    h = _rms_norm(x, lp["norm_att"], eps)
    q = (h @ _dequant(lp["wq"])).reshape(T, H, hd)
    k = (h @ _dequant(lp["wk"])).reshape(T, KV, hd)
    v = (h @ _dequant(lp["wv"])).reshape(T, KV, hd)
    if m.get("qk_norm"):
        q = _rms_norm(q, lp["norm_q"], eps)
        k = _rms_norm(k, lp["norm_k"], eps)
    q, k = _rope(q, positions, theta, conv), _rope(k, positions, theta, conv)
    return x + _attention(q, k, v, hide) @ _dequant(lp["wo"])


def swiglu(h, w1, w2, w3):
    """``(silu(h w1) * (h w3)) w2`` over planes or dense matrices."""
    import jax

    return (jax.nn.silu(h @ _dequant(w1)) * (h @ _dequant(w3))) @ _dequant(w2)


def layers_program(layer_fn):
    """jit of a whole layer stack scanned over its leading axis: ``(tokens[T],
    embedding, layers, keep[L], shift, shift_from, hide) -> x[T, dim]``, where
    ``layer_fn(x, lp, positions, hide) -> x`` is ONE layer with its residuals.
    ``keep`` and ``shift`` are the negative control's handles: an honest run
    passes ones and 0. Rotary positions are relative, so moving every row by
    one changes nothing; the control moves the rows from ``shift_from`` on
    (the emitted tokens) against the prompt, as a decode at a wrong position
    would. A stack that is not one scan (two stacks in a pattern) writes its
    own program with this signature."""
    import jax
    import jax.numpy as jnp

    def run(tokens, embedding, layers, keep, shift, shift_from, hide):
        positions = jnp.arange(tokens.shape[0])
        positions = positions + jnp.where(positions >= shift_from, shift, 0)
        x = embedding[tokens].astype(jnp.float32)

        def body(x, xs):
            lp, keep_l = xs
            return x + keep_l * (layer_fn(x, lp, positions, hide) - x), None

        x, _ = jax.lax.scan(body, x, (layers, keep))
        return x

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return run(*args)

    return jax.jit(traced)


@functools.lru_cache(maxsize=None)
def _layers_fn(model_key: str):
    """The dense decoder's stack for one configuration (its ``model`` as JSON)."""
    m = json.loads(model_key)
    eps = float(m["norm_epsilon"])

    def layer(x, lp, positions, hide):
        x1 = attention_half(m, x, lp, positions, hide)
        return x1 + swiglu(_rms_norm(x1, lp["norm_ffn"], eps), lp["w1"], lp["w2"], lp["w3"])

    return layers_program(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float):
    """jit: final norm of the selected rows, then one chunk of the head.
    Returns per row: the chunk's largest and second-largest logit, the sum
    and sum of squares, and the logit of ``emitted - lo`` where it falls in
    the chunk (else -inf)."""
    import jax
    import jax.numpy as jnp

    def run(x_rows, final_norm, head_chunk, emitted, lo):
        with jax.default_matmul_precision("highest"):
            h = _rms_norm(x_rows, final_norm, eps)
            logits = h @ _dequant(head_chunk)                     # [n, chunk]
        top2 = jax.lax.top_k(logits, 2)[0]
        idx = emitted - lo
        inside = (idx >= 0) & (idx < logits.shape[1])
        mine = jnp.take_along_axis(logits, jnp.clip(idx, 0, logits.shape[1] - 1)[:, None], axis=1)[:, 0]
        return (top2[:, 0], top2[:, 1], logits.sum(axis=1), (logits * logits).sum(axis=1),
                jnp.where(inside, mine, -jnp.inf))

    return jax.jit(run)


ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "norm_att", "norm_ffn")


def layer_tree(params, names) -> dict:
    """The named leaves of the engine's layer stack, Q40 planes as :class:`Planes`."""
    return {n: _planes(getattr(params.layers, n)) for n in names}


def _layer_tree(params) -> dict:
    names = ATTENTION_LEAVES + ("w1", "w2", "w3")
    if params.layers.norm_q is not None:
        names += ("norm_q", "norm_k")
    return layer_tree(params, names)


def control_handles(n_layers: int, n_prompt: int, T: int, control: str):
    """``(keep[L,1,1], shift, shift_from, hide)`` as :func:`layers_program`
    takes them: ``shift`` (emitted rows one position late), ``droplayer`` (the
    middle layer left out), ``dropblock`` (the emitted rows do not see the
    middle 16 positions of the prompt: a cache block lost under a live
    sequence); anything else is an honest run."""
    import jax.numpy as jnp

    keep = np.ones((n_layers, 1, 1), dtype=np.float32)
    if control == "droplayer":
        keep[n_layers // 2] = 0.0
    lo = max(0, n_prompt // 2 - LOST_BLOCK // 2)
    hide = (n_prompt, lo, lo + LOST_BLOCK) if control == "dropblock" else (T, 0, 0)
    return (jnp.asarray(keep), jnp.int32(1 if control == "shift" else 0), jnp.int32(n_prompt),
            jnp.asarray(hide, dtype=jnp.int32))


def head_gaps(model: dict, params, x, n_prompt: int, emitted: list[int]) -> dict:
    """From the stack's output ``x [T, dim]``: final norm and the head in
    chunks of VOCAB_CHUNK rows over the rows that predict ``emitted``, and per
    emitted position the gap, the top-2 margin and the logits' standard
    deviation."""
    import jax.numpy as jnp

    n_out, T = len(emitted), x.shape[0]
    n_pad = -(-n_out // N_OUT_PAD) * N_OUT_PAD
    rows = np.clip(n_prompt - 1 + np.arange(n_pad), 0, T - 1)
    x_rows = x[jnp.asarray(rows)]
    em = np.zeros(n_pad, dtype=np.int32)
    em[:n_out] = emitted
    em_dev = jnp.asarray(em)
    head = _planes(params.logits)
    V = model["vocab_size"]
    head_fn = _head_fn(float(model["norm_epsilon"]))
    top1 = np.full(n_pad, -np.inf)
    top2 = np.full(n_pad, -np.inf)
    s1, s2 = np.zeros(n_pad), np.zeros(n_pad)
    mine = np.full(n_pad, -np.inf)
    for lo in range(0, V, VOCAB_CHUNK):
        hi = min(V, lo + VOCAB_CHUNK)
        chunk = (Planes(head.scales[:, lo:hi], head.codes[:, lo:hi])
                 if isinstance(head, Planes) else head[lo:hi])
        a, b, c, d, e = (np.asarray(t, dtype=np.float64) for t in
                         head_fn(x_rows, params.final_norm, chunk, em_dev, jnp.int32(lo)))
        both = np.sort(np.stack([top1, top2, a, b]), axis=0)
        top1, top2 = both[-1], both[-2]
        s1 += c
        s2 += d
        mine = np.maximum(mine, e)
    std = np.sqrt(np.maximum(s2 / V - (s1 / V) ** 2, 1e-30))
    sl = slice(0, n_out)
    return {"gap": ((top1 - mine) / std)[sl], "margin": ((top1 - top2) / std)[sl],
            "std": std[sl], "finite": bool(np.isfinite(s2[sl]).all())}


def teacher_force(model: dict, params, prompt: list[int], emitted: list[int], *, control: str,
                  layers_fn, layers, controls=CONTROLS) -> dict:
    """Run ``layers_fn`` (a :func:`layers_program`, or a program of its
    signature) over ``prompt + emitted`` with ``layers`` as its layer tree and
    the control's handles, then :func:`head_gaps`. Row P-1+i predicts
    ``emitted[i]``; sequence lengths pad to BLOCK_Q."""
    import jax.numpy as jnp

    if control not in controls:
        raise ValueError(f"unknown control {control!r}")
    seq = list(prompt) + list(emitted[:-1])
    T = -(-len(seq) // BLOCK_Q) * BLOCK_Q
    tokens = np.zeros(T, dtype=np.int32)
    tokens[:len(seq)] = seq
    x = layers_fn(jnp.asarray(tokens), params.embedding, layers,
                  *control_handles(model["num_hidden_layers"], len(prompt), T, control))
    return head_gaps(model, params, x, len(prompt), emitted)


def reference_gaps(model: dict, params, prompt: list[int], emitted: list[int], *,
                   control: str = "none") -> dict:
    """Teacher-force the reference on ``prompt + emitted`` and return, per
    emitted position, the gap, the reference's top-2 margin (both in units of
    the logits' standard deviation) and that standard deviation, as numpy
    arrays of ``len(emitted)``.

    ``control``: ``none`` (honest), or a negative control made in the reference
    only: ``shift``, ``droplayer`` or ``dropblock`` (:func:`control_handles`)."""
    return teacher_force(model, params, prompt, emitted, control=control,
                         layers_fn=_layers_fn(json.dumps(model, sort_keys=True)),
                         layers=_layer_tree(params))
