"""Bytes and FLOPs of a decoder of window and full attention layers with routed
experts of which a share is held: the ``counts`` module of ``laguna-s-2.1``
(README, "A layer equation"), each function for ONE chip, counting what this
chip HOLDS (its heads, its experts, its rows of the vocabulary).

What a dispatch must read of the routed experts is the DISTINCT held experts
its rows chose (:func:`experts_touched`, at the mean row count), each once;
what it must compute is the held PAIRS. The decode kernel reads a plane once a
pair, not once an expert: it is charged its time and not credited with the
second reading.

The context a reader hands these functions comes from ``dllama_kv_blocks_used``,
which for this configuration is the FULL layers' pool (every cached layer of
the other configurations lives in that pool; here the six full layers do). The
sliding layers hold a window at most: a row's cache reads there are bounded by
``sliding_window`` (:func:`window_tokens`), whatever its context.
"""

KERNEL = "expert_gemv"


def _dims(model: dict) -> dict:
    d, hd = model["hidden_size"], model["head_dim"]
    kinds, heads = model["layer_types"], model["num_attention_heads_per_layer"]
    n_full = sum(k == "full_attention" for k in kinds)
    n_slide = len(kinds) - n_full
    q_full = hd * next(h for h, k in zip(heads, kinds) if k == "full_attention")
    q_slide = hd * next((h for h, k in zip(heads, kinds) if k != "full_attention"), 0)
    n_dense = len(model.get("mlp_only_layers") or [])
    return {"d": d, "kv": hd * model["num_key_value_heads"], "n_full": n_full, "n_slide": n_slide,
            "q_full": q_full, "q_slide": q_slide, "n_dense": n_dense, "n_routed": len(kinds) - n_dense,
            "expert": 3 * d * model["moe_intermediate_size"], "shared": 3 * d * model["shared_expert_intermediate_size"],
            "dense": 3 * d * model["intermediate_size"], "v": model["vocab_size"],
            "held": model["num_experts"], "width": model["router_width"], "k": model["num_experts_per_tok"],
            "window": model["sliding_window"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    attention of both kinds, the dense layers, the shared experts."""
    m = _dims(model)
    attn = lambda q: 2 * m["d"] * (q + m["kv"])
    return (m["n_full"] * attn(m["q_full"]) + m["n_slide"] * attn(m["q_slide"])
            + m["n_dense"] * m["dense"] + m["n_routed"] * m["shared"])


def float32_rows_bytes(model: dict) -> int:
    """The router's rows over its whole width and the per-head gate's rows."""
    m = _dims(model)
    hd = model["head_dim"]
    gates = m["n_full"] * m["q_full"] // hd + m["n_slide"] * m["q_slide"] // hd
    return (m["n_routed"] * m["width"] + gates) * m["d"] * 4


def pairs_held(model: dict, rows: float) -> float:
    """Expected (row, expert) pairs a routed layer computes here for ``rows``
    rows under uniform routing: ``rows k held / width``."""
    m = _dims(model)
    return rows * m["k"] * m["held"] / m["width"]


def experts_touched(model: dict, rows: float) -> float:
    """Expected DISTINCT held experts a routed layer's ``rows`` rows choose
    under uniform routing: each row's ``k`` distinct choices miss a given
    expert with probability ``1 - k / width``."""
    m = _dims(model)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["width"]) ** rows)


def window_tokens(model: dict, rows: float, context_tokens: float) -> float:
    """Cached positions the sliding layers read for ``rows`` rows whose
    contexts sum to ``context_tokens``: the window's bound a row."""
    if rows <= 0:
        return 0.0
    return rows * min(context_tokens / rows, float(model["sliding_window"]))


def _cache_bytes(model: dict, full_tokens: float, slide_tokens: float, kv_bytes: int) -> float:
    m = _dims(model)
    return 2.0 * m["kv"] * kv_bytes * (m["n_full"] * full_tokens + m["n_slide"] * slide_tokens)


def _plane_bytes(weights: float, scale_bytes: int) -> float:
    return weights * (1.0 + scale_bytes / 32.0)


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, rows) * m["expert"], scale_bytes)
    cache = _cache_bytes(model, context_tokens, window_tokens(model, rows, context_tokens), kv_bytes)
    return ((weights + float32_rows_bytes(model) + m["v"] * m["d"] * head_bytes + cache) / chips
            + rows * m["d"] * 2)


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["v"] * m["d"] + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, rows) * m["expert"])
    attention = 4.0 * (m["n_full"] * m["q_full"] * context_tokens
                       + m["n_slide"] * m["q_slide"] * window_tokens(model, rows, context_tokens))
    return (matmuls + attention) / chips


def _attended(chunk: float, context_before: float, window: float | None) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    if window is None:
        return chunk * context_before + chunk * (chunk + 1) / 2.0
    return sum(min(context_before + t + 1, window) for t in range(int(chunk)))


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, chunk) * m["expert"])
    attention = 4.0 * (m["n_full"] * m["q_full"] * _attended(chunk, context_before, None)
                       + m["n_slide"] * m["q_slide"] * _attended(chunk, context_before, m["window"]))
    return (matmuls + attention) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, chunk) * m["expert"], scale_bytes)
    cache = _cache_bytes(model, context_before + chunk, min(context_before, m["window"]) + chunk, kv_bytes)
    return (weights + float32_rows_bytes(model) + cache) / chips


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """``expert_gemv``: bytes and FLOPs of ONE (row, expert) pair in one routed
    layer, its three planes (gate, up, down) read once as held, and the
    pairs a step of ``rows`` rows is EXPECTED to run here a layer
    (``pairs_per_layer``; a reader that knows the pairs really run uses
    those). ``calls_per_program`` is the kernel's launches in one step: three
    a routed layer. None for a kernel this configuration does not have."""
    if kernel != KERNEL:
        return None
    m = _dims(model)
    return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
            "pairs_per_layer": pairs_held(model, rows), "layers": m["n_routed"],
            "calls_per_program": 3 * m["n_routed"]}
