"""Bytes and FLOPs of a decoder whose period is sliding layers closed by a
full one, every layer routed over experts that are all held: the ``counts``
module of ``mellum2-12b-a2.5b`` (README, "A layer equation"), each function
for ONE chip, counting what this chip HOLDS.

What a dispatch must read of the routed experts is the DISTINCT experts its
rows chose (:func:`experts_touched`, at the mean row count), each once; what it
must compute is the PAIRS. At 16 rows of 8 a step is past ``share.step_form``
(128 pairs over 64 experts) and takes the run form, a plane a run of pairs
(``expert_chunk``), as lfm2's does.

The context a reader hands these functions comes from ``dllama_kv_blocks_used``,
which for this configuration is the FULL layers' pool (one layer in four). The
sliding layers hold a window at most: a row's cache reads there are bounded by
``sliding_window`` (:func:`window_tokens`), whatever its context. The paged
walk's kernel counts (``paged_ragged_attention``) credit the FULL layers' walk
alone, which is what a step's ``kv_walk_blocks`` counts; the sliding layers'
walks run under the same kernel name and are charged to its time, so the
share under-reads by what they move (at most 64 blocks a row in twelve layers).
"""

KERNEL = "expert_gemv"
CHUNK_KERNEL = "expert_chunk"
WALK_KERNEL = "paged_ragged_attention"


def _dims(model: dict) -> dict:
    d, hd = model["hidden_size"], model["head_dim"]
    kinds = model["layer_types"]
    n_full = sum(k == "full_attention" for k in kinds)
    return {"d": d, "q": hd * model["num_attention_heads"], "kv": hd * model["num_key_value_heads"],
            "n_full": n_full, "n_slide": len(kinds) - n_full, "n_routed": len(kinds),
            "expert": 3 * d * model["moe_intermediate_size"], "v": model["vocab_size"],
            "held": model["num_experts"], "k": model["num_experts_per_tok"], "window": model["sliding_window"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    every layer's attention."""
    m = _dims(model)
    return m["n_routed"] * 2 * m["d"] * (m["q"] + m["kv"])


def float32_rows_bytes(model: dict) -> int:
    """The router's rows."""
    m = _dims(model)
    return m["n_routed"] * m["held"] * m["d"] * 4


def pairs_held(model: dict, rows: float) -> float:
    """(row, expert) pairs a routed layer computes for ``rows`` rows: every
    expert is held."""
    return rows * _dims(model)["k"]


def experts_touched(model: dict, rows: float) -> float:
    """Expected DISTINCT experts a routed layer's ``rows`` rows choose under
    uniform routing: each row's ``k`` distinct choices miss a given expert with
    probability ``1 - k / held``."""
    m = _dims(model)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["held"]) ** rows)


def window_tokens(model: dict, rows: float, context_tokens: float) -> float:
    """Cached positions the sliding layers read for ``rows`` rows whose
    contexts sum to ``context_tokens``: the window's bound a row."""
    if rows <= 0:
        return 0.0
    return rows * min(context_tokens / rows, float(model["sliding_window"]))


def cached_token_bytes(model: dict, kv_bytes: int = 2) -> int:
    """K and V of one cached token in one layer."""
    return 2 * _dims(model)["kv"] * kv_bytes


def cached_token_flops(model: dict) -> float:
    """Scores and the weighted sum of one cached token for one row in one layer."""
    return 4.0 * _dims(model)["q"]


def _cache_bytes(model: dict, full_tokens: float, slide_tokens: float, kv_bytes: int) -> float:
    m = _dims(model)
    return cached_token_bytes(model, kv_bytes) * (m["n_full"] * full_tokens + m["n_slide"] * slide_tokens)


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
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["v"] * m["d"] + m["n_routed"] * m["held"] * m["d"])
                     + m["n_routed"] * pairs_held(model, rows) * m["expert"])
    attention = cached_token_flops(model) * (m["n_full"] * context_tokens
                                             + m["n_slide"] * window_tokens(model, rows, context_tokens))
    return (matmuls + attention) / chips


def _attended(chunk: float, context_before: float, window: float | None) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    if window is None:
        return chunk * context_before + chunk * (chunk + 1) / 2.0
    return sum(min(context_before + t + 1, window) for t in range(int(chunk)))


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["n_routed"] * m["held"] * m["d"])
                     + m["n_routed"] * pairs_held(model, chunk) * m["expert"])
    attention = cached_token_flops(model) * (m["n_full"] * _attended(chunk, context_before, None)
                                             + m["n_slide"] * _attended(chunk, context_before, m["window"]))
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
    layer, its three planes read once. ``expert_chunk``: bytes of ONE expert's
    three planes (what a run of pairs that share it fetches once) and the FLOPs
    of one pair. ``paged_ragged_attention``: useful bytes and FLOPs of ONE
    cached token one row's walk reads in ONE full layer (a reader multiplies by
    the block size, the blocks the steps really walked in the full pool and the
    full layers; module docstring). None for a kernel this configuration does
    not have."""
    m = _dims(model)
    if kernel in (KERNEL, CHUNK_KERNEL):
        return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
                "pairs_per_layer": pairs_held(model, rows), "planes_per_layer": experts_touched(model, rows),
                "layers": m["n_routed"], "calls_per_program": 3 * m["n_routed"]}
    if kernel == WALK_KERNEL:
        return {"bytes": float(cached_token_bytes(model)), "flops": cached_token_flops(model),
                "layers": m["n_full"], "calls_per_program": m["n_full"] + m["n_slide"]}
    return None
