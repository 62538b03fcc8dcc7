"""Bytes and FLOPs of a decoder of gated short-convolution and attention layers
with routed experts behind leading dense layers: the ``counts`` module of
``lfm2-24b-a2b`` (``lfm2/README.md``), each function for ONE chip, counting what this
chip HOLDS (here: every expert of the layers it holds, every head, every row
of the vocabulary).

What a dispatch must read of the routed experts is the DISTINCT held experts
its rows chose (:func:`experts_touched`, at the mean row count), each once;
what it must compute is the held PAIRS. A kernel that reads a plane once a pair
is charged its time and not credited with the second reading.

The cache is K and V of the ATTENTION layers alone: ``2 x num_key_value_heads x
head`` values a token a layer, 2048 useful bytes in bfloat16 (8 KB a token over
the four attention layers held). The pool pads a head's 64 lanes to 128; the
padding is charged to the kernel's time and not credited as bytes. A conv
layer's whole state is ``(conv_L_cache - 1) x hidden_size`` values a sequence,
read and written once a step whatever the context.
"""

KERNEL = "expert_gemv"
CHUNK_KERNEL = "expert_chunk"
WALK_KERNEL = "paged_ragged_attention"


def _dims(model: dict) -> dict:
    d, H = model["hidden_size"], model["num_attention_heads"]
    hd = d // H
    kinds = model["layer_types"]
    n_attn = sum(k == "full_attention" for k in kinds)
    n_dense = int(model["num_dense_layers"])
    return {"d": d, "hd": hd, "q": H * hd, "kv": model["num_key_value_heads"] * hd,
            "n_attn": n_attn, "n_conv": len(kinds) - n_attn, "n_dense": n_dense, "n_routed": len(kinds) - n_dense,
            "taps": model["conv_L_cache"], "expert": 3 * d * model["moe_intermediate_size"],
            "dense": 3 * d * model["intermediate_size"], "V": model["vocab_size"],
            "held": model["num_experts"], "width": model["router_width"], "k": model["num_experts_per_tok"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    both kinds of mixer, the dense layers."""
    m = _dims(model)
    return (m["n_conv"] * 4 * m["d"] * m["d"] + m["n_attn"] * 2 * m["d"] * (m["q"] + m["kv"])
            + m["n_dense"] * m["dense"])


def float32_rows_bytes(model: dict) -> int:
    """The router's rows over its whole width, its bias, the taps."""
    m = _dims(model)
    return (m["n_routed"] * m["width"] * (m["d"] + 1) + m["n_conv"] * m["taps"] * m["d"]) * 4


def pairs_held(model: dict, rows: float) -> float:
    """Expected (row, expert) pairs a routed layer computes here for ``rows``
    rows under uniform routing: ``rows k held / width``."""
    m = _dims(model)
    return rows * m["k"] * m["held"] / m["width"]


def experts_touched(model: dict, rows: float) -> float:
    """Expected DISTINCT held experts a routed layer's ``rows`` rows choose
    under uniform routing."""
    m = _dims(model)
    return m["held"] * (1.0 - (1.0 - m["k"] / m["width"]) ** rows)


def _plane_bytes(weights: float, scale_bytes: int) -> float:
    return weights * (1.0 + scale_bytes / 32.0)


def cached_token_bytes(model: dict, kv_bytes: int = 2) -> int:
    """Useful bytes of one cached token in one attention layer: K and V."""
    return 2 * _dims(model)["kv"] * kv_bytes


def cached_token_flops(model: dict) -> float:
    """FLOPs one query token spends on one cached token in one attention
    layer, all heads: the score and the value over a head's lanes."""
    return 4.0 * _dims(model)["q"]


def tail_bytes(model: dict, rows: float, kv_bytes: int = 2) -> float:
    """The conv layers' tails of ``rows`` sequences, read and written once."""
    m = _dims(model)
    return 2.0 * rows * m["n_conv"] * (m["taps"] - 1) * m["d"] * kv_bytes


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, rows) * m["expert"], scale_bytes)
    cache = m["n_attn"] * cached_token_bytes(model, kv_bytes) * context_tokens
    return ((weights + float32_rows_bytes(model) + m["V"] * m["d"] * head_bytes + cache) / chips
            + tail_bytes(model, rows, kv_bytes) + rows * m["d"] * 2)


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["V"] * m["d"] + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, rows) * m["expert"])
    return (matmuls + m["n_attn"] * cached_token_flops(model) * context_tokens) / chips


def _attended(chunk: float, context_before: float) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    return chunk * context_before + chunk * (chunk + 1) / 2.0


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, chunk) * m["expert"])
    return (matmuls + m["n_attn"] * cached_token_flops(model) * _attended(chunk, context_before)) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, chunk) * m["expert"], scale_bytes)
    cache = m["n_attn"] * cached_token_bytes(model, kv_bytes) * (context_before + chunk)
    return (weights + float32_rows_bytes(model) + cache) / chips + tail_bytes(model, 1, kv_bytes)


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """``expert_gemv``: bytes and FLOPs of ONE (row, expert) pair in one routed
    layer, its three planes (gate, up, down) read once as held, and the pairs a
    step of ``rows`` rows is EXPECTED to run here a layer. ``expert_chunk``: bytes
    of ONE held expert's three planes (what a run of pairs that share it
    fetches once) and the FLOPs of one pair. ``paged_ragged_attention``: useful
    bytes and FLOPs of ONE cached token one row's walk reads in ONE attention
    layer (a reader multiplies by the block size, the blocks the steps really
    walked and the layers). None for a kernel this configuration does not
    have."""
    m = _dims(model)
    if kernel in (KERNEL, CHUNK_KERNEL):
        return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
                "pairs_per_layer": pairs_held(model, rows), "planes_per_layer": experts_touched(model, rows),
                "layers": m["n_routed"], "calls_per_program": 3 * m["n_routed"]}
    if kernel == WALK_KERNEL:
        return {"bytes": float(cached_token_bytes(model)), "flops": cached_token_flops(model),
                "layers": m["n_attn"], "calls_per_program": m["n_attn"]}
    return None
