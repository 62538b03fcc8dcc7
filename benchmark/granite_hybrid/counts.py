"""Bytes and FLOPs of a decoder whose every layer is an SSD mixer or attention
without positions, THEN gated routed experts beside a gated shared one: the
``counts`` module of ``granite-4.0-h-small`` (``granite_hybrid/README.md``), each
function for ONE chip, counting what this chip HOLDS (every expert, every
mixer, the whole vocabulary; the head IS the embedding and is read once a
dispatch as a dense bfloat16 matrix).

What a dispatch must read of the routed experts is the DISTINCT experts its rows
chose (:func:`experts_touched`, under uniform routing), each once; what it must
compute is the PAIRS. An expert is THREE planes in the model's width (10.03 MB
at 1.0625 B a weight). The cache is K and V of the ATTENTION layers alone (2 x
8 x 128 values a token a layer). A mixer layer's state is ``heads x head x
state`` float32 values and a tail of ``(mamba_d_conv - 1)`` rows of the
convolution's channels a sequence, read and written once a step whatever the
context.
"""

STEP_KERNEL = "ssd_step"
KERNEL = "expert_gemv"
CHUNK_KERNEL = "expert_chunk"
WALK_KERNEL = "paged_ragged_attention"


def _dims(model: dict) -> dict:
    d, hd = model["hidden_size"], model["head_dim"]
    kinds = model["layer_types"]
    H, P, G, N = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_n_groups"], model["mamba_d_state"]
    d_ssm = H * P
    hid, wide = model["intermediate_size"], model["shared_intermediate_size"]
    return {"d": d, "hd": hd, "q": model["num_attention_heads"] * hd, "kv": model["num_key_value_heads"] * hd,
            "n_mixer": kinds.count("mamba"), "n_attn": kinds.count("attention"), "n_routed": len(kinds),
            "H": H, "P": P, "G": G, "N": N, "d_ssm": d_ssm, "conv": d_ssm + 2 * G * N, "taps": model["mamba_d_conv"],
            "mixer": d * (2 * d_ssm + 2 * G * N) + d_ssm * d, "attn": 2 * d * (model["num_attention_heads"] * hd
                                                                             + model["num_key_value_heads"] * hd),
            "expert": 3 * d * hid, "routed_always": 3 * d * wide, "V": model["vocab_size"],
            "held": model["num_local_experts"], "width": model["num_local_experts"], "k": model["num_experts_per_tok"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    the mixers' two projections, q k v wo and the shared expert."""
    m = _dims(model)
    return m["n_mixer"] * m["mixer"] + m["n_attn"] * m["attn"] + m["n_routed"] * m["routed_always"]


def float32_rows_bytes(model: dict) -> int:
    """The router's rows over its whole width; the mixers' dt rows, taps and
    bias."""
    m = _dims(model)
    return (m["n_routed"] * m["width"] * m["d"]
            + m["n_mixer"] * (m["H"] * m["d"] + (m["taps"] + 1) * m["conv"])) * 4


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
    """Bytes of one cached token in one attention layer: K and V."""
    return 2 * _dims(model)["kv"] * kv_bytes


def cached_token_flops(model: dict) -> float:
    """FLOPs one query token spends on one cached token in one attention
    layer, all heads: the score and the value over a head's lanes."""
    return 4.0 * _dims(model)["q"]


def state_bytes(model: dict, rows: float, kv_bytes: int = 2) -> float:
    """The mixer layers' float32 states and tails of ``rows`` sequences, read
    and written once."""
    m = _dims(model)
    return 2.0 * rows * m["n_mixer"] * (m["H"] * m["P"] * m["N"] * 4 + (m["taps"] - 1) * m["conv"] * kv_bytes)


def state_flops(model: dict, tokens: float) -> float:
    """The recurrence of ``tokens`` tokens in every mixer layer: decay, the
    outer product's update and the readout, 5 FLOPs a state value."""
    m = _dims(model)
    return 5.0 * tokens * m["n_mixer"] * m["H"] * m["P"] * m["N"]


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, rows) * m["expert"], scale_bytes)
    cache = m["n_attn"] * cached_token_bytes(model, kv_bytes) * context_tokens
    return ((weights + float32_rows_bytes(model) + m["V"] * m["d"] * head_bytes + cache) / chips
            + state_bytes(model, rows, kv_bytes) + rows * m["d"] * 2)


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["V"] * m["d"] + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, rows) * m["expert"])
    return (matmuls + m["n_attn"] * cached_token_flops(model) * context_tokens) / chips + state_flops(model, rows)


def _attended(chunk: float, context_before: float) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    return chunk * context_before + chunk * (chunk + 1) / 2.0


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, chunk) * m["expert"])
    return ((matmuls + m["n_attn"] * cached_token_flops(model) * _attended(chunk, context_before)) / chips
            + state_flops(model, chunk))


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, chunk) * m["expert"], scale_bytes)
    cache = m["n_attn"] * cached_token_bytes(model, kv_bytes) * (context_before + chunk)
    return (weights + float32_rows_bytes(model) + cache) / chips + state_bytes(model, 1, kv_bytes)


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """``ssd_step``: bytes and FLOPs of ONE mixer layer's step form over ``rows``
    rows (each row's state read once and written once; ``dt x`` and the decay in,
    two float32 columns a head, a group's B and C in, ``y`` out) and the calls one
    step program makes. ``expert_gemv``: bytes and FLOPs of ONE (row, expert) pair
    in one routed layer, its THREE planes read once, and the pairs a step
    of ``rows`` rows is EXPECTED to run here a layer. ``expert_chunk``: bytes of
    ONE held expert's three planes (what a run of pairs that share it fetches
    once) and the FLOPs of one pair. ``paged_ragged_attention``: bytes and FLOPs
    of ONE cached token one row's walk reads in ONE attention layer. None for a
    kernel this configuration does not have."""
    m = _dims(model)
    if kernel == STEP_KERNEL:
        vectors = (3 * m["H"] * m["P"] + 2 * m["G"] * m["N"]) * 4
        return {"bytes": rows * (2.0 * m["H"] * m["P"] * m["N"] * 4 + vectors),
                "flops": 5.0 * rows * m["H"] * m["P"] * m["N"], "calls_per_program": m["n_mixer"]}
    if kernel in (KERNEL, CHUNK_KERNEL):
        return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
                "pairs_per_layer": pairs_held(model, rows), "planes_per_layer": experts_touched(model, rows),
                "layers": m["n_routed"], "calls_per_program": 3 * m["n_routed"]}
    if kernel == WALK_KERNEL:
        return {"bytes": float(cached_token_bytes(model)), "flops": cached_token_flops(model),
                "layers": m["n_attn"], "calls_per_program": m["n_attn"]}
    return None
