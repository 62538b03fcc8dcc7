"""Bytes and FLOPs of a decoder of latent attention (MLA) layers with routed
experts of which a share is held: the ``counts`` module of ``a.x-k1`` (README,
"A layer equation"), each function for ONE chip, counting what this chip HOLDS
(its experts, its rows of the vocabulary; attention whole: it is data-parallel
in the deployment).

**The cache is one row a token a layer**: ``kv_lora_rank + qk_rope_head_dim``
useful values (576: 1152 B in bfloat16), whatever the head count. The pool pads
a row to 640 lanes; the padding is charged to the kernel's time and not
credited as bytes. Attention is ABSORBED in both programs, so a cached token
costs every head ``2 (576 + 512)`` FLOP (the score over the whole row, the value
over its first 512 lanes), and a query pays ``W_uk`` and ``W_uv`` once a token
(held per head in bfloat16: 2 B a weight).

What a dispatch must read of the routed experts is the DISTINCT held experts
its rows chose (:func:`experts_touched`, at the mean row count), each once; what
it must compute is the held PAIRS. The decode kernel reads a plane once a pair:
it is charged its time and not credited with the second reading.

The context a reader hands these functions comes from ``dllama_kv_blocks_used``:
a block shared by several sequences (a matched prefix) counts once there, though
every sequence's walk reads it, so ``decode_hbm_share`` under-reads in a cell
with a shared prefix; ``mla_step_hbm_share`` counts what the steps walked.
"""

KERNEL = "expert_gemv"
STEP_KERNEL = "mla_paged_step"


def _dims(model: dict) -> dict:
    d, H = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v, r = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    L, nd = model["num_hidden_layers"], model["first_k_dense_replace"]
    row = -(-(r + rope) // 128) * 128
    return {"d": d, "H": H, "L": L, "n_dense": nd, "n_routed": L - nd, "latent": r + rope, "r": r, "v": v,
            # Q40 planes of one layer's attention: W_dq, W_uq, W_dkv (as held: padded to whole lane tiles), W_o
            "attn_q40": d * model["q_lora_rank"] + model["q_lora_rank"] * H * (nope + rope) + d * row + H * v * d,
            "absorb": r * H * (nope + v),                       # W_uk and W_uv, held per head in bfloat16
            "expert": 3 * d * model["moe_intermediate_size"],
            "shared": 3 * d * model["n_shared_experts"] * model["moe_intermediate_size"],
            "dense": 3 * d * model["intermediate_size"], "V": model["vocab_size"],
            "held": model["n_routed_experts"], "width": model["router_width"], "k": model["num_experts_per_tok"]}


def always_read_weights(model: dict) -> int:
    """Weights in the Q40 planes every dispatch reads whatever its routing:
    attention's four, the dense layers, the shared experts."""
    m = _dims(model)
    return m["L"] * m["attn_q40"] + m["n_dense"] * m["dense"] + m["n_routed"] * m["shared"]


def dense_bf16_bytes(model: dict) -> int:
    """``W_uk`` and ``W_uv`` of every layer as held (2 B a weight)."""
    m = _dims(model)
    return 2 * m["L"] * m["absorb"]


def float32_rows_bytes(model: dict) -> int:
    """The router's rows over its whole width."""
    m = _dims(model)
    return m["n_routed"] * m["width"] * m["d"] * 4


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


def latent_token_bytes(model: dict, kv_bytes: int = 2) -> int:
    """Useful bytes of one cached token in one layer."""
    return _dims(model)["latent"] * kv_bytes


def latent_token_flops(model: dict) -> float:
    """FLOPs one query token spends on one cached token in one layer, all heads:
    the score over the row's useful lanes, the value over the latent's."""
    m = _dims(model)
    return 2.0 * m["H"] * (m["latent"] + m["r"])


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, rows) * m["expert"], scale_bytes)
    cache = m["L"] * latent_token_bytes(model, kv_bytes) * context_tokens
    return ((weights + dense_bf16_bytes(model) + float32_rows_bytes(model) + m["V"] * m["d"] * head_bytes + cache)
            / chips + rows * m["d"] * 2)


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (rows * (always_read_weights(model) + m["L"] * m["absorb"] + m["V"] * m["d"]
                             + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, rows) * m["expert"])
    return (matmuls + m["L"] * latent_token_flops(model) * context_tokens) / chips


def _attended(chunk: float, context_before: float) -> float:
    """Sum over the chunk's tokens of the keys each sees."""
    return chunk * context_before + chunk * (chunk + 1) / 2.0


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    m = _dims(model)
    matmuls = 2.0 * (chunk * (always_read_weights(model) + m["L"] * m["absorb"] + m["n_routed"] * m["width"] * m["d"])
                     + m["n_routed"] * pairs_held(model, chunk) * m["expert"])
    return (matmuls + m["L"] * latent_token_flops(model) * _attended(chunk, context_before)) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    m = _dims(model)
    weights = _plane_bytes(always_read_weights(model)
                           + m["n_routed"] * experts_touched(model, chunk) * m["expert"], scale_bytes)
    cache = m["L"] * latent_token_bytes(model, kv_bytes) * (context_before + chunk)
    return (weights + dense_bf16_bytes(model) + float32_rows_bytes(model) + cache) / chips


def kernel_counts(model: dict, kernel: str, *, rows) -> dict | None:
    """``expert_gemv``: bytes and FLOPs of ONE (row, expert) pair in one routed
    layer, its three planes (gate, up, down) read once as held, and the pairs a
    step of ``rows`` rows is EXPECTED to run here a layer. ``mla_paged_step``:
    bytes and FLOPs of ONE cached token one row's walk reads in ONE layer (a
    reader multiplies by the block size, the blocks the steps really walked
    and the layers). None for a kernel this configuration does not have."""
    m = _dims(model)
    if kernel == KERNEL:
        return {"bytes": _plane_bytes(m["expert"], 2), "flops": 2.0 * m["expert"],
                "pairs_per_layer": pairs_held(model, rows), "layers": m["n_routed"],
                "calls_per_program": 3 * m["n_routed"]}
    if kernel == STEP_KERNEL:
        return {"bytes": float(latent_token_bytes(model)), "flops": latent_token_flops(model),
                "layers": m["L"], "calls_per_program": m["L"]}
    return None
