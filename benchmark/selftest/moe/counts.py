"""Bytes and FLOPs of a decoder whose feed-forward is routed: the worked case
of README's "A layer equation". Per layer the four attention matrices, the
float32 gate, and of ``num_experts`` experts (three planes each,
``moe_intermediate_size`` wide) those a step touches: a row computes
``num_experts_per_tok`` of them, and a step of ``rows`` rows reads
``E (1 - (1 - k/E)^rows)`` on average under a uniform router.
"""


def _dims(model: dict):
    d, L, hd = model["hidden_size"], model["num_hidden_layers"], model["head_dim"]
    q, kv = hd * model["num_attention_heads"], hd * model["num_key_value_heads"]
    return d, L, q, kv, model["vocab_size"]


def _attention_weights(model: dict) -> int:
    d, _L, q, kv, _v = _dims(model)
    return d * q + 2 * d * kv + q * d


def _expert_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def experts_touched(model: dict, rows: float) -> float:
    E, k = model["num_experts"], model["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** rows)


def _weight_bytes(model: dict, rows: float, scale_bytes: int) -> float:
    d, L, _q, _kv, _v = _dims(model)
    planes = _attention_weights(model) + experts_touched(model, rows) * _expert_weights(model)
    return L * (planes * (1.0 + scale_bytes / 32.0) + model["num_experts"] * d * 4)


def _row_flops(model: dict) -> float:
    """FLOPs of the layers' matmuls for one row."""
    d, L, _q, _kv, _v = _dims(model)
    return 2.0 * L * (_attention_weights(model) + model["num_experts"] * d
                      + model["num_experts_per_tok"] * _expert_weights(model))


def decode_step_bytes(model: dict, *, rows, context_tokens, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    d, L, _q, kv, v = _dims(model)
    cache = 2 * L * kv * kv_bytes * context_tokens
    return (_weight_bytes(model, rows, scale_bytes) + v * d * head_bytes + cache) / chips + rows * d * 2


def decode_step_flops(model: dict, *, rows, context_tokens, chips: int = 1) -> float:
    d, L, q, _kv, v = _dims(model)
    return (rows * (_row_flops(model) + 2.0 * v * d) + 4.0 * L * q * context_tokens) / chips


def prefill_chunk_flops(model: dict, *, chunk, context_before, chips: int = 1) -> float:
    _d, L, q, _kv, _v = _dims(model)
    attended = chunk * context_before + chunk * (chunk + 1) / 2.0
    return (chunk * _row_flops(model) + 4.0 * L * q * attended) / chips


def prefill_chunk_bytes(model: dict, *, chunk, context_before, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    _d, L, _q, kv, _v = _dims(model)
    cache = 2 * L * kv * kv_bytes * (context_before + chunk)
    return (_weight_bytes(model, chunk, scale_bytes) + cache) / chips
