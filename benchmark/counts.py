"""The bytes and FLOPs a dense decoder's decode step and prefill chunk need: the
default ``counts`` module (README, "Adding things": a configuration whose layer
equation is another names its own under ``modules.counts``).

Seven matrices a layer (q, k, v, o, gate, up, down), K and V rows of every
cached position in every layer, a dense bf16 head. The functions moved here
from ``peaks.py`` (PR 29) with their arithmetic as it was; the device's peaks
stay there, one table for every configuration.
"""

from __future__ import annotations

def _dims(model: dict) -> tuple[int, int, int, int, int, int]:
    d, h, L = model["hidden_size"], model["intermediate_size"], model["num_hidden_layers"]
    q = model["head_dim"] * model["num_attention_heads"]
    kv = model["head_dim"] * model["num_key_value_heads"]
    return d, h, L, q, kv, model["vocab_size"]


def layer_matmul_weights(model: dict) -> int:
    """Weights in the seven matrices of all layers."""
    d, h, L, q, kv, _ = _dims(model)
    return L * (d * q + 2 * d * kv + q * d + 3 * d * h)


def decode_step_bytes(model: dict, *, rows: int, context_tokens: int, chips: int = 1,
                      kv_bytes: int = 2, scale_bytes: int = 2, head_bytes: int = 2) -> float:
    """HBM bytes ONE chip must read for one decode step of ``rows`` sequences
    whose contexts sum to ``context_tokens``: every layer matrix once as it is
    held (int8 codes + one scale per 32), the dense head, the K and V rows of
    every cached position, and the embedding rows of the step's tokens.
    Sharded ``chips`` ways, each chip reads its share; activations are noise."""
    d, _h, L, _q, kv, v = _dims(model)
    weights = layer_matmul_weights(model) * (1.0 + scale_bytes / 32.0)
    head = v * d * head_bytes
    cache = 2 * L * kv * kv_bytes * context_tokens
    return (weights + head + cache) / chips + rows * d * 2


def decode_step_flops(model: dict, *, rows: int, context_tokens: int, chips: int = 1) -> float:
    d, _h, L, q, _kv, v = _dims(model)
    return (2.0 * rows * (layer_matmul_weights(model) + v * d) + 4.0 * L * q * context_tokens) / chips


def prefill_chunk_flops(model: dict, *, chunk: int, context_before: int, chips: int = 1) -> float:
    """FLOPs one chip needs for a prefill chunk of ``chunk`` tokens that sit
    after ``context_before`` cached ones: the layer matmuls, causal attention
    over what each token may see, and no head (a prefill needs no logits; a
    program that computes them anyway is charged for its time, not credited
    with the work)."""
    d, _h, L, q, _kv, _v = _dims(model)
    attended = chunk * context_before + chunk * (chunk + 1) / 2.0
    return (2.0 * chunk * layer_matmul_weights(model) + 4.0 * L * q * attended) / chips


def prefill_chunk_bytes(model: dict, *, chunk: int, context_before: int, chips: int = 1,
                        kv_bytes: int = 2, scale_bytes: int = 2) -> float:
    d, _h, L, _q, kv, _v = _dims(model)
    weights = layer_matmul_weights(model) * (1.0 + scale_bytes / 32.0)
    cache = 2 * L * kv * kv_bytes * (context_before + chunk)
    return (weights + cache) / chips
