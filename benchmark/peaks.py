"""Device peaks, and the bytes and FLOPs a decode step and a prefill chunk need.

Peaks are the vendor's published numbers, keyed by ``device_kind`` as JAX
reports it. A kind that is not in the table is an error, never a default.
Copied (with the arithmetic's idea) from ``dllama_tpu/runtime/roofline.py``'s
``NAMEPLATE_SPECS``; the benchmark keeps its own so that the yardstick does
not move with the program.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1600 Gbit/s interconnect
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e (System architecture)"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e (System architecture)"},
}


class UnknownDeviceKind(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDeviceKind(
            f"no published peaks for device kind {device_kind!r}; add a row with its source "
            f"to benchmark/peaks.py (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


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


def roofline_seconds(flops: float, bytes_: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops"], bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
