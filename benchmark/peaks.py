"""Device peaks: the one table every configuration is held against.

Peaks are the vendor's published numbers, keyed by ``device_kind`` as JAX
reports it. A kind that is not in the table is an error, never a default.
Copied (with the arithmetic's idea) from ``dllama_tpu/runtime/roofline.py``'s
``NAMEPLATE_SPECS``; the benchmark keeps its own so that the yardstick does
not move with the program. What a MODEL needs of the device (the bytes and
FLOPs of a decode step and a prefill chunk) is the configuration's ``counts``
module: ``counts.py`` for the dense decoders (README, "Adding things").
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


def roofline_seconds(flops: float, bytes_: float, device_kind: str) -> tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops"], bytes_ / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
