"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A kind that is not here is an error."""

from __future__ import annotations

PEAKS: dict[str, dict] = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}")
    return PEAKS[device_kind]
