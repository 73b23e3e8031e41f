"""Published peaks of one chip, keyed by JAX's device_kind. A device that is
not here is an error, never a default: a share of a guessed peak is worse
than no number."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
    # 819 GB/s, 1600 Gbit/s chip-to-chip interconnect.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no published peaks for device_kind {device_kind!r}; add them "
            f"with their source to benchmarks/harness/peaks.py") from None
