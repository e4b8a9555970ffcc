"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip
interconnect per chip. A kind that is not listed is an error.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12,      # FLOP/s
                    "hbm_bytes_s": 819e9,      # bytes/s
                    "ici_bytes_s": 1600e9 / 8},  # bytes/s per chip
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; listed: {sorted(PEAKS)}"
                         ) from None
