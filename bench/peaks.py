"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over
four links (50 GB/s per link).  A device kind missing from the table is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 50e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
