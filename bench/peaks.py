"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A device that is not in the table is an error:
a roofline share against a guessed peak is no measurement.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)}): add them with their source")
    return PEAKS[device_kind]
