"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(bf16_flop_per_s=197e12, int8_op_per_s=394e12,
                        hbm_bytes=16e9, hbm_bytes_per_s=819e9),
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
