"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
JAX reports a v5e chip as "TPU v5 lite".  A kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "int8_ops": 393e12,          # OP/s
        "hbm_bytes_per_s": 819e9,    # B/s
        "hbm_bytes": 16e9,           # B
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
