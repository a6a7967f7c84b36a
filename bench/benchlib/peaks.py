"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.  The MFU
denominator is the bf16 peak: the program's float32 matmuls run at JAX's
default precision, which on the TPU is bf16 passes on the MXU.  A device
kind missing here is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(f"bench: no peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
