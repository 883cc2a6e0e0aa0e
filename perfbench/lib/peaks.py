"""Published peaks of the devices the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

_V5E = {"hbm_bytes": 16 * 10**9, "hbm_gbps": 819.0,
        "bf16_tflops": 197.0, "int8_tops": 393.0}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       f"table ({sorted(PEAKS)})")
    return PEAKS[device_kind]
