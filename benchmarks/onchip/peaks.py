"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation, "TPU v5e"
system architecture page — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s per chip.  A kind missing from the table is an error: a
roofline share against a guessed peak is no measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,        # bf16, the largest the chip publishes
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; KeyError naming the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the "
                       f"table holds {sorted(PEAKS)}") from None
