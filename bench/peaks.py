"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not in the table is an error."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
                  "819 GB/s HBM bandwidth, 16 GB HBM per chip",
    },
}


def peak(kind: str) -> dict:
    """The peaks of ``kind``; raises ``KeyError`` for an unknown chip."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[kind]
