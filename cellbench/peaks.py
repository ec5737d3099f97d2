"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect. Copied from ``magiattention_tpu/benchmarking/perf_report.py:
DEVICE_PEAKS`` so that no later change to the program moves the yardstick.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16.0e9,
        "ici_bits_per_s": 1600.0e9,
    },
}


def peaks_for(device_kind: str) -> dict[str, float]:
    """The peaks of the device a run is on; raises on an unknown kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}: add it to "
            f"cellbench/peaks.py with its source (known: "
            f"{sorted(DEVICE_PEAKS)})"
        ) from None
