"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

The benchmark's own copy (the program's is ``pbs_tpu/telemetry/peaks.py``):
a later PR cannot move a roofline share by editing the program's table.
A device that is not listed is an error, not a default.
"""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
#: HBM bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
