"""Per-chip peaks, keyed by the ``device_kind`` JAX reports.

The roofline stall estimator (``source.py``), HBM admission
(``runtime/memory.py``) and the bench scripts' utilization figures all
divide by what the chip could do at best. Those peaks are facts about a
device, so they come from this one table and not from a constant that
is true of one chip only: a TPU whose kind is not listed is an error,
not a default.
"""

from __future__ import annotations

import dataclasses

from pbs_tpu import knobs


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    hbm_bytes: int


DEVICE_PEAKS: dict[str, DevicePeaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s, 16 GB of HBM per chip.
    "TPU v5 lite": DevicePeaks(197e12, 819e9, 16 << 30),
}

#: The chip a platform with no published peaks (the CPU the tests run
#: on) is modeled as; the telemetry.source.* knob defaults restate it.
MODELED_KIND = "TPU v5 lite"

_KNOBS = {"flops": "telemetry.source.peak_flops",
          "hbm_bw": "telemetry.source.peak_hbm_bw"}


def device_peaks(device=None) -> DevicePeaks:
    """Peaks of ``device`` (default: the first JAX device). A knob
    moved off its default overrides the table's entry."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else MODELED_KIND
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for TPU device_kind {kind!r}; add it "
            f"to pbs_tpu.telemetry.peaks.DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    moved = {field: knobs.get(name) for field, name in _KNOBS.items()
             if knobs.get(name) != knobs.default(name)}
    return dataclasses.replace(DEVICE_PEAKS[kind], **moved)
