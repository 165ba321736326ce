"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``. A kind that is not here is an error, never a
default: a utilization over a guessed peak is no measurement."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s of one chip
    hbm_bytes_s: float      # bytes/s of one chip's HBM
    hbm_bytes: float        # bytes of HBM on one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s per chip'),
}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
