"""Production mesh construction.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run needs to set XLA_FLAGS before the first jax
device query; see launch/dryrun.py).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks, for roofline and utilization figures."""
    flops_bf16: float           # FLOP/s
    hbm_bw: float               # bytes/s
    ici_bw: float               # bytes/s per link
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        ici_bw=50e9,            # 1,600 Gbit/s over four links
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of one chip kind; a kind without published peaks here is
    an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(data: int, model: int = 1, pod: int = 1, devices=None):
    """A (pod?, data, model) mesh over an explicit device list — the elastic
    runtime builds these as the ``data`` axis grows/shrinks."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = pod * data * model
    assert devices.size >= n, f"need {n} devices, have {devices.size}"
    devs = devices.reshape(-1)[:n]
    if pod > 1:
        return jax.sharding.Mesh(devs.reshape(pod, data, model),
                                 ("pod", "data", "model"))
    return jax.sharding.Mesh(devs.reshape(data, model), ("data", "model"))
