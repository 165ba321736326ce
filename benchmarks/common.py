"""Shared helpers for the benchmark harness.

Each benchmark reproduces one paper table/figure on the smoke-scale workload
on forced CPU host devices. Its wall-clock numbers time XLA's CPU backend:
they bear on the paper's *relative* claims (EDL vs stop-resume ratios) at
best, and say nothing about a TPU. No TPU number comes from these scripts;
``chip_smoke.py`` is the only code here that has run on a chip.
"""
from __future__ import annotations

import json
import os
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments")


def emit(name: str, us_per_call: float, derived: str):
    """The harness contract: ``name,us_per_call,derived`` CSV on stdout."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def save(name: str, payload: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"bench_{name}.json"), "w") as f:
        json.dump(payload, f, indent=1)


def make_trainer(p: int = 2, *, batch: int = 8, seq: int = 64,
                 arch: str = "edl-paper", **kw):
    from repro.configs import get_config
    from repro.core import ElasticTrainer
    from repro.optim import adamw
    cfg = get_config(arch, smoke=True)
    return ElasticTrainer(cfg, global_batch=batch, seq_len=seq,
                          init_parallelism=p, optimizer=adamw(1e-3),
                          n_samples=1 << 12, d_partitions=32, **kw)
