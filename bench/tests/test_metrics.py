"""The readers of the program's spans and scopes (``bench/metrics/``), on a
run made by hand: a traced window of four steps, the program's spans, and
the scope times that ``bench.harness.reduce_trace`` puts in
``run.scopes``."""
import importlib
import json
import types
from pathlib import Path

import pytest

from bench import scopes, trace

ROOT = Path(__file__).resolve().parents[2]


def _run(traced=True):
    steps = [("bench.step", i * 250e6, (i + 1) * 250e6 - 1e6)
             for i in range(4)]
    red = trace.Reduced(ops={0: [(0, 10, "fusion")]}, spans=steps)
    spans = [scopes.Span("edl.step", 0, 100e6, {}, "main"),
             scopes.Span("edl.step.wait", 10e6, 90e6, {}, "main"),
             scopes.Span("edl.adjust.move", 300e6, 350e6, {"adj": 1}, "main"),
             scopes.Span("edl.adjust.ready", 400e6, 420e6, {"adj": 1},
                         "main")]
    return types.SimpleNamespace(
        reduced=red if traced else None, traced=(0.0, 1.0), spans=spans,
        scopes={"attention": 0.8, "mlp": 0.2, "head_loss": 0.0,
                "optimizer": 0.04, "unscoped": 0.01, "containers": 0.0,
                "busy": 1.05})


def _read(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


@pytest.mark.parametrize("name,want", [
    ("attention_ms", 200.0), ("mlp_ms", 50.0), ("optimizer_ms", 10.0),
    ("head_loss_ms", None),             # a scope that ran no op
    ("step_host_ms", 20.0),             # 100 ms less the 80-ms wait
    ("adjust_move_ms", 120.0),          # move's start to ready's end
])
def test_program_readings_per_traced_step(name, want):
    got = _read(name, _run())
    assert got == (None if want is None else pytest.approx(want))


def test_every_per_layer_metric_reads_nothing_from_an_untraced_run():
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    run = _run(traced=False)
    run.adjustments, run.held, run.peak_flops = [], [], 1.0
    for m in spec["per_layer"]:
        assert _read(m["name"], run) is None, m["name"]
