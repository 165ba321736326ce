"""Model FLOPs utilization of the traced steps: the FLOPs the steps require
(``bench.flops``) over the chip-seconds the tenant held in the traced window
(device-trace clock; a chip that release_devices gave back does not count
until grant_devices returns it) times the chip's bf16 peak."""
from bench import trace


def read(run):
    red = run.reduced
    if red is None or not red.ops or run.peak_flops <= 0:
        return None
    lo, hi = red.host_to_trace(run.traced)
    spans = [s for s in red.step_spans() if s[1] >= lo and s[2] <= hi]
    held = sum(e - s for chip in red.ops
               for s, e in trace.held_intervals(run, chip, lo, hi, red)) / 1e9
    if not spans or held <= 0:
        return None
    return 100.0 * len(spans) * run.flops_per_step / (held * run.peak_flops)
