"""Share of the traced window in which no op ran on a chip the tenant held:
1 - union of device-op intervals / held time, averaged over the chips."""
from bench import trace


def read(run):
    red = run.reduced
    if red is None or not red.ops:
        return None
    lo, hi = red.host_to_trace(run.traced)
    busy, window = trace.busy_and_window(run, lo, hi)
    return None if window <= 0 else 100.0 * (1.0 - busy / window)
