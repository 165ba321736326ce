"""The 95th percentile of every window step's wall time, in ms: host clock
from the call of ``ElasticTrainer.step()`` to its return, which waits for
the step's loss on the device. Python's inclusive quantiles."""
import statistics


def read(run):
    if len(run.steps) < 2:
        return None
    ms = sorted((s.t1 - s.t0) * 1e3 for s in run.steps)
    return statistics.quantiles(ms, n=100, method="inclusive")[94]
