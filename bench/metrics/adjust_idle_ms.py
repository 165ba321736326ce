"""Device idle time of an adjustment, in ms: on each chip held both before
and after it, the time in which no op ran between the adjustment's request
and the return of the first step on the new allocation (the draining step,
the commit and its transfers, the first new step); the mean over those
chips and over the adjustments that lie in the traced window."""
from bench import trace


def read(run):
    red = run.reduced
    if red is None or not red.ops:
        return None
    lo, hi = red.host_to_trace(run.traced)
    per_adj = []
    for a in run.adjustments:
        if a.t_done is None:
            continue
        s, e = red.host_to_trace((a.t_request, a.t_done))
        if s < lo or e > hi:
            continue
        chips = _held_at(run, a.t_request) & _held_at(run, a.t_done)
        idle = [(e - s - trace.union(red.ops.get(c, []), s, e)) / 1e6
                for c in sorted(chips) if c in red.ops]
        if idle:
            per_adj.append(sum(idle) / len(idle))
    return sum(per_adj) / len(per_adj) if per_adj else None


def _held_at(run, t):
    ids = run.held[0][1]
    for tt, held in run.held:
        if tt <= t:
            ids = held
    return set(ids)
