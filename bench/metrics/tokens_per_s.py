"""Tokens trained in the window over the window's seconds (host clock, from
the window's start to the return of its last step). Adjustments and their
switches lie inside the window, so where a cell adjusts this is goodput."""


def read(run):
    t0, t1 = run.window
    if not run.steps or t1 <= t0:
        return None
    return sum(s.tokens for s in run.steps) / (t1 - t0)
