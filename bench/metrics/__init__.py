"""One reader per metric, found by the metric's name in BENCHMARK.json:
``read(run)`` returns the number, or None where the run holds nothing to
read (the harness then leaves the metric out of the line).

A named scope's reader is one line over ``scope_ms``: a scope that a
configuration file names under ``"scopes"`` is read by a file
``<metric>.py`` here with ``def read(run): return scope_ms(run, "<scope>")``.
"""


def traced(run) -> tuple | None:
    """(lo, hi): the traced window on the trace's clock, or None where the
    run was not traced."""
    red = run.reduced
    if red is None or not red.ops:
        return None
    return red.host_to_trace(run.traced)


def traced_steps(run) -> list:
    """The ``bench.step`` spans that lie in the traced window."""
    window = traced(run)
    if window is None:
        return []
    lo, hi = window
    return [s for s in run.reduced.step_spans() if s[1] >= lo and s[2] <= hi]


def scope_ms(run, scope: str) -> float | None:
    """Device ms of ``scope`` a traced step: ``run.scopes[scope]`` (leaf
    ops' exclusive seconds, averaged over the chips) over the traced steps.
    None where the scope ran no op."""
    steps = traced_steps(run)
    if not steps or not run.scopes.get(scope):
        return None
    return 1e3 * run.scopes[scope] / len(steps)
