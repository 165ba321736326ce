"""Host ms of a traced step outside the device wait: the mean over the
program's ``edl.step`` spans in the traced window of the span less what
its ``edl.step.wait`` and ``edl.adjust.*`` spans cover (batch, put,
dispatch and bookkeeping; ``bench.scopes.step_host_ms``)."""
from bench import scopes
from bench.metrics import traced


def read(run):
    window = traced(run)
    return None if window is None else scopes.step_host_ms(run.spans,
                                                           *window)
