"""ms by which an adjustment's state move holds up training, from the
program's spans: from the later of the move's start
(``edl.adjust.staged_reshard``, else ``edl.adjust.move``) and the end of
the draining step's ``edl.step.wait`` (the step has left the device; the
staged move queues behind it there) to the end of ``edl.adjust.ready``.
The mean over the adjustments whose spans all lie in the traced window
(``bench.scopes.adjust_move_ms``)."""
from bench import scopes
from bench.metrics import traced


def read(run):
    window = traced(run)
    return None if window is None else scopes.adjust_move_ms(run.spans,
                                                             *window)
