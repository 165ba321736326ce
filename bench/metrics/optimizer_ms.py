"""Device ms a traced step in the program's ``optimizer`` scope: the
optimizer's update of parameters and moments and the gradient norm
(``training/step.py``). Leaf ops' exclusive time, averaged over the chips
(``bench.scopes.scope_times``)."""
from bench.metrics import scope_ms


def read(run):
    return scope_ms(run, "optimizer")
