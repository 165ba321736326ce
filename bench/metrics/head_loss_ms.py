"""Device ms a traced step in the program's ``head_loss`` scope: the final
norm, the LM head and the chunked cross-entropy (``models/model.py``),
forward, recompute and backward. Leaf ops' exclusive time, averaged over
the chips (``bench.scopes.scope_times``)."""
from bench.metrics import scope_ms


def read(run):
    return scope_ms(run, "head_loss")
