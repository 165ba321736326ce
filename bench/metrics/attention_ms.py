"""Device ms a traced step in the program's ``attention`` scope: each
layer's mixer (q, k, v and output projections, rotary embedding, scores,
softmax and values; ``models/blocks.py``), forward, recompute and
backward. Leaf ops' exclusive time, averaged over the chips
(``bench.scopes.scope_times``)."""
from bench.metrics import scope_ms


def read(run):
    return scope_ms(run, "attention")
