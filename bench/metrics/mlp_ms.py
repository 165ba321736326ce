"""Device ms a traced step in the program's ``mlp`` scope: each layer's
feed-forward (the SwiGLU MLP, or the expert layer; ``models/blocks.py``),
forward, recompute and backward. Leaf ops' exclusive time, averaged over
the chips (``bench.scopes.scope_times``)."""
from bench.metrics import scope_ms


def read(run):
    return scope_ms(run, "mlp")
