"""Records the small trace that ``test_scopes.py`` reads: two step programs
that share a module name (``jit_step``) and their instructions' names, each
a ``lax.scan`` over two named scopes, run in turn on one TPU chip. The
first puts the 512 x 512 matmul in scope ``mlp`` and the softmax in
``attention``; the second, at a wider shape, the other way round.

    python bench/tests/record_scopes.py <out_dir>

Writes ``small_scopes_trace.xplane.pb`` and ``small_scopes_hlo.json.gz``
(the two modules' ``as_text()``, in order, as a gzipped JSON list) into
``<out_dir>`` and prints each device line with its event count and the
files' sizes."""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp


def make(matmul_scope: str, softmax_scope: str):
    def step(x, w):
        def body(c, _):
            with jax.named_scope(matmul_scope):
                h = jnp.tanh(c @ w)
            with jax.named_scope(softmax_scope):
                c = jax.nn.softmax(h, axis=-1).astype(c.dtype)
            return c, None
        c, _ = jax.lax.scan(body, x, None, length=4)
        return c.sum()
    return jax.jit(step)


def main(out: str) -> int:
    first = make("mlp", "attention")
    second = make("attention", "mlp")
    args1 = (jnp.ones((512, 512), jnp.bfloat16),
             jnp.ones((512, 512), jnp.bfloat16) / 512)
    args2 = (jnp.ones((256, 1024), jnp.bfloat16),
             jnp.ones((1024, 1024), jnp.bfloat16) / 1024)
    texts = [first.lower(*args1).compile().as_text(),
             second.lower(*args2).compile().as_text()]
    first(*args1).block_until_ready()
    second(*args2).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        first(*args1).block_until_ready()
        second(*args2).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(out, exist_ok=True)
    trace = os.path.join(out, "small_scopes_trace.xplane.pb")
    shutil.copy(path, trace)
    shutil.rmtree(tmp, ignore_errors=True)
    hlo = os.path.join(out, "small_scopes_hlo.json.gz")
    with gzip.open(hlo, "wt") as f:
        json.dump(texts, f)
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(trace).planes:
        if plane.name.startswith("/device:"):
            print(plane.name, [(ln.name, sum(1 for _ in ln.events))
                               for ln in plane.lines])
    print(trace, os.path.getsize(trace), hlo, os.path.getsize(hlo))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
