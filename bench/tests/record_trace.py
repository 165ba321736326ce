"""Records the small trace that ``test_trace.py`` reads: three annotated
steps of a small jitted program on one TPU chip, the way the benchmark's
step loop annotates its steps.

    python bench/tests/record_trace.py <out_dir>

Prints each plane with its lines and event counts, and the path of the
``.xplane.pb`` written under ``<out_dir>``."""
import glob
import os
import sys

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("bench.step", step_num=i):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.request.test"):
            pass
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(plane.name, [(ln.name, sum(1 for _ in ln.events))
                           for ln in plane.lines])
    print(path, os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
