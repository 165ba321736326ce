"""Fig 7 — performance under static parallelism: the elasticity layer
(RPC-ish coordination, dynamic data pipeline, per-step notify_batch_end) must
cost ~nothing vs a plain synchronous jit loop (the Horovod analogue)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, make_trainer, save


def plain_loop_throughput(p: int, steps: int, *, batch=8, seq=64) -> float:
    """Horovod-analogue: static data-parallel jit loop, pre-sharded data."""
    import jax
    from repro.configs import get_config
    from repro.core.elastic_runtime import jit_step
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw
    from repro.training.step import init_train_state
    cfg = get_config("edl-paper", smoke=True)
    opt = adamw(1e-3)
    mesh = make_mesh(p, 1)
    # AOT-compiled executable — the identical execution path EDL uses, so
    # the measured delta is exactly the elasticity layer's overhead
    fn, args, st_sh, b_sh = jit_step(cfg, opt, mesh, seq_len=seq,
                                     global_batch=batch)
    with jax.set_mesh(mesh):
        fn = fn.lower(*args).compile()
    state = jax.device_put(init_train_state(cfg, opt, jax.random.PRNGKey(0)),
                           st_sh)
    bt = {"tokens": np.random.randint(0, cfg.vocab, (batch, seq), np.int32),
          "labels": np.random.randint(0, cfg.vocab, (batch, seq), np.int32)}
    bt = jax.device_put(bt, b_sh)
    state, m = fn(state, bt)        # warm
    jax.block_until_ready(m["loss"])
    t0 = time.monotonic()
    for _ in range(steps):
        state, m = fn(state, bt)
        jax.block_until_ready(m["loss"])
    return steps * batch / (time.monotonic() - t0)


def run(steps: int = 30):
    rows = {}
    for p in (1, 2, 4):
        plain = plain_loop_throughput(p, steps)
        tr = make_trainer(p)
        tr.run(5)                  # warm
        t0 = time.monotonic()
        tr.run(steps)
        edl = steps * tr.global_batch / (time.monotonic() - t0)
        rows[p] = {"edl": edl, "plain": plain, "ratio": edl / plain}
        emit(f"fig7_static_p{p}", 1e6 / edl,
             f"edl/horovod-throughput-ratio={edl / plain:.3f}")
    save("static_parallelism", rows)
    return rows


if __name__ == "__main__":
    run()
