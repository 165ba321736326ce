"""Whole runs at a small size on the CPU (the harness's look for a chip
skipped), with the timed path broken underneath: each fault a cell can have
must come out as not correct; and the control (the reference in float8 in
the program's place) must fail one of the numbers a cell compares.

Faults are planted in the program's step factory before the trainer is
built: a step that returns its state unchanged; half of the batch left out,
the mean taken over the rest; and, where the cell spans chips, the gradient
exchange left out (each chip updates its shard of the parameters from the
gradient of its own rows)."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.tests.small import small_parts

SEED = (1 << 33) + 12345


def _run(workload, seed=SEED, **mix):
    from bench import harness
    cell, config, traffic = small_parts(workload)
    return harness.run_cell(workload, seed, 0.5, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            parts=(cell, config, dict(traffic, **mix)))


def _plant(monkeypatch, fault):
    import repro.core.elastic_runtime as er
    real = er.make_train_step

    def factory(cfg, optimizer, use_pallas=False, **kw):
        return fault(real(cfg, optimizer, use_pallas, **kw), cfg, optimizer,
                     kw)
    monkeypatch.setattr(er, "make_train_step", factory)


def state_unchanged(step, cfg, optimizer, kw):
    def broken(state, batch):
        return state, step(state, batch)[1]
    return broken


def half_batch(step, cfg, optimizer, kw):
    def broken(state, batch):
        def first_half_twice(x):
            h = x.shape[0] // 2
            return jnp.concatenate([x[:h], x[:h]], axis=0)
        return step(state, jax.tree.map(first_half_twice, batch))
    return broken


def no_exchange(step, cfg, optimizer, kw):
    from repro.models import model as M
    from repro.sharding import manual_region
    from repro.training.step import params_sharding
    mesh = kw["mesh"]
    n = mesh.shape["data"]
    shardings = params_sharding(cfg, mesh)

    def own_shard(g, sh):
        """Chip d's shard of the parameter takes chip d's gradient."""
        for dim, ax in enumerate(sh.spec):
            if "data" in (ax if isinstance(ax, tuple) else (ax,)):
                size = g.shape[dim + 1] // n
                return jnp.concatenate(
                    [jax.lax.slice_in_dim(g[d], d * size, (d + 1) * size,
                                          axis=dim) for d in range(n)],
                    axis=dim)
        return g[0]

    def broken(state, batch):
        rows = jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

        def lf(p, b):
            with manual_region():
                return M.loss_fn(cfg, p, b)[0]
        losses, grads = jax.vmap(jax.value_and_grad(lf), in_axes=(None, 0))(
            state["params"], rows)
        grads = jax.tree.map(own_shard, grads, shardings)
        params, opt = optimizer.update(grads, state["opt"], state["params"])
        zero = jnp.zeros((), jnp.float32)
        return ({"params": params, "opt": opt, "step": state["step"] + 1},
                {"loss": losses[0], "xent": losses[0], "aux": zero,
                 "grad_norm": zero})
    return broken


FAULTS = [("phi3-l2.steady", state_unchanged),
          ("phi3-l2.steady", half_batch),
          ("nemo-l2.elastic", state_unchanged),
          ("nemo-l2.elastic", half_batch),
          ("nemo-l2.elastic", no_exchange)]


@pytest.mark.parametrize("workload", ["phi3-l2.steady", "nemo-l2.elastic"])
def test_sound_run_reads_far_below_the_faults(workload):
    """The limits are set for the cells' own sizes on the chip; at this
    size the sound readings only have to lie far below what the faults
    read (a tenth and more, see the fault readings in PERF.md)."""
    r = _run(workload)
    checks = r["checks"]
    assert checks["duplicate_samples"]["value"] == 0
    for name in ("grad_gap", "change_gap"):
        if name in checks:
            assert checks[name]["value"] < 5e-3, checks


@pytest.mark.parametrize("workload,mix", [
    ("nemo-l2.elastic", {"prefetch": False}),
    ("phi3-l2.steady", {"virtual_workers": 2}),
])
def test_mix_options_run_sound(workload, mix):
    """A shape left to the trainer's own path (no prefetch), and the
    virtual-worker step, run and compare sound at a small size."""
    r = _run(workload, **mix)
    assert r["correct"] or all(
        n["value"] < 5e-3 for k, n in r["checks"].items()
        if k != "duplicate_samples"), r["checks"]
    assert r["checks"]["duplicate_samples"]["value"] == 0


@pytest.mark.parametrize("workload,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, f in FAULTS])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    _plant(monkeypatch, fault)
    r = _run(workload)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", ["phi3-l2.steady", "nemo-l2.elastic"])
def test_fp8_control_is_not_correct(workload):
    """The control, the reference in float8 put in the program's place, at
    a small size: one of the numbers the cell compares fails its limit."""
    from bench import check
    from bench.data import TokenRows
    cell, config, traffic = small_parts(workload)
    rows = TokenRows(traffic["n_samples"], traffic["seq_len"],
                     config["vocab_size"], SEED)
    b = traffic["global_batch"]
    ids = [list(range(k * b, (k + 1) * b))
           for k in range(traffic["check_steps"])]
    devices = jax.devices()[:cell["chips"]]
    run = dict(config=config, traffic=traffic, seed=SEED, rows=rows,
               check_ids=ids, devices=devices)
    gaps = check.gaps(check.reference_run(**run, ein="fp8"),
                      check.reference_run(**run))
    limits = check.limits_for(workload)
    assert any(gaps[k] > v for k, v in limits.items() if k in gaps), gaps
