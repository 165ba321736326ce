"""Training step + sharding builders.

TrainState = {"params": tree, "opt": {"count", "mu"[, "nu"]}, "step": i32}.
Moments shard exactly like their parameters; the global batch dim shards over
the elastic ``(pod, data)`` axes — resizing that axis is what EDL elasticity
does, and because the global batch is constant the step math is identical at
any parallelism (tested in tests/test_elastic.py).

Two step flavours:

  * ``make_train_step(cfg, opt)`` — the default GSPMD step: one
    value_and_grad over the global batch, gradients pinned to the parameter
    shardings (ZeRO reduce-scatter). Fast, but the fp32 reduction order —
    and XLA's gemm k-blocking, which follows the per-device matrix shapes —
    depends on the device count, so two parallelisms agree only to
    float tolerance.
  * ``make_train_step(cfg, opt, n_virtual=K, mesh=..., global_batch=...,
    seed=...)`` — the DETERMINISTIC virtual-worker step (EasyScale-style,
    see docs/architecture.md "Deterministic elasticity"): the global batch
    is split into ``n_virtual`` fixed-size slices; a full-manual
    ``shard_map`` gives each device a Python loop over its contiguous block
    of virtual workers, so every per-virtual-worker forward/backward runs
    at the SAME ``(global_batch / n_virtual, seq)`` shape at every dp, and
    the loss/grad reduction is a fixed balanced binary tree over the
    virtual axis — a function of ``n_virtual`` alone. Per-virtual-worker
    RNG keys (``fold_in(fold_in(key(seed), vw), step)``) make dropout/noise
    shape-independent too. Result: bitwise-identical loss trajectories and
    parameters across every (dp, mp), at the cost of replicating the
    params across the mesh inside the step (deterministic mode trades the
    ZeRO reduce-scatter and model-axis sharding for reproducibility).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.optim import Optimizer
from repro.sharding import spec_for


def init_train_state(cfg, optimizer: Optimizer, key) -> dict:
    params = M.init_params(cfg, key)
    return {"params": params, "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32)}


def _global_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def _vw_tree_reduce(x):
    """Fixed balanced binary-tree sum over the leading (virtual-worker)
    axis. The pairing order is a pure function of ``x.shape[0]`` —
    never of the device mesh — so fp32 accumulation is bitwise-stable
    across every parallelism."""
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        even, odd = x[0:2 * half:2], x[1:2 * half:2]
        x = jnp.concatenate([even + odd, x[2 * half:]], axis=0)
    return x[0]


def make_train_step(cfg, optimizer: Optimizer, use_pallas: bool = False, *,
                    n_virtual: int = 0, mesh: Mesh | None = None,
                    global_batch: int = 0, seed: int = 0):
    """Build the train step. With ``n_virtual > 0`` (requires ``mesh`` and
    ``global_batch``) the deterministic virtual-worker step is built
    instead of the default GSPMD step — see the module docstring."""
    if n_virtual:
        assert mesh is not None and global_batch, \
            "virtual-worker step needs mesh + global_batch"
        return _make_virtual_train_step(cfg, optimizer, n_virtual, mesh,
                                        global_batch, seed, use_pallas)

    def train_step(state, batch):
        def lf(p):
            return M.loss_fn(cfg, p, batch, use_pallas=use_pallas)
        (loss, parts), grads = jax.value_and_grad(lf, has_aux=True)(
            state["params"])
        # pin gradient shardings to the parameter shardings: the data-axis
        # reduction lowers as reduce-scatter (ZeRO) instead of all-reduce
        from repro.models.model import param_logical_axes
        from repro.sharding import constrain
        axes = param_logical_axes(cfg)
        grads = jax.tree.map(
            lambda g, a: constrain(g, a), grads, axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
            gnorm = _global_norm(grads)
        metrics = {"loss": loss, "xent": parts["xent"], "aux": parts["aux"],
                   "grad_norm": gnorm}
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    return train_step


def _make_virtual_train_step(cfg, optimizer: Optimizer, n_virtual: int,
                             mesh: Mesh, global_batch: int, seed: int,
                             use_pallas: bool):
    from repro.models.model import param_logical_axes
    from repro.sharding import constrain, manual_region
    dp = mesh.shape["data"] * mesh.shape.get("pod", 1)
    if n_virtual % dp:
        raise ValueError(f"n_virtual={n_virtual} not divisible by data "
                         f"parallelism {dp}")
    if global_batch % n_virtual:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"n_virtual={n_virtual}")
    local = n_virtual // dp         # virtual workers per device
    per = global_batch // n_virtual  # samples per virtual worker
    axes_tree = param_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(e, (str, type(None))) for e in x)

    def train_step(state, batch):
        def body(params, step_no, lbatch):
            # this device's contiguous virtual-worker block: [vw0, vw0+local)
            vw0 = jax.lax.axis_index("data") * local
            outs = []
            for i in range(local):
                vb = {k: v[i * per:(i + 1) * per] for k, v in lbatch.items()}
                # per-(virtual worker, step) RNG: dropout/noise depend on
                # the virtual worker's identity, never on which device
                # hosts it or how many devices exist
                vw_key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(seed), vw0 + i),
                    step_no)

                def lf(p, key=vw_key, b=vb):
                    # manual_region: per-device values carry no mesh axes,
                    # so the model's sharding annotations must no-op here
                    with manual_region():
                        return M.loss_fn(cfg, p, b, use_pallas=use_pallas,
                                         rng=key)
                (loss, parts), grads = jax.value_and_grad(
                    lf, has_aux=True)(params)
                outs.append((loss, parts["xent"], parts["aux"], grads))
            losses = jnp.stack([o[0] for o in outs])
            xents = jnp.stack([o[1] for o in outs])
            auxes = jnp.stack([o[2] for o in outs])
            grads = jax.tree.map(lambda *g: jnp.stack(g),
                                 *[o[3] for o in outs])
            return losses, xents, auxes, grads

        # Full-manual shard_map over BOTH mesh axes: params replicate
        # (in_spec P()), every device computes its virtual workers at the
        # fixed (per, seq) shape, per-vw results come back stacked over the
        # virtual axis. check_vma=False: the replicated-params claim is
        # ours, not inferrable. (Partial-auto over the model axis is not
        # supported by this XLA; deterministic mode therefore replicates
        # model-axis compute too — the documented cost of vw mode.)
        pspec = jax.tree.map(lambda _: P(), state["params"])
        bspec = {k: P("data") for k in batch}
        gspec = jax.tree.map(lambda _: P("data"), state["params"])
        losses, xents, auxes, grads = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P(), bspec),
            out_specs=(P("data"), P("data"), P("data"), gspec),
            check_vma=False)(state["params"], state["step"], batch)

        # fixed virtual-order tree reduction: the ONLY cross-device sum,
        # and its order is a function of n_virtual alone
        loss = _vw_tree_reduce(losses) / n_virtual
        xent = _vw_tree_reduce(xents) / n_virtual
        aux = _vw_tree_reduce(auxes) / n_virtual
        grads = jax.tree.map(lambda g: _vw_tree_reduce(g) / n_virtual, grads)
        grads = jax.tree.map(lambda g, a: constrain(g, a), grads, axes_tree,
                             is_leaf=is_axes)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
            # grad_norm is diagnostic-only: its leaf-internal reductions
            # follow the sharded layout, so it is NOT part of the bitwise
            # contract
            gnorm = _global_norm(grads)
        metrics = {"loss": loss, "xent": xent, "aux": aux,
                   "grad_norm": gnorm}
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    return train_step


# ------------------------------------------------------------- shardings
def params_sharding(cfg, mesh: Mesh):
    axes = M.param_logical_axes(cfg)
    shapes = M.param_shape_structs(cfg)
    return jax.tree.map(
        lambda a, s: NamedSharding(mesh, spec_for(a, s.shape, mesh)),
        axes, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def state_sharding(cfg, mesh: Mesh, optimizer: Optimizer) -> dict:
    ps = params_sharding(cfg, mesh)
    repl = NamedSharding(mesh, P())
    opt = {"count": repl, "mu": ps}
    if optimizer.slots >= 2:
        opt["nu"] = ps
    return {"params": ps, "opt": opt, "step": repl}


def state_shape_structs(cfg, optimizer: Optimizer) -> dict:
    """Abstract TrainState for AOT lowering (no allocation)."""
    p = M.param_shape_structs(cfg)
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    opt = {"count": i32, "mu": jax.tree.map(f32, p)}
    # default optimizer assumed adamw (2 slots) for the dry-run
    opt["nu"] = jax.tree.map(f32, p)
    return {"params": p, "opt": opt, "step": i32}


def batch_sharding(cfg, mesh: Mesh, batch_specs: dict,
                   cache_shape: tuple[int, int] | None = None) -> dict:
    """Shardings for a model-input dict. ``cache_shape=(batch, max_seq)`` must
    be given when the dict contains a decode cache."""
    def one(spec):
        axes = ("batch",) + (None,) * (len(spec.shape) - 1)
        return NamedSharding(mesh, spec_for(axes, spec.shape, mesh))

    out = {}
    for k, v in batch_specs.items():
        if k == "cache":
            assert cache_shape is not None
            out[k] = cache_sharding(cfg, mesh, *cache_shape)
        else:
            out[k] = one(v)
    return out


def cache_sharding(cfg, mesh: Mesh, batch: int, max_seq: int):
    from repro.models.cache import cache_logical_axes, cache_specs
    axes = cache_logical_axes(cfg, batch, max_seq)
    specs = cache_specs(cfg, batch, max_seq)
    return jax.tree.map(
        lambda a, s: NamedSharding(mesh, spec_for(a, s.shape, mesh)),
        axes, specs,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
