"""The comparison that decides ``correct``.

The program's first ``check_steps`` steps (set-up drives them through the
window's own loop and adjustments) are followed by the plain reference that
the configuration names (``bench/reference/<module>.py``) from the same
seeded weights and the same rows. The
numbers compared, each a share:

  loss_gap     the largest |program loss - reference loss| / reference loss
               over the check steps;
  grad_gap     the worst leaf's |program norm - reference norm| of the first
               gradient (the program's read from its Adam state after one
               step, mu / (1 - b1)), over the larger of the reference leaf's
               norm and the median leaf's;
  change_gap   the same for the parameters' change over the check steps,
               leaving out leaves whose reference gradient is under a
               thousandth of the median leaf's (Adam moves those by
               round-off alone);
  duplicate_samples  sample ids served twice within the run: the data
               pipeline's exactly-once accounting (exact: limit 0).

Limits live in ``bench/limits/<workload>.json`` with the readings they were
set from; a cell compares the numbers its file names (a number with no
upper reading there is not compared, and is logged). A cell without one is
never correct.
"""
from __future__ import annotations

import gc
import json
import statistics
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def limits_for(workload: str) -> dict | None:
    path = BENCH / "limits" / f"{workload}.json"
    if not path.exists():
        return None
    with open(path) as f:
        return {k: v["limit"] for k, v in json.load(f)["limits"].items()}


def _mesh_shardings(shapes: dict, devices, batch: int):
    """Reference placement over the cell's chips: each leaf split along its
    largest dimension that the chip count divides, the batch by rows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import weights
    n = len(devices)
    if n == 1:
        return None, None
    mesh = Mesh(np.asarray(devices), ("x",))

    def spec(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0:
                return P(*[("x" if j == i else None)
                           for j in range(len(shape))])
        return P()
    sh = weights.nest({k: NamedSharding(mesh, spec(s))
                       for k, s in shapes.items()})
    bsh = NamedSharding(mesh, P("x") if batch % n == 0 else P())
    return sh, bsh


def reference_run(config: dict, traffic: dict, seed: int, rows, check_ids,
                  devices, ein: str = "exact", loss_rows: int | None = None
                  ) -> dict:
    """The reference (or, with ``ein="fp8"``, the control; with
    ``loss_rows``, a fault planted in it) over the check steps."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    from bench.reference import for_config
    ref = for_config(config)
    shapes = ref.param_shapes(config)
    sh, bsh = _mesh_shardings(shapes, devices, traffic["global_batch"])
    dtype = jnp.dtype(config["param_dtype"])
    std = config["initializer_range"]
    params = weights.make(shapes, seed, std, dtype, sh)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=sh)
    m, v = zeros(params), zeros(params)
    step = ref.make_step(config, traffic["optimizer"], ref.EINSUMS[ein],
                         loss_rows, sh, bsh)
    losses, grad_norms = [], None
    for k, ids in enumerate(check_ids):
        b = rows.read_ids(ids)
        toks, labs = (jax.device_put(jnp.asarray(b[x]), bsh)
                      if bsh is not None else jnp.asarray(b[x])
                      for x in ("tokens", "labels"))
        params, m, v, lval, gn = step(params, m, v, jnp.int32(k + 1),
                                      toks, labs)
        losses.append(float(lval))
        if k == 0:
            grad_norms = {kk: float(x) for kk, x in
                          weights.flatten(gn).items()}
    del m, v
    change = weights.change_norms(params, shapes, seed, std, dtype)
    del params
    gc.collect()
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The three shares of one run against the reference, with the worst
    leaf of each (for the log)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    rg, pg = ref["grad_norms"], prog["grad_norms"]
    g_med = statistics.median(rg.values())
    grad = {k: abs(pg[k] - rg[k]) / max(rg[k], g_med) for k in rg}
    moved = [k for k in rg if rg[k] >= 1e-3 * g_med]
    rc, pc = ref["change_norms"], prog["change_norms"]
    c_med = statistics.median(rc[k] for k in moved)
    change = {k: abs(pc[k] - rc[k]) / max(rc[k], c_med) for k in moved}
    gk = max(grad, key=grad.get)
    ck = max(change, key=change.get)
    return {"loss_gap": loss_gap, "grad_gap": grad[gk],
            "change_gap": change[ck], "worst_grad_leaf": gk,
            "worst_change_leaf": ck,
            "left_out": sorted(set(rg) - set(moved))}


def compare(config, traffic, cell, seed, rows, check_ids, prog, devices,
            duplicates: int) -> dict:
    """The numbers that the cell's limits file names, each beside its
    limit; the others are logged with the reference's losses."""
    ref = reference_run(config, traffic, seed, rows, check_ids, devices)
    g = gaps(prog, ref)
    lim = limits_for(cell["name"]) or {}
    values = {"loss_gap": g["loss_gap"], "grad_gap": g["grad_gap"],
              "change_gap": g["change_gap"],
              "duplicate_samples": duplicates}
    numbers = {k: {"value": values[k], "limit": v} for k, v in lim.items()}
    ok = bool(numbers) and all(n["value"] <= n["limit"]
                               for n in numbers.values())
    log = {"program_losses": prog["losses"], "reference_losses": ref["losses"],
           "not_compared": {k: v for k, v in values.items()
                            if k not in numbers},
           **{k: g[k] for k in ("worst_grad_leaf", "worst_change_leaf",
                                "left_out")}}
    print(f"bench reference {json.dumps(log)}", flush=True)
    return {"correct": ok, "numbers": numbers}
