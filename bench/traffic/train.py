"""The driver of training traffic (``"kind": "train"``): one tenant built as
the program's trainer is built, stepped back to back, with the mix's cycle
of adjustments (``release``, ``grant``, ``reshape``) requested between
steps. Every ``bench/traffic/*.json`` of this kind is read here.

Mix keys (``KEYS``; ``REQUIRED`` has no default):
  global_batch, seq_len     rows a step and tokens a row;
  start                     the (p, mp) shape the tenant starts on;
  n_samples, d_partitions   the data pipeline's rows and partitions;
  optimizer                 ``{"name": "adamw", lr, b1, b2, eps,
                            weight_decay}``; the name must be in
                            ``OPTIMIZERS``;
  cycle                     adjustments requested in turn, each
                            ``{"op": ..., "to": [p, mp]}``;
  steps_between             steps after each adjustment is done before the
                            next request;
  check_steps               set-up's steps that the reference follows (they
                            request the cycle's adjustments back to back);
  time_allowance_s          the trainer's switch allowance (0, as the
                            cluster executor's tenants run);
  prefetch                  build every shape of the cycle in set-up, as the
                            executor's speculative prefetch does; false
                            leaves each to the trainer's own path when its
                            adjustment asks, in the check steps and again
                            in the window (shapes built by the check steps
                            are dropped before it);
  virtual_workers           the trainer's deterministic-elasticity workers
                            (null: off).

The reference that the check follows is the configuration's own
(``"reference"`` in its file, a module of ``bench/reference/``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import time

from bench.harness import REQUIRED, Adjustment, Step, log

KEYS = {"global_batch": REQUIRED, "seq_len": REQUIRED, "start": REQUIRED,
        "n_samples": REQUIRED, "d_partitions": REQUIRED,
        "optimizer": REQUIRED, "cycle": [], "steps_between": 0,
        "check_steps": REQUIRED, "time_allowance_s": 0.0, "prefetch": True,
        "virtual_workers": None}
OPS = ("release", "grant", "reshape")


def _adamw(o):
    from repro.optim import adamw
    return adamw(o["lr"], o["b1"], o["b2"], o["eps"], o["weight_decay"])


OPTIMIZERS = {"adamw": _adamw}


def optimizer(traffic: dict):
    o = traffic["optimizer"]
    if o.get("name") not in OPTIMIZERS:
        raise ValueError(f"optimizer {o.get('name')!r} is not one of "
                         f"{sorted(OPTIMIZERS)}")
    return OPTIMIZERS[o["name"]](o)


def reference(config: dict):
    from bench.reference import for_config
    return for_config(config)


_ABSENT = type("Absent", (), {"__repr__": lambda self: "absent"})()


def _get(obj, path: str):
    """The program configuration's field at a dotted path ("moe.top_k");
    ``_ABSENT`` where a block on the way is None."""
    for name in path.split("."):
        if obj is None:
            return _ABSENT
        obj = getattr(obj, name)
    return obj


def _replace(obj, values: dict):
    """``obj`` with the fields at the dotted paths of ``values`` replaced,
    inside nested blocks too."""
    top, nested = {}, {}
    for path, v in values.items():
        head, _, rest = path.partition(".")
        if rest:
            nested.setdefault(head, {})[rest] = v
        else:
            top[path] = v
    for head, sub in nested.items():
        block = getattr(obj, head)
        if block is None:
            raise ValueError(f"program config {obj.name} has no {head} to "
                             f"set {sorted(sub)} in")
        top[head] = _replace(block, sub)
    return dataclasses.replace(obj, **top)


def _flat(d: dict, prefix: str = "") -> dict:
    """A file's nested dicts as dotted paths; a null block stays whole."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = v
    return out


def program_config(config: dict):
    """The program's ArchConfig for a configuration file: the program's
    own published configuration with the file's cuts and dtypes, checked
    width by width against the file, and against the architecture that the
    file's ``program_arch`` states, field by field into nested blocks
    (``"moe": {...}``).

    The reference module's ``APPLIED`` maps the program fields that the
    file sets, dotted into nested blocks ("moe.n_experts"), to the file's
    keys; its ``WIDTHS`` maps the fields that are checked. A field in both
    would be checked against the value just set from the same key, so a
    module that names one is refused."""
    ref = reference(config)
    both = sorted(set(ref.APPLIED) & set(ref.WIDTHS))
    if both:
        raise ValueError(f"reference module {ref.__name__} both sets and "
                         f"checks {both}")
    module, _, attr = config["program_config"].partition(":")
    base = getattr(importlib.import_module(module), attr or "CONFIG")
    cfg = _replace(base, {path: config[key]
                          for path, key in ref.APPLIED.items()})
    widths = {path: config[key] for path, key in ref.WIDTHS.items()}
    for want in (widths, _flat(config["program_arch"])):
        got = {k: _get(cfg, k) for k in want}
        if got != want:
            raise ValueError(f"program config {cfg.name} differs from "
                             f"{config['name']}: {got} != {want}")
    return cfg


def build_trainer(cfg, traffic: dict, devices, seed: int, rows):
    """The tenant as ``repro.launch.train.build_trainer`` builds it (same
    constructor and arguments; that function takes an architecture name,
    and these configurations are cut), fed the benchmark's rows."""
    from repro.core import ElasticTrainer
    p, mp = traffic["start"]
    return ElasticTrainer(
        cfg, global_batch=traffic["global_batch"], seq_len=traffic["seq_len"],
        init_parallelism=p, model_parallel=mp, optimizer=optimizer(traffic),
        dataset=rows, n_samples=traffic["n_samples"],
        d_partitions=traffic["d_partitions"], seed=seed % (1 << 31),
        devices=devices, time_allowance_s=traffic["time_allowance_s"],
        virtual_workers=traffic["virtual_workers"])


def cycle_shapes(traffic: dict) -> list:
    shapes = [tuple(traffic["start"])]
    for a in traffic["cycle"]:
        if a["op"] not in OPS:
            raise ValueError(f"unknown adjustment {a['op']!r}; have {OPS}")
        if tuple(a["to"]) not in shapes:
            shapes.append(tuple(a["to"]))
    return shapes


def check_dtypes(trainer, config: dict):
    """The state holds what the configuration states: parameters in
    ``param_dtype``, the optimizer's moments in
    ``optimizer_moment_dtype``."""
    import jax
    import jax.numpy as jnp
    want = {"params": jnp.dtype(config["param_dtype"]),
            "mu": jnp.dtype(config["optimizer_moment_dtype"]),
            "nu": jnp.dtype(config["optimizer_moment_dtype"])}
    st = trainer.state
    got = {"params": st["params"], "mu": st["opt"]["mu"],
           "nu": st["opt"]["nu"]}
    for k, tree in got.items():
        bad = {str(x.dtype) for x in jax.tree.leaves(tree)} - {
            str(want[k])}
        if bad:
            raise ValueError(f"the program keeps {k} in {sorted(bad)}, the "
                             f"configuration states {want[k]}")


def set_weights(trainer, config: dict, seed: int):
    """Replace the trainer's own random parameters with the benchmark's,
    drawn from ``seed`` on the device in the trainer's sharding."""
    import jax
    import jax.numpy as jnp
    from bench import weights
    from repro.models.model import param_shape_structs
    shapes = reference(config).param_shapes(config)
    prog = {k: tuple(v.shape) for k, v in
            weights.flatten(param_shape_structs(trainer.cfg)).items()}
    if prog != {k: tuple(v) for k, v in shapes.items()}:
        raise ValueError(f"program parameter layout differs from the "
                         f"reference's: {prog}")
    old = trainer.state
    trainer.state = None
    opt, count = old["opt"], old["step"]
    del old
    gc.collect()
    params = weights.make(shapes, seed, config["initializer_range"],
                          jnp.dtype(config["param_dtype"]),
                          trainer.exec.state_shardings["params"])
    trainer.state = {"params": params, "opt": opt, "step": count}
    jax.block_until_ready(params)


def memory_of(handle) -> dict:
    m = handle.step_fn.memory_analysis()
    return {"argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes, "temp": m.temp_size_in_bytes,
            "alias": m.alias_size_in_bytes,
            "total": (m.argument_size_in_bytes + m.output_size_in_bytes
                      + m.temp_size_in_bytes - m.alias_size_in_bytes)}


class StepLoop:
    """Steps the tenant and requests the mix's adjustments between steps.
    After an adjustment is done (the first step on the new shape has
    returned), ``steps_between`` steps run before the next request."""

    def __init__(self, trainer, traffic: dict, run):
        self.tr = trainer
        self.traffic = traffic
        self.run = run
        self.annotate = False
        self.cycle = list(traffic["cycle"])
        self.next_op = 0
        self.pending: Adjustment | None = None
        self.since = 0
        self.released: list = []
        trainer.on_devices_released = self._on_released
        self.sample_ids: list = []
        self.losses: list = []

    def _on_released(self, trainer, freed):
        self.released.extend(freed)

    def _note_held(self, t):
        self.run.held.append((t, tuple(d.id for d in self.tr.devices)))

    def _request(self, steps_between: int):
        if not self.cycle or self.pending is not None or \
                self.since < steps_between:
            return
        a = self.cycle[self.next_op % len(self.cycle)]
        self.next_op += 1
        target = tuple(a["to"])
        tr = self.tr
        adj = Adjustment(a["op"], time.perf_counter(), target)
        with self._span(f"bench.request.{a['op']}"):
            if a["op"] == "release":
                if target[0] >= tr.p:
                    raise ValueError(f"release to {target} from p={tr.p}")
                tr.release_devices(tr.p - target[0])
            elif a["op"] == "grant":
                freed, self.released = self.released, []
                tr.grant_devices(freed)
            else:
                tr.reshape(*target)
        self._note_held(time.perf_counter())
        self.pending = adj
        self.run.adjustments.append(adj)

    def _span(self, name, **kw):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        if name == "bench.step":
            return jax.profiler.StepTraceAnnotation(name, **kw)
        return jax.profiler.TraceAnnotation(name)

    def step(self, steps_between: int) -> Step:
        self._request(steps_between)
        tr = self.tr
        shape = (tr.p, tr.model_parallel)
        t0 = time.perf_counter()
        with self._span("bench.step", step_num=tr.step_idx):
            m = tr.step()
        t1 = time.perf_counter()
        if m is None:
            raise RuntimeError("the trainer drew no batch (epoch boundary): "
                               "n_samples is too small for the run")
        st = Step(t0, t1, shape,
                  self.traffic["global_batch"] * self.traffic["seq_len"])
        self.sample_ids.append(tr._last_sample_ids.copy())
        self.losses.append(m["loss"])
        adj = self.pending
        now = (tr.p, tr.model_parallel)
        if adj is not None:
            if adj.t_commit is None and now != shape:
                adj.t_commit = t1
                self._note_held(t1)
            elif adj.t_commit is not None and shape == adj.target:
                adj.t_done = t1
                self.pending = None
                self.since = 0
        else:
            self.since += 1
        return st


class Driver:
    """Set-up builds the trainer, every shape of the cycle (with
    ``prefetch``) and the benchmark's weights; ``warm`` drives the check
    steps through the window's own loop and reads what the reference
    follows."""

    def __init__(self, cell, config, traffic, seed, devices, run):
        from bench import flops
        from bench.data import TokenRows
        self.config, self.traffic, self.seed = config, traffic, seed
        self.cell, self.devices, self.run = cell, devices, run
        optimizer(traffic)              # an unknown name fails here
        shapes = cycle_shapes(traffic)
        run.flops_per_step = flops.flops_per_step(
            config, traffic["global_batch"], traffic["seq_len"])
        self.rows = TokenRows(traffic["n_samples"], traffic["seq_len"],
                              config["vocab_size"], seed)
        self.trainer = build_trainer(program_config(config), traffic,
                                     devices, seed, self.rows)
        check_dtypes(self.trainer, config)
        if traffic["prefetch"]:
            for p, mp in shapes:
                self.trainer._build_exec(p, mp, devices=devices)
        log("compiled_memory", shapes={
            f"{h.p}x{h.mp}": memory_of(h)
            for h in self.trainer._exec_cache.values()})
        set_weights(self.trainer, config, seed)
        self.loop = StepLoop(self.trainer, traffic, run)
        run.held.append((time.perf_counter(), tuple(d.id for d in devices)))
        self.prog: dict = {}
        self.check_ids: list = []

    @property
    def in_flight(self) -> bool:
        return self.loop.pending is not None

    @property
    def annotate(self) -> bool:
        return self.loop.annotate

    @annotate.setter
    def annotate(self, on: bool):
        self.loop.annotate = on

    def warm(self):
        """The first ``check_steps`` steps, with the cycle's adjustments
        requested back to back, and as many more as the cycle begun needs
        to come back to its start; then the loop starts the cycle afresh."""
        import jax
        import jax.numpy as jnp
        from bench import weights
        ref = reference(self.config)
        tr, loop, traffic = self.trainer, self.loop, self.traffic
        norms = jax.jit(ref.leaf_norms)
        b1 = traffic["optimizer"]["b1"]
        for i in range(traffic["check_steps"]):
            loop.step(0)
            if i == 0:
                mu = norms(tr.state["opt"]["mu"])
                self.prog["grad_norms"] = {
                    k: float(v) / (1.0 - b1)
                    for k, v in weights.flatten(mu).items()}
        self.prog["change_norms"] = weights.change_norms(
            tr.state["params"], ref.param_shapes(self.config), self.seed,
            self.config["initializer_range"],
            jnp.dtype(self.config["param_dtype"]))
        self.prog["losses"] = list(loop.losses)
        self.check_ids = [ids.copy() for ids in loop.sample_ids]
        while loop.pending is not None or (
                loop.cycle and loop.next_op % len(loop.cycle)):
            loop.step(0)        # finish the cycle begun, back at the start
        loop.next_op = 0
        loop.since = 0
        self.run.adjustments.clear()
        if not traffic["prefetch"]:
            cur = tr._exec_key(tr.p, tr.model_parallel)
            for key in [k for k in tr._exec_cache if k != cur]:
                del tr._exec_cache[key]
        jax.block_until_ready(tr.state)

    def step(self) -> Step:
        return self.loop.step(self.traffic["steps_between"])

    def free(self):
        """Drop the trainer's state and programs; the sample ids and the
        check's readings stay. While tracing, each step program's HLO text
        goes to ``run.hlo_texts`` first."""
        tr = self.trainer
        if self.run.trace:
            self.run.hlo_texts = [h.step_fn.as_text()
                                  for h in tr._exec_cache.values()]
        tr.state = None
        tr.exec = None
        tr._exec_cache.clear()
        tr.on_devices_released = None
        self.loop.tr = None
        self.trainer = None

    def duplicates(self) -> int:
        """Sample ids served twice over every step of the run: the data
        pipeline's exactly-once accounting."""
        ids = self.loop.sample_ids
        return sum(len(x) for x in ids) - len({int(i) for x in ids
                                               for i in x})

    def check(self) -> dict:
        from bench import check
        return check.compare(self.config, self.traffic, self.cell, self.seed,
                             self.rows, self.check_ids, self.prog,
                             self.devices, duplicates=self.duplicates())
