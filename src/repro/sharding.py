"""Logical-axis sharding rules (MaxText-style) for the repro framework.

Every parameter / activation dim is annotated with a *logical* axis name;
rules map logical names to physical mesh axes. The elastic (data-parallel)
axis is ``('pod', 'data')`` — EDL elasticity resizes it; the ``model`` axis
carries tensor / expert parallelism and is fixed for a job's lifetime.

A dim whose size is not divisible by the product of its mapped mesh axes is
left unsharded (GSPMD would pad, but replication keeps memory math exact and
the dry-run honest).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> tuple of mesh axes (tried in order; dropped if not divisible).
# ``fsdp`` axes shard weights over the elastic data axis (ZeRO-3 style);
# ``tensor`` axes shard over the model axis.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # activations
    "batch": ("pod", "data"),
    "seq": (),                  # unsharded by default (train); see decode rules
    "seq_shard": ("data",),     # long-context KV-cache sequence sharding
    "embed_act": (),
    # weights
    "vocab": ("model",),
    "embed": ("pod", "data"),   # FSDP dim
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "qk_dim": (),
    "experts": ("model",),      # expert parallelism
    # fallback: when n_experts doesn't divide the model axis (mixtral: 8e on
    # a 16-way axis), expert weights would replicate and EVERY model rank
    # would redo the full expert compute (observed 16x FLOPs on mixtral
    # train_4k). Sharding the per-expert FFN dim instead keeps the matmuls
    # 16-way parallel (TP inside each expert).
    "expert_mlp": ("model",),
    "layers": (),
    "ssm_inner": ("model",),    # mamba/rwkv inner dim (TP)
    "ssm_state": (),
    "conv": (),
    "lora": (),                 # MLA low-rank dims stay replicated
    "fsdp2": ("pod", "data"),   # secondary FSDP dim for 2D-sharded weights
    None: (),
}


def mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


def spec_for(logical_axes: Sequence[str | None], shape: Sequence[int],
             mesh: Mesh, rules: Mapping[str, tuple[str, ...]] | None = None,
             ) -> P:
    """Build a PartitionSpec for one array from its logical axis names."""
    rules = dict(DEFAULT_RULES) if rules is None else {**DEFAULT_RULES, **rules}
    used: set[str] = set()
    entries: list[Any] = []
    for name, dim in zip(logical_axes, shape):
        mapped = tuple(a for a in rules.get(name, ()) if a in mesh.shape and a not in used)
        if mapped and dim % mesh_axis_size(mesh, mapped) == 0:
            entries.append(mapped if len(mapped) > 1 else mapped[0])
            used.update(mapped)
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_shardings(axes_tree: Any, shape_tree: Any, mesh: Mesh,
                   rules: Mapping[str, tuple[str, ...]] | None = None) -> Any:
    """Map a pytree of logical-axis tuples + matching shapes to NamedShardings."""
    def one(axes, shaped):
        shape = shaped.shape if hasattr(shaped, "shape") else tuple(shaped)
        return NamedSharding(mesh, spec_for(axes, shape, mesh, rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


_TLS = threading.local()


@contextlib.contextmanager
def manual_region():
    """Suspend ``constrain`` for the enclosed trace.

    Inside a full-manual ``shard_map`` body every value is a PER-DEVICE
    block — mesh-axis sharding constraints are meaningless there (and XLA
    rejects them). The deterministic virtual-worker train step traces the
    model's ``loss_fn`` inside such a body, so the model code's logical-axis
    annotations must become no-ops without the model knowing; thread-local
    so concurrent tracers (background AOT compiles) are unaffected."""
    prev = getattr(_TLS, "manual", False)
    _TLS.manual = True
    try:
        yield
    finally:
        _TLS.manual = prev


def constrain(x: jax.Array, logical_axes: Sequence[str | None],
              rules: Mapping[str, tuple[str, ...]] | None = None) -> jax.Array:
    """with_sharding_constraint from logical axes; no-op outside a mesh or
    inside a ``manual_region`` (per-device shard_map trace)."""
    if getattr(_TLS, "manual", False):
        return x
    mesh = get_abstract_mesh_or_none()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(logical_axes, x.shape, mesh, rules)))


def get_abstract_mesh_or_none():
    """The mesh visible at trace time, set by ``jax.set_mesh(mesh)``. (A bare
    ``with mesh:`` only sets JAX's private physical-mesh context, so callers
    that want the model's sharding annotations enter ``jax.set_mesh``.)"""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


@dataclasses.dataclass(frozen=True)
class ShardedInit:
    """A parameter's shape, logical axes and initializer, kept together so the
    same metadata drives init, sharding and the dry-run ShapeDtypeStructs."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"        # normal | zeros | ones | scaled
    scale: float = 1.0

    def materialize(self, key, dtype):
        if self.init == "zeros":
            return jax.numpy.zeros(self.shape, dtype)
        if self.init == "ones":
            return jax.numpy.ones(self.shape, dtype)
        if self.init == "alog":     # mamba A_log: log(1..N) along last dim
            a = jax.numpy.log(jax.numpy.arange(1, self.shape[-1] + 1,
                                               dtype=jax.numpy.float32))
            return jax.numpy.broadcast_to(a, self.shape).astype(dtype)
        fan_in = self.shape[0] if len(self.shape) > 1 else max(self.shape[-1], 1)
        std = self.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, self.shape) * std).astype(dtype)


def fit_chunk(total: int, desired: int) -> int:
    """Largest chunk <= desired that divides total (chunked loops need an
    exact tiling; non-divisible requests degrade instead of failing)."""
    c = max(1, min(desired, total))
    while total % c:
        c -= 1
    return c
