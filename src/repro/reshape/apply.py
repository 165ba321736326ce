"""Plan executors.

Two of them, sharing the StateSpec box arithmetic:

  * ``apply_plan(plan, state, dst_shardings, move)`` — the LIVE path:
    every topology switch of ``ElasticTrainer`` (resize, grant, release,
    migrate, reshape) moves the jax train state onto the destination
    shardings through a ``StateMove``, which keeps every byte on the
    devices. Inside one device set the move is one jitted identity over
    the whole state whose ``out_shardings`` are the destination's: XLA
    lowers it to the collectives the plan's moves name. A change of device
    set goes through an intermediate layout on the larger set (the smaller
    side's partition specs on that set's devices, with one extra replica
    axis), so that crossing between the sets is a ``device_put`` in which
    every destination box is a box some source device holds: JAX copies or
    reuses whole device buffers and never fetches the array to the host.
    Only device counts that do not divide (say 3 -> 2) and host (numpy)
    input go through host memory, by a plain ``device_put``; the move
    reports those bytes as ``host_bytes``.

  * ``shard_state`` / ``apply_plan_host`` / ``assemble_state`` — a pure
    numpy REFERENCE executor over explicit per-slot shard dicts. It is the
    oracle the property tests round-trip (apply(plan(a,b)) then
    apply(plan(b,a)) must be the identity on every tensor) and needs no
    mesh, no devices and no jax trace.
"""
from __future__ import annotations

import numpy as np

from repro.reshape.plan import ReshardPlan
from repro.reshape.spec import StateSpec, flatten_tree, unflatten_tree


def shard_state(spec: StateSpec, state: dict) -> list[dict]:
    """Split a global (host) state tree into per-mesh-slot shard dicts:
    ``out[i][path]`` is the box the device at linear index i holds."""
    flat = flatten_tree(state)
    out: list[dict] = []
    for i in range(spec.n_devices):
        shards = {}
        for t in spec.tensors:
            box = t.box(spec.dp, spec.mp, i)
            shards[t.path] = np.asarray(flat[t.path])[
                tuple(slice(lo, hi) for lo, hi in box)]
        out.append(shards)
    return out


def assemble_state(spec: StateSpec, shards: list[dict]) -> dict:
    """Reconstruct the global state tree from per-slot shards (the inverse
    of ``shard_state``; replicated boxes overwrite with equal values)."""
    flat = {}
    for t in spec.tensors:
        ref = shards[0][t.path]
        full = np.empty(t.shape, dtype=ref.dtype)
        for i in range(spec.n_devices):
            box = t.box(spec.dp, spec.mp, i)
            full[tuple(slice(lo, hi) for lo, hi in box)] = shards[i][t.path]
        flat[t.path] = full
    return unflatten_tree(flat)


def apply_plan_host(plan: ReshardPlan, shards: list[dict]) -> list[dict]:
    """Reference executor: move per-slot shards from ``plan.src`` layout to
    ``plan.dst`` layout with numpy slicing/concat only."""
    if len(shards) != plan.src.n_devices:
        raise ValueError(f"got {len(shards)} shard dicts for a "
                         f"{plan.src.n_devices}-slot source mesh")
    global_flat = flatten_tree(assemble_state(plan.src, shards))
    out: list[dict] = []
    for i in range(plan.dst.n_devices):
        dst = {}
        for t in plan.dst.tensors:
            box = t.box(plan.dst.dp, plan.dst.mp, i)
            dst[t.path] = global_flat[t.path][
                tuple(slice(lo, hi) for lo, hi in box)].copy()
        out.append(dst)
    return out


REPLICA_AXIS = "replica"     # the intermediate layout's extra mesh axis


def _identity(tree):
    return tree


def _one_mesh(shardings):
    """The mesh every leaf's sharding is laid out on, or None when the
    leaves are not all ``NamedSharding``s of one mesh (host leaves
    included)."""
    from jax.sharding import NamedSharding
    meshes = {s.mesh if isinstance(s, NamedSharding) else None
              for s in shardings}
    return meshes.pop() if len(meshes) == 1 else None


def _over(shardings, mesh):
    """The same partition specs on ``mesh`` (whose extra leading axis,
    absent from every spec, replicates)."""
    from jax.sharding import NamedSharding
    return [NamedSharding(mesh, s.spec) for s in shardings]


def _layout(devices, k: int, mesh):
    """``devices`` (in their order) as a mesh of shape (k, *mesh.shape):
    ``mesh``'s axes after a leading ``REPLICA_AXIS``."""
    from jax.sharding import Mesh
    grid = np.array(devices, dtype=object).reshape(
        (k,) + mesh.devices.shape)
    return Mesh(grid, (REPLICA_AXIS,) + tuple(mesh.axis_names))


def _compile(src, dst, shapes):
    """One jitted identity from ``src`` to ``dst`` over the whole state,
    compiled ahead of time (both sides on one ordered device list)."""
    import jax
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
            for s, sh in zip(shapes, src)]
    return jax.jit(_identity, out_shardings=dst).lower(args).compile()


class StateMove:
    """The move of a train state from one layout onto another, chosen from
    the two sides' device lists and compiled when it is built (in an
    adjustment's prep, beside the step).

    ``route`` says how it runs:

      keep     the layouts are equal: the state is returned as it is.
      reshard  one ordered device list: one jitted identity.
      shrink   the destination has k >= 1 times fewer devices (k = 1:
               other devices, or the same in another order): reshard
               inside the source set into the destination's specs on the
               source's devices laid out (k, *destination mesh) —
               ``REPLICA_AXIS`` first — then cross onto the destination,
               whose every box that layout holds. Where the destination's
               devices lead the source's, the crossing copies nothing.
      grow     the mirror, k > 1 times more: cross onto the source's specs
               on the destination's devices laid out (k, *source mesh),
               whose every box the source holds, then reshard inside the
               destination set.
      host     anything else (device counts that do not divide, host
               leaves): ``jax.device_put``, through host memory.

    ``host_bytes`` is what a call moves through host memory: the state's
    bytes on the ``host`` route, else 0.
    """

    def __init__(self, src_shardings, dst_shardings, shapes):
        """``src_shardings`` and ``dst_shardings`` are trees of the state's
        shardings on either side (a source leaf is None for a host array)
        and ``shapes`` the tree of its ``ShapeDtypeStruct``s."""
        src = flatten_tree(src_shardings)
        self.paths = list(src)
        dst = [flatten_tree(dst_shardings)[p] for p in self.paths]
        shapes = [flatten_tree(shapes)[p] for p in self.paths]
        src = list(src.values())
        self._steps: list = []
        self.host_bytes = 0
        src_mesh, dst_mesh = _one_mesh(src), _one_mesh(dst)
        if all(a is not None and a == b for a, b in zip(src, dst)):
            self.route = "keep"
            return
        a = [] if src_mesh is None else list(src_mesh.devices.flat)
        b = [] if dst_mesh is None else list(dst_mesh.devices.flat)
        small, big = sorted((a, b), key=len)
        if not small or len(big) % len(small):
            self.route = "host"
            self.host_bytes = sum(s.size * s.dtype.itemsize for s in shapes)
            self._steps = [self._cross(dst)]
            return
        k = len(big) // len(small)
        if a == b:
            self.route = "reshard"
            self._steps = [_compile(src, dst, shapes)]
        elif len(b) <= len(a):
            self.route = "shrink"
            mid = _over(dst, _layout(a, k, dst_mesh))
            self._steps = [_compile(src, mid, shapes), self._cross(dst)]
        else:
            self.route = "grow"
            mid = _over(src, _layout(b, k, src_mesh))
            self._steps = [self._cross(mid), _compile(mid, dst, shapes)]

    @staticmethod
    def _cross(shardings):
        import jax
        return lambda leaves: jax.device_put(leaves, shardings)

    @classmethod
    def between(cls, state: dict, dst_shardings) -> "StateMove":
        """The move of ``state`` (jax or host leaves) onto
        ``dst_shardings``, built from the leaves themselves."""
        import jax
        return cls(jax.tree.map(lambda x: getattr(x, "sharding", None),
                                state),
                   dst_shardings,
                   jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                       np.shape(x), x.dtype), state))

    def __call__(self, state: dict) -> dict:
        flat = flatten_tree(state)
        if list(flat) != self.paths:
            raise ValueError(f"the state's tensors {sorted(flat)} are not "
                             f"the ones this move was built for")
        leaves = list(flat.values())
        for step in self._steps:
            leaves = step(leaves)
        return unflatten_tree(dict(zip(self.paths, leaves)))


def apply_plan(plan: ReshardPlan, state: dict, dst_shardings,
               move: StateMove | None = None) -> tuple[dict, int]:
    """Live executor: move a train state onto ``dst_shardings`` along
    ``move`` (built here from the state's own leaves when not given) and
    return ``(new state, host_bytes)``. The plan's job here is validation
    (same collection, same global shapes — checked at planning time and
    again against the state) and the per-tensor move accounting the
    scaling record reports; ``keep`` moves cost nothing. Nothing is
    donated: the source state stays valid until the caller drops it."""
    planned = sorted(m.path for m in plan.moves)
    if planned != sorted(flatten_tree(state)):
        raise ValueError("the plan's tensors are not the state's")
    move = move or StateMove.between(state, dst_shardings)
    return move(state), move.host_bytes
