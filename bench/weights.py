"""The benchmark's own weights: every leaf drawn from ``--seed`` in one jitted
call on the device, in the dtype and with the sharding it is used in.

The layout is a flat map from a leaf's path ("layers/slot0/ffn/wo") to its
shape; leaves whose name is ``scale`` (RMSNorm gains) start at 1, every
other leaf is N(0, std^2). A leaf's draw depends only on the seed and its
path, so the program and the reference get the same numbers."""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int):
    """A key from a seed of any size up to 64 bits."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        *parents, leaf = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = val
    return tree


def flatten(tree, prefix: str = "") -> dict:
    if not isinstance(tree, dict):
        return {prefix: tree}
    out = {}
    for k in sorted(tree):
        out.update(flatten(tree[k], f"{prefix}/{k}" if prefix else str(k)))
    return out


def draw(shapes: dict, key, std: float, dtype) -> dict:
    """Traceable: the flat map of leaves for ``shapes``."""
    out = {}
    for path in sorted(shapes):
        shape = tuple(shapes[path])
        if path.rsplit("/", 1)[-1] == "scale":
            out[path] = jnp.ones(shape, dtype)
            continue
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        out[path] = (jax.random.normal(k, shape, jnp.float32) * std
                     ).astype(dtype)
    return out


def make(shapes: dict, seed: int, std: float, dtype, shardings=None) -> dict:
    """The nested weight tree, made on the device in one jitted call.
    ``shardings`` is a nested tree like the result, or None."""
    fn = jax.jit(lambda k: nest(draw(shapes, k, std, dtype)),
                 out_shardings=shardings)
    return fn(base_key(seed))


def change_norms(params, shapes: dict, seed: int, std: float, dtype) -> dict:
    """Per-leaf norm of (params - the seed's initial draw), computed on the
    device with the draw made again inside the same jitted call."""
    def fn(p, key):
        p0 = nest(draw(shapes, key, std, dtype))
        return jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), p, p0)
    out = jax.jit(fn)(params, base_key(seed))
    return {k: float(v) for k, v in flatten(out).items()}
