"""Top-level language model: param specs, init, forward (train / prefill /
decode) over the scanned block stack, and the chunked cross-entropy loss.

The whole depth lowers as one ``lax.scan`` over periods (see blocks.scan_plan)
so HLO size and compile time are depth-independent — essential for the
multi-pod dry-run of 60-layer configs, and it is also what production JAX
frameworks (MaxText et al.) do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.layers import apply_embed, apply_linear, apply_rmsnorm, dt, \
    embed_specs, rmsnorm_specs, unembed_specs
from repro.sharding import ShardedInit, constrain, fit_chunk


# ------------------------------------------------------------------- specs
def param_spec_tree(cfg) -> dict:
    slots, n_periods = B.scan_plan(cfg)
    stack = lambda s: ShardedInit((n_periods,) + s.shape,
                                  ("layers",) + s.axes, s.init, s.scale)
    layers = {}
    for j, (mixer, ffn) in enumerate(slots):
        spec = B.block_specs(cfg, mixer, ffn)
        layers[f"slot{j}"] = jax.tree.map(
            stack, spec, is_leaf=lambda x: isinstance(x, ShardedInit))
    tree = {"layers": layers,
            "final_norm": rmsnorm_specs(cfg.d_model),
            "unembed": unembed_specs(cfg.d_model, cfg.vocab)}
    if cfg.frontend == "tokens":
        tree["embed"] = embed_specs(cfg.vocab, cfg.d_model)
    return tree


def param_logical_axes(cfg) -> dict:
    return jax.tree.map(lambda s: s.axes, param_spec_tree(cfg),
                        is_leaf=lambda x: isinstance(x, ShardedInit))


def param_shape_structs(cfg) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
                        param_spec_tree(cfg),
                        is_leaf=lambda x: isinstance(x, ShardedInit))


def init_params(cfg, key) -> dict:
    specs = param_spec_tree(cfg)
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ShardedInit))
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(cfg.param_dtype)
    vals = [s.materialize(k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


# ------------------------------------------------------------------ forward
@jax.custom_vjp
def _barrier(x):
    """Differentiable ``optimization_barrier``: identity with a barrier on
    the forward value AND on the backward cotangent. ``lax.optimization_barrier``
    has no differentiation rule, so using it raw under ``value_and_grad``
    raises NotImplementedError; the custom_vjp keeps the anti-hoisting effect
    in both passes (the backward barrier stops XLA from hoisting the
    rematerialized residual converts out of the backward scan too)."""
    return jax.lax.optimization_barrier(x)


def _barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _barrier_bwd(_, ct):
    return (jax.lax.optimization_barrier(ct),)


_barrier.defvjp(_barrier_fwd, _barrier_bwd)


def _stack_forward(cfg, params, x, *, positions, cache=None, use_pallas=False,
                   mode="train"):
    """Scan the block stack. Returns (x, new_cache_layers, aux_mean)."""
    slots, n_periods = B.scan_plan(cfg)
    layer_params = params["layers"]

    def period_fn(x, xs):
        # barrier: stop XLA from hoisting the (bf16 -> f32) convert of the
        # rematerialized layer input across the scan boundary, which would
        # materialize an fp32 copy of the whole [n_layers, B, L, D] residual
        # stack (observed: +24 GiB/device on phi3 train_4k).
        x = _barrier(x)
        p_slots, c_slots = xs
        new_c = {}
        aux_total = jnp.zeros((), jnp.float32)
        for j, (mixer, ffn) in enumerate(slots):
            x, nc, aux = B.block_forward(
                cfg, p_slots[f"slot{j}"], x, mixer=mixer, ffn=ffn,
                positions=positions,
                cache=None if c_slots is None else c_slots[f"slot{j}"],
                use_pallas=use_pallas)
            aux_total = aux_total + aux
            if nc is not None:
                new_c[f"slot{j}"] = nc
        return x, (new_c if new_c else None, aux_total)

    body = period_fn
    if cfg.remat and mode == "train":
        body = jax.checkpoint(period_fn,
                              policy=jax.checkpoint_policies.nothing_saveable)

    cache_layers = None if cache is None else cache["layers"]
    g = max(1, cfg.remat_group)
    if (cfg.scan_layers and cache is None and mode == "train" and g > 1
            and n_periods % g == 0 and n_periods // g > 1):
        # Grouped (sqrt-style) remat: save the layer input only every g
        # periods — residual stack shrinks by g at the cost of re-running
        # (g-1)/g of the forward once more in backward.
        def group_fn(x, p_g):
            # NESTED remat: each period inside the group keeps its own
            # checkpoint (``body``), else a group's backward would hold g
            # layers of intra-layer residuals at once (measured: rg4 made
            # phi3 temp WORSE, 19.3 -> 26.2 GiB, before this nesting).
            aux_t = jnp.zeros((), jnp.float32)
            for i in range(g):
                x, (_, a) = body(
                    x, (jax.tree.map(lambda t: t[i], p_g), None))
                aux_t = aux_t + a
            return x, aux_t
        gbody = jax.checkpoint(group_fn,
                               policy=jax.checkpoint_policies.nothing_saveable)
        p_grouped = jax.tree.map(
            lambda a: a.reshape((n_periods // g, g) + a.shape[1:]),
            layer_params)
        x, aux_groups = jax.lax.scan(gbody, x, p_grouped)
        return x, None, jnp.mean(aux_groups) / g
    if cfg.scan_layers and n_periods > 1:
        xs = (layer_params, cache_layers)
        x, (new_cache, auxes) = jax.lax.scan(body, x, xs)
        aux = jnp.mean(auxes) if auxes is not None else jnp.zeros(())
    else:
        new_slices, aux_list = [], []
        for i in range(n_periods):
            p_i = jax.tree.map(lambda a: a[i], layer_params)
            c_i = (None if cache_layers is None else
                   jax.tree.map(lambda a: a[i], cache_layers))
            x, (nc, a) = body(x, (p_i, c_i))
            new_slices.append(nc)
            aux_list.append(a)
        new_cache = (None if new_slices[0] is None else
                     jax.tree.map(lambda *xs: jnp.stack(xs), *new_slices))
        aux = jnp.mean(jnp.stack(aux_list))
    return x, new_cache, aux


def embed_inputs(cfg, params, batch):
    cd = dt(cfg, "compute")
    if cfg.frontend == "embeds":
        return batch["embeds"].astype(cd)
    return apply_embed(params["embed"], batch["tokens"], cd)


def forward(cfg, params, batch, *, mode: str, cache=None, use_pallas=False,
            rng=None):
    """mode: 'train' -> (hidden, aux); 'prefill' -> (last-position logits,
    aux); 'decode' -> (logits [B,1,V], new_cache). ``rng`` keys the input
    dropout (train only, ``cfg.dropout > 0``); with ``rng=None`` the
    forward is fully deterministic."""
    x = embed_inputs(cfg, params, batch)
    if mode == "train" and rng is not None and cfg.dropout > 0.0:
        keep = 1.0 - cfg.dropout
        mask = jax.random.bernoulli(rng, keep, x.shape)
        x = jnp.where(mask, x / keep, jnp.zeros((), x.dtype)).astype(x.dtype)
    Bsz, L, _ = x.shape
    x = constrain(x, ("batch", None, None))
    if mode == "decode":
        assert cache is not None
        positions = jnp.broadcast_to(cache["pos"], (Bsz, 1))
    else:
        positions = jnp.broadcast_to(jnp.arange(L), (Bsz, L))

    x, new_cache_layers, aux = _stack_forward(
        cfg, params, x, positions=positions, cache=cache,
        use_pallas=use_pallas, mode=mode)
    with jax.named_scope("head_loss"):
        x = apply_rmsnorm(params["final_norm"], x, cfg.norm_eps)

    if mode == "train":
        return x, aux
    if mode == "prefill":
        logits = apply_linear(params["unembed"], x[:, -1],
                              jnp.float32)            # [B, V]
        logits = constrain(logits, ("batch", "vocab"))
        return logits, aux
    logits = apply_linear(params["unembed"], x, jnp.float32)  # [B,1,V]
    logits = constrain(logits, ("batch", None, "vocab"))
    new_cache = {"layers": new_cache_layers, "pos": cache["pos"] + 1}
    return logits, new_cache


def chunked_xent(cfg, params, hidden, labels):
    """Cross-entropy in seq chunks so [B, chunk, V] is the only logits buffer
    ever materialized (vocab up to 152k would otherwise OOM)."""
    Bsz, L, D = hidden.shape
    chunk = fit_chunk(L, cfg.loss_chunk)
    n_chunks = L // chunk
    w = params["unembed"]["w"]

    def body(total, ci):
        h_c = jax.lax.dynamic_slice_in_dim(hidden, ci * chunk, chunk, 1)
        l_c = jax.lax.dynamic_slice_in_dim(labels, ci * chunk, chunk, 1)
        logits = jnp.einsum("bcd,dv->bcv", h_c.astype(jnp.float32),
                            w.astype(jnp.float32))
        logits = constrain(logits, ("batch", None, "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l_c[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            jnp.arange(n_chunks),
                            unroll=n_chunks if cfg.full_unroll else 1)
    return total / (Bsz * L)


def loss_fn(cfg, params, batch, *, use_pallas=False, rng=None):
    hidden, aux = forward(cfg, params, batch, mode="train",
                          use_pallas=use_pallas, rng=rng)
    labels = batch["labels"]
    with jax.named_scope("head_loss"):
        loss = chunked_xent(cfg, params, hidden, labels)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    return loss + aux_w * aux, {"xent": loss, "aux": aux}


def prefill(cfg, params, batch, *, use_pallas=False):
    logits, _ = forward(cfg, params, batch, mode="prefill",
                        use_pallas=use_pallas)
    return logits


def serve_step(cfg, params, batch, cache):
    """ONE new token against the cache. Returns (next_token_ids, new_cache)."""
    logits, new_cache = forward(cfg, params, batch, mode="decode", cache=cache)
    next_ids = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return next_ids, new_cache
