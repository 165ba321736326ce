"""Plain reference of the dense decoder the benchmark trains: forward, loss,
gradient and AdamW update in float32 under ``jax.default_matmul_precision(
"highest")``, written from the published description (RMSNorm, RoPE with
the rotate-half layout, causal grouped-query attention, SwiGLU MLP, untied
LM head, mean token cross-entropy). It imports nothing of the program.

Departures, each on purpose:
  * the weight layout (paths and the stacked leading layer axis) is the
    program's, so the same seeded draw (``bench.weights``) feeds both;
  * attention runs in blocks of query rows and every layer is
    rematerialized, so that a step at the timed sizes fits a chip; the
    arithmetic is the same as one dense softmax;
  * AdamW decays every leaf, norms and embedding included, as the program's
    ``adamw`` does.

``einsum`` is a parameter: ``exact`` (float32, highest precision) is the
reference; ``fp8`` (both operands of every matmul, and the cotangents in the
backward pass, rounded to float8 e4m3 with one scale per tensor) is the
control that a correct program must not be mistaken for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def exact(spec, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _fp8(x):
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8(spec, a, b):
    return exact(spec, _fp8(a), _fp8(b))


def _fp8_fwd(spec, a, b):
    aq, bq = _fp8(a), _fp8(b)
    return exact(spec, aq, bq), (aq, bq)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(functools.partial(exact, spec), *res)
    return vjp(_fp8(g))


fp8.defvjp(_fp8_fwd, _fp8_bwd)
EINSUMS = {"exact": exact, "fp8": fp8}

# program attribute <- configuration key: the widths that the program's
# configuration must have as the file states them
WIDTHS = {"d_model": "hidden_size", "d_ff": "intermediate_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads",
          "resolved_head_dim": "head_dim",
          "tie_embeddings": "tie_word_embeddings"}
# program attribute <- configuration key: what the file sets in the
# program's own published configuration (its cuts, dtypes and constants)
APPLIED = {"n_layers": "num_hidden_layers", "vocab": "vocab_size",
           "param_dtype": "param_dtype", "compute_dtype": "compute_dtype",
           "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
           "remat": "remat"}


def dims(cfg: dict) -> tuple:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def param_shapes(cfg: dict) -> dict:
    d, h, kv, hd, f, v, n = dims(cfg)
    s = "layers/slot0/"
    return {
        "embed/table": (v, d),
        s + "norm1/scale": (n, d),
        s + "mixer/wq/w": (n, d, h * hd),
        s + "mixer/wk/w": (n, d, kv * hd),
        s + "mixer/wv/w": (n, d, kv * hd),
        s + "mixer/wo/w": (n, h * hd, d),
        s + "norm2/scale": (n, d),
        s + "ffn/wi_gate": (n, d, f),
        s + "ffn/wi_up": (n, d, f),
        s + "ffn/wo": (n, f, d),
        "final_norm/scale": (d,),
        "unembed/w": (d, v),
    }


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul: attention projections,
    SwiGLU MLP and the LM head; the embedding lookup is no matmul."""
    d, h, kv, hd, f, v, n = dims(cfg)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return n * per_layer + d * v


def flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one trained token, by ``bench.flops``'s rule: 6 for
    every matmul parameter (2 forward, 4 backward), plus causal
    self-attention, 6 x seq x n_heads x head_dim a layer (QK^T and PV at
    2 x seq/2 x n_heads x head_dim each forward, times 3 for forward and
    backward)."""
    d, h, kv, hd, f, v, n = dims(cfg)
    return 6.0 * matmul_params(cfg) + 6 * n * seq_len * h * hd


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [B, L, heads, hd]; rotate-half layout."""
    hd, L = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(L, dtype=F32)[:, None] * inv[None, :]     # [L, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, ein, block):
    """Causal softmax attention; q, k, v: [B, L, H, hd] (kv already
    repeated to H heads). Computed in blocks of ``block`` query rows."""
    B, L, H, hd = q.shape
    block = min(block, L)
    kpos = jnp.arange(L)

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = ein("bqhd,bkhd->bhqk", qi, k) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        return ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(one, jnp.arange(L // block))     # [nb, B, blk, H, hd]
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H, hd)


def loss(params: dict, tokens, labels, cfg: dict, ein=exact,
         rows: int | None = None, block: int = 512):
    """Mean next-token cross-entropy over the first ``rows`` rows of the
    batch (all of them by default)."""
    d, h, kv, hd, f, V, n = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    lay = params["layers"]["slot0"]
    x = jnp.take(params["embed"]["table"].astype(F32), tokens, axis=0)
    B, L, _ = x.shape

    @jax.checkpoint
    def layer(x, i):
        at = lambda t: t[i].astype(F32)                    # noqa: E731
        a = _rmsnorm(x, at(lay["norm1"]["scale"]), eps)
        q = ein("bld,de->ble", a, at(lay["mixer"]["wq"]["w"]))
        k = ein("bld,de->ble", a, at(lay["mixer"]["wk"]["w"]))
        v = ein("bld,de->ble", a, at(lay["mixer"]["wv"]["w"]))
        q = _rope(q.reshape(B, L, h, hd), theta)
        k = jnp.repeat(_rope(k.reshape(B, L, kv, hd), theta), h // kv, 2)
        v = jnp.repeat(v.reshape(B, L, kv, hd), h // kv, 2)
        o = _attention(q, k, v, ein, block).reshape(B, L, h * hd)
        x = x + ein("ble,ed->bld", o, at(lay["mixer"]["wo"]["w"]))
        a = _rmsnorm(x, at(lay["norm2"]["scale"]), eps)
        gate = ein("bld,df->blf", a, at(lay["ffn"]["wi_gate"]))
        up = ein("bld,df->blf", a, at(lay["ffn"]["wi_up"]))
        return x + ein("blf,fd->bld", jax.nn.silu(gate) * up,
                       at(lay["ffn"]["wo"]))

    for i in range(n):
        x = layer(x, i)
    x = _rmsnorm(x, params["final_norm"]["scale"].astype(F32), eps)
    logits = ein("bld,dv->blv", x, params["unembed"]["w"].astype(F32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    per_tok = lse - gold
    if rows is not None:
        per_tok = per_tok[:rows]
    return jnp.mean(per_tok)


def leaf_norms(tree) -> dict:
    return jax.tree.map(lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(F32)))),
                        tree)


def make_step(cfg: dict, opt: dict, ein=exact, rows: int | None = None,
              shardings=None, batch_sharding=None):
    """One AdamW step: (params, m, v, count, tokens, labels) ->
    (params, m, v, loss, per-leaf gradient norms)."""
    if opt.get("name", "adamw") != "adamw":
        raise ValueError(f"the reference steps AdamW, not {opt['name']!r}")
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["lr"], opt["weight_decay"]

    def step(params, m, v, count, tokens, labels):
        with jax.default_matmul_precision("highest"):
            lval, g = jax.value_and_grad(loss)(params, tokens, labels, cfg,
                                               ein, rows)
            c = count.astype(F32)
            bc1, bc2 = 1.0 - b1 ** c, 1.0 - b2 ** c
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
            params = jax.tree.map(
                lambda p, m_, v_: p - (lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                          + eps) + lr * wd * p),
                params, m, v)
        return params, m, v, lval, leaf_norms(g)

    if shardings is None:
        return jax.jit(step, donate_argnums=(0, 1, 2))
    return jax.jit(step, donate_argnums=(0, 1, 2),
                   in_shardings=(shardings, shardings, shardings, None,
                                 batch_sharding, batch_sharding),
                   out_shardings=(shardings, shardings, shardings, None,
                                  None))
