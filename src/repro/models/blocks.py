"""Composable decoder blocks and the per-architecture layer plan.

A block = pre-norm mixer (+residual) then pre-norm FFN (+residual).
Mixer kinds: 'attn' (GQA or MLA per cfg), 'mamba', 'rwkv_tm'.
FFN kinds: 'mlp', 'moe', 'rwkv_cm'.

``layer_plan(cfg)`` expands the architecture into a per-layer (mixer, ffn)
list; ``scan_plan`` folds it into the smallest repeating period so the whole
stack lowers as ONE lax.scan over periods (compile time independent of depth).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, moe as moe_mod, ssm
from repro.models.layers import apply_mlp, apply_rmsnorm, dt, mlp_specs, \
    rmsnorm_specs


def layer_plan(cfg) -> list[tuple[str, str]]:
    plan = []
    for i in range(cfg.n_layers):
        if cfg.family == "ssm" and cfg.ssm.kind == "rwkv6":
            mixer = "rwkv_tm"
        elif cfg.hybrid_pattern:
            mixer = {"m": "mamba", "a": "attn"}[
                cfg.hybrid_pattern[i % len(cfg.hybrid_pattern)]]
        else:
            mixer = "attn"
        if mixer == "rwkv_tm":
            ffn = "rwkv_cm"
        elif cfg._layer_is_moe(i):
            ffn = "moe"
        else:
            ffn = "mlp"
        plan.append((mixer, ffn))
    return plan


def scan_plan(cfg) -> tuple[list[tuple[str, str]], int]:
    """Returns (slots, n_periods): plan == slots * n_periods."""
    plan = layer_plan(cfg)
    n = len(plan)
    for period in range(1, n + 1):
        if n % period == 0 and all(plan[i] == plan[i % period]
                                   for i in range(n)):
            return plan[:period], n // period
    return plan, 1


MIXERS = {
    "attn": (attention.attention_specs, attention.attention_forward,
             attention.attention_cache_spec),
    "mamba": (ssm.mamba_specs, ssm.mamba_forward, ssm.mamba_cache_spec),
    "rwkv_tm": (ssm.rwkv_tm_specs, ssm.rwkv_tm_forward, ssm.rwkv_cache_spec),
}


def block_specs(cfg, mixer: str, ffn: str) -> dict:
    s = {"norm1": rmsnorm_specs(cfg.d_model),
         "mixer": MIXERS[mixer][0](cfg),
         "norm2": rmsnorm_specs(cfg.d_model)}
    if ffn == "mlp":
        s["ffn"] = mlp_specs(cfg.d_model, cfg.d_ff)
    elif ffn == "moe":
        s["ffn"] = moe_mod.moe_specs(cfg)
    elif ffn == "rwkv_cm":
        s["ffn"] = ssm.rwkv_cm_specs(cfg)
    return s


def block_cache_spec(cfg, mixer: str, batch: int, max_seq: int) -> dict:
    return MIXERS[mixer][2](cfg, batch, max_seq)


def block_forward(cfg, p, x, *, mixer: str, ffn: str, positions, cache=None,
                  use_pallas=False):
    """Returns (x, new_cache, aux_loss)."""
    cd = dt(cfg, "compute")
    h = apply_rmsnorm(p["norm1"], x, cfg.norm_eps)
    # named scopes name the ops (forward, recompute and backward) in the
    # compiled program's metadata and the profiler's op names; they change
    # no instruction
    with jax.named_scope("attention"):
        mix_out, new_cache = MIXERS[mixer][1](
            cfg, p["mixer"], h, positions=positions, cache=cache,
            use_pallas=use_pallas)
    x = x + mix_out
    h = apply_rmsnorm(p["norm2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("mlp"):
        if ffn == "mlp":
            f = apply_mlp(p["ffn"], h, cd)
        elif ffn == "moe":
            f, aux = moe_mod.moe_forward(cfg, p["ffn"], h)
        else:   # rwkv channel-mix (keeps its own shift state)
            f, cm_cache = ssm.rwkv_cm_forward(cfg, p["ffn"], h, cache=cache)
            if cm_cache is not None:
                new_cache = {**(new_cache or {}), **cm_cache}
    return x + f, new_cache, aux
