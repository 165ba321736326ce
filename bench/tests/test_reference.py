"""The plain reference agrees with the program at a small size of both
configurations: the same seeded weights and rows give the same loss,
gradients and AdamW step, with the program computing in float32."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.data import TokenRows
from bench.reference import dense_decoder as ref

BENCH = Path(__file__).resolve().parents[1]
SMALL = {"phi3-mini-3.8b-l2": dict(num_key_value_heads=4, head_dim=64),
         "mistral-nemo-12b-l2-v16k": dict(num_key_value_heads=2, head_dim=32)}
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}


def small(name):
    """The configuration at its program's CPU size (the SMOKE widths),
    computed in float32 throughout."""
    with open(BENCH / "configs" / f"{name}.json") as f:
        c = json.load(f)
    c.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
             vocab_size=512, param_dtype="float32", compute_dtype="float32",
             program_config=c["program_config"] + ":SMOKE", **SMALL[name])
    return c


def program_cfg(c):
    from bench.traffic.train import program_config
    return dataclasses.replace(program_config(c), attn_chunk=32,
                               loss_chunk=32)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_loss_and_gradient_agree(name):
    from repro.models.model import loss_fn
    c = small(name)
    cfg = program_cfg(c)
    params = weights.make(ref.param_shapes(c), 7, 0.02, jnp.float32)
    b = TokenRows(64, 128, c["vocab_size"], 7).read_ids([3, 9])
    toks, labs = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    with jax.default_matmul_precision("highest"):
        (pl, _), pg = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": toks, "labels": labs}),
            has_aux=True)(params)
        rl, rg = jax.value_and_grad(ref.loss)(params, toks, labs, c, ref.exact,
                                              None, 32)
    assert float(pl) == pytest.approx(float(rl), rel=1e-5)
    for k, g in weights.flatten(rg).items():
        np.testing.assert_allclose(weights.flatten(pg)[k], g, rtol=2e-3,
                                   atol=2e-6, err_msg=k)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_adamw_step_agrees(name):
    from repro.optim import adamw
    from repro.training.step import make_train_step
    c = small(name)
    cfg = program_cfg(c)
    params = weights.make(ref.param_shapes(c), 11, 0.02, jnp.float32)
    opt = adamw(OPT["lr"], OPT["b1"], OPT["b2"], OPT["eps"],
                OPT["weight_decay"])
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    b = TokenRows(64, 128, c["vocab_size"], 11).read_ids([1, 2])
    toks, labs = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    with jax.default_matmul_precision("highest"):
        new, m = jax.jit(make_train_step(cfg, opt))(
            state, {"tokens": toks, "labels": labs})
    zeros = jax.tree.map(jnp.zeros_like, params)
    step = ref.make_step(c, OPT)
    rp, _, _, rl, _ = step(jax.tree.map(jnp.copy, params), zeros,
                           jax.tree.map(jnp.copy, zeros), jnp.int32(1),
                           toks, labs)
    assert float(m["loss"]) == pytest.approx(float(rl), rel=1e-5)
    # Adam's first update is g / (|g| + eps) per element, so elements whose
    # gradient is near eps move by different amounts on round-off; compare
    # each leaf's update as a whole
    got, p0 = weights.flatten(new["params"]), weights.flatten(params)
    for k, p in weights.flatten(rp).items():
        dp, dr = np.asarray(got[k] - p0[k]), np.asarray(p - p0[k])
        assert np.linalg.norm(dp - dr) <= 1e-3 * np.linalg.norm(dr), k


def test_fp8_control_departs_from_the_reference():
    c = small("phi3-mini-3.8b-l2")
    params = weights.make(ref.param_shapes(c), 5, 0.02, jnp.float32)
    b = TokenRows(64, 128, c["vocab_size"], 5).read_ids([0, 1])
    toks, labs = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    exact = ref.loss(params, toks, labs, c, ref.exact, None, 32)
    low = ref.loss(params, toks, labs, c, ref.fp8, None, 32)
    assert float(low) != float(exact)
    assert abs(float(low) - float(exact)) / float(exact) < 0.05
