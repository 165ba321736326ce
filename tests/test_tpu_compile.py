"""Compiles for a described TPU v5e chip that is not attached: whatever the
chip's compiler refuses (an unaligned block, too much VMEM, a program that
does not fit the chip's memory) fails here, before any chip time is spent.
Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. Keep these compiles in this one file, so that one worker
loads it."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 15.75e9     # what one v5e chip lets a program use


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def test_flash_attention_compiles_at_edl_paper_width(one_chip):
    """12 heads of 64 at L = 1024 in bf16 (edl-paper's attention)."""
    from repro.kernels.attention.kernel import flash_attention_bhld
    q = jax.ShapeDtypeStruct((1, 12, 1024, 64), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_bhld(q, k, v, interpret=False)
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_compiles_at_rwkv6_width(one_chip):
    """32 heads of 64 at L = 1024 (rwkv6-1.6b's time mixer)."""
    from repro.kernels.rwkv.kernel import wkv6_bhld

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    x = s(1, 32, 1024, 64)
    compiled = jax.jit(
        lambda r, k, v, w, u, s0: wkv6_bhld(r, k, v, w, u, s0,
                                            interpret=False)
    ).lower(x, x, x, x, s(32, 64), s(1, 32, 64, 64)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_edl_paper_train_step_fits_one_chip(topo):
    """The trainer's own step at chip_smoke.py's size: the full edl-paper
    configuration at global batch 8 x 512 on one chip."""
    from repro.configs import get_config
    from repro.core.elastic_runtime import jit_step
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw
    mesh = make_mesh(1, 1, devices=[topo.devices[0]])
    step, args, _, _ = jit_step(get_config("edl-paper"), adamw(1e-3), mesh,
                                seq_len=512, global_batch=8)
    with jax.set_mesh(mesh):
        mem = step.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"
