"""Each cell shape's train step, compiled for a described TPU v5e 2x2 host
that is not attached, fits a chip. Nothing runs, so these say nothing about
results or times. The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library."""
import json
import os
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
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


def _cell(workload):
    from bench.harness import (cell_parts, driver_module, load_benchmark,
                               mix_with_defaults)
    cell, config, traffic = cell_parts(load_benchmark(), workload)
    return cell, config, mix_with_defaults(traffic,
                                           driver_module(traffic).KEYS)


@pytest.mark.parametrize("workload,shape", [
    ("phi3-l2.steady", (1, 1)),
    ("nemo-l2.elastic", (4, 1)),
    ("nemo-l2.elastic", (2, 1)),
    ("nemo-l2.elastic", (2, 2)),
])
def test_cell_step_fits_a_chip(topo, workload, shape):
    from bench.traffic.train import cycle_shapes, optimizer, program_config
    from repro.core.elastic_runtime import jit_step
    from repro.launch.mesh import make_mesh
    cell, config, traffic = _cell(workload)
    assert shape in cycle_shapes(traffic)
    p, mp = shape
    mesh = make_mesh(p, mp, devices=list(topo.devices)[:p * mp])
    step, args, _, _ = jit_step(
        program_config(config), optimizer(traffic), mesh,
        seq_len=traffic["seq_len"], global_batch=traffic["global_batch"])
    with jax.set_mesh(mesh):
        mem = step.lower(*args).compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"
