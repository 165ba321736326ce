"""The drivers fail loudly: a device leak, a job that does not finish, the
wall deadline and a device count the backend cannot give all end a run with
an error instead of a summary and exit code 0. In process, on the one CPU
device the suite runs on, at the smallest sizes."""
import os

import jax
import pytest

from repro.cluster.executor import ClusterExecutor
from repro.launch import cluster as cluster_driver
from repro.launch import train as train_driver
from repro.launch.devices import pick_devices

TINY = ["--devices", "1", "--batch", "2", "--seq", "16", "--n-samples", "64",
        "--d-partitions", "4", "--json"]
CACHE_KEYS = ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def driver_process(monkeypatch, tmp_path):
    """A driver's main() sets process-wide state (the forced host device
    flag, the compile cache): keep it from leaking into later tests."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    old = {k: getattr(jax.config, k) for k in CACHE_KEYS}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("fault,want", [("none", 0), ("leak", 1),
                                        ("unfinished", 1)])
def test_cluster_driver_exit_code(driver_process, monkeypatch, capsys,
                                  fault, want):
    extra = []
    if fault == "leak":
        # a finishing job's devices never reach the free pool
        monkeypatch.setattr(ClusterExecutor, "_return_devices",
                            lambda self, freed: [])
    if fault == "unfinished":
        extra = ["--max-rounds", "2"]
    rc = cluster_driver.main(["--jobs", "a=resnet50:1:3@0", *TINY, *extra])
    err = capsys.readouterr().err
    assert rc == want, err
    if fault == "leak":
        assert "LEAK" in err and "device leak" in err
    if fault == "unfinished":
        assert "unfinished after 2 round(s): ['a']" in err


def test_train_driver_fails_at_the_wall_deadline(driver_process,
                                                 monkeypatch):
    monkeypatch.setenv("EDL_WALL_LIMIT_S", "0")
    with pytest.raises(TimeoutError, match="EDL_WALL_LIMIT_S"):
        train_driver.main(["--smoke", "--init-p", "1", "--steps", "3",
                           *TINY])


def test_pick_devices_refuses_more_than_the_backend_has(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    n = len(jax.devices())
    assert pick_devices(n) == jax.devices()
    with pytest.raises(RuntimeError, match=f"--devices {n + 1}"):
        pick_devices(n + 1)


@pytest.mark.parametrize("platforms,forced", [(None, True), ("cpu", True),
                                              ("tpu,cpu", True),
                                              ("tpu", False)])
def test_pick_devices_forces_host_devices_unless_another_platform_is_named(
        monkeypatch, platforms, forced):
    """A host without an accelerator emulates the devices it is asked for
    whether or not JAX_PLATFORMS is set; naming only another platform
    leaves XLA's flags alone. (The backend is initialized first, so the
    calls below only read it.)"""
    n = len(jax.devices())
    monkeypatch.setenv("XLA_FLAGS", "")
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    pick_devices(n)
    assert (f"--xla_force_host_platform_device_count={n}"
            in os.environ["XLA_FLAGS"]) is forced
