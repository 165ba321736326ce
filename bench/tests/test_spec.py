"""BENCHMARK.json names only pieces that exist, each found by its name: a
configuration file, a traffic mix, a reader for every metric; and the
command refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def test_every_piece_is_found_by_name(spec):
    import importlib
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        with open(ROOT / c["file"]) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (BENCH / "limits" / f"{w['name']}.json").exists()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"])
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)


def test_limits_lie_between_their_readings(spec):
    for w in spec["workloads"]:
        with open(BENCH / "limits" / f"{w['name']}.json") as f:
            lim = json.load(f)["limits"]
        for name, entry in lim.items():
            if name == "duplicate_samples":
                assert entry["limit"] == 0
                continue
            assert entry["lower"] < entry["limit"] < entry["upper"], name


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-l2.steady",
         "--seed", str((1 << 32) + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_run_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_every_mix_has_a_driver_that_knows_its_keys(spec):
    from bench.harness import driver_module, mix_with_defaults
    for w in spec["workloads"]:
        with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
            mix = json.load(f)
        mod = driver_module(mix)
        assert callable(mod.Driver)
        mix_with_defaults(mix, mod.KEYS)


def test_every_configuration_names_its_reference(spec):
    from bench.reference import for_config
    for c in spec["configs"]:
        with open(ROOT / c["file"]) as f:
            conf = json.load(f)
        ref = for_config(conf)
        assert ref.param_shapes(conf)


def _steady_mix():
    with open(BENCH / "traffic" / "steady.json") as f:
        return json.load(f)


@pytest.mark.parametrize("change,error", [
    ({"kind": "no_such_kind"}, "has no driver"),
    ({"kind": "../harness"}, "has no driver"),
    ({"prefetchh": False}, "has no keys"),
    ({"check_steps": None}, None),
])
def test_a_mix_the_harness_cannot_read_is_refused(change, error):
    from bench.harness import driver_module, mix_with_defaults
    mix = dict(_steady_mix(), **change)
    if error is None:               # a required key left out
        mix.pop("check_steps")
        error = "lacks"
    with pytest.raises(ValueError, match=error):
        mix_with_defaults(mix, driver_module(mix).KEYS)


def test_an_optimizer_the_driver_does_not_have_is_refused():
    from bench.traffic.train import optimizer
    mix = _steady_mix()
    mix["optimizer"] = dict(mix["optimizer"], name="lion")
    with pytest.raises(ValueError, match="lion"):
        optimizer(mix)
