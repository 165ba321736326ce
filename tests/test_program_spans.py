"""The trainer's profiler spans (``edl.step.*``, ``edl.adjust.*``) and the
``span`` helper they are made with.

A small trainer on four virtual CPU devices runs a few steps, one
``release_devices``, one ``grant_devices`` and one ``reshape`` under
``jax.profiler``; the trace is read back with ``bench.scopes.read``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.obs.trace import span

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import scopes  # noqa: E402

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
import glob, json
import jax
from repro.configs import get_config
from repro.core import ElasticTrainer
from repro.optim import adamw
from repro.reshape import StateSpec, plan_reshard

out = sys.argv[1]
cfg, opt = get_config("edl-paper", smoke=True), adamw(1e-3)
tr = ElasticTrainer(cfg, global_batch=8, seq_len=32, init_parallelism=4,
                    optimizer=opt, n_samples=512, d_partitions=8, seed=0,
                    devices=jax.devices(), time_allowance_s=0.0)
freed = []
tr.on_devices_released = lambda t, devs: freed.extend(devs)
for p, mp in [(2, 1), (2, 2)]:
    tr._build_exec(p, mp)           # prefetch: every switch is a cache hit
tr.step()


def until_done(n_records):
    while len(tr.controller.history) < n_records:
        tr.step()
    tr.step()


jax.profiler.start_trace(out)
tr.step()
tr.release_devices(2)
until_done(1)
tr.grant_devices(list(freed))
until_done(2)
tr.overlap_reshard = False          # the reshape moves inside the stop
tr.reshape(2, 2)
until_done(3)
jax.profiler.stop_trace()
moved = [plan_reshard(StateSpec.for_config(cfg, opt, r.from_p, r.from_mp),
                      StateSpec.for_config(cfg, opt, r.to_p, r.to_mp)
                      ).bytes_moved for r in tr.controller.history]
print(json.dumps({
    "trace": glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True)[0],
    "records": [[r.adj, r.op, r.stop_time] for r in tr.controller.history],
    "moved": moved}))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    out = tmp_path_factory.mktemp("edl_spans")
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT, str(out)],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    return got, scopes.read(got["trace"])


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_step_spans_nest_inside_their_step(traced):
    _, prof = traced
    steps = [s for s in prof.spans if s.name == "edl.step"]
    parts = [s for s in prof.spans if s.name.startswith("edl.step.")]
    assert len(steps) == 7
    assert {s.name for s in parts} == {
        "edl.step.batch", "edl.step.put", "edl.step.dispatch",
        "edl.step.wait", "edl.step.post"}
    for part in parts:
        owner = [s for s in steps if s.args["step"] == part.args["step"]]
        assert len(owner) == 1 and _inside(part, owner[0]), part
    for st in steps:
        assert st.args["p"] * st.args["mp"] in (4, 2)
        mine = [p for p in parts if p.args["step"] == st.args["step"]]
        assert [p.name for p in sorted(mine, key=lambda p: p.start)] == [
            "edl.step.batch", "edl.step.put", "edl.step.dispatch",
            "edl.step.wait", "edl.step.post"]
    put = next(s for s in parts if s.name == "edl.step.put")
    assert put.args["bytes"] > 0 and \
        next(s for s in parts if s.name == "edl.step.batch").args["rows"] == 8


def test_each_adjustment_shares_one_adj(traced):
    got, prof = traced
    by_adj = scopes.adjustments(prof.spans)
    assert sorted(by_adj) == [adj for adj, _, _ in got["records"]] == [0, 1, 2]
    ops = {adj: op for adj, op, _ in got["records"]}
    for adj, by_name in by_adj.items():
        assert {"edl.adjust.request", "edl.adjust.prep",
                "edl.adjust.stop_window", "edl.adjust.ready"} <= set(by_name)
        for name, spans in by_name.items():
            for sp in spans:
                if "op" in sp.args:
                    assert sp.args["op"] == ops[adj], (name, sp.args)
    req = {a: b["edl.adjust.request"][0].args for a, b in by_adj.items()}
    assert [(r["from"], r["to"], r["cache_hit"]) for _, r in
            sorted(req.items())] == [("4x1", "2x1", 1), ("2x1", "4x1", 1),
                                     ("4x1", "2x2", 1)]


def test_moves_carry_the_planners_bytes(traced):
    got, prof = traced
    by_adj = scopes.adjustments(prof.spans)
    # release and grant stage their move in the draining step; the reshape
    # (overlap off) moves inside the stop window
    for adj, name, staged in [(0, "edl.adjust.staged_reshard", 1),
                              (1, "edl.adjust.staged_reshard", 1),
                              (2, "edl.adjust.move", 0)]:
        (move,) = by_adj[adj][name]
        assert move.args["bytes"] == got["moved"][adj] > 0
        assert move.args["host_bytes"] == 0    # the move stays on devices
        (stop,) = by_adj[adj]["edl.adjust.stop_window"]
        assert stop.args["staged"] == staged
        assert scopes.move_ms(by_adj[adj]) > 0


def test_stop_window_contains_ready_and_the_move(traced):
    got, prof = traced
    by_adj = scopes.adjustments(prof.spans)
    for adj, _, stop_time in got["records"]:
        (stop,) = by_adj[adj]["edl.adjust.stop_window"]
        (ready,) = by_adj[adj]["edl.adjust.ready"]
        assert _inside(ready, stop)
        for move in by_adj[adj].get("edl.adjust.move", []):
            assert _inside(move, stop) and move.end <= ready.start
        # the record's stop time is the span's, on the host's clock
        assert stop_time <= (stop.end - stop.start) / 1e9 + 1e-3


def test_step_host_time_leaves_out_the_wait_and_the_switch(traced):
    _, prof = traced
    steps = [s for s in prof.spans if s.name == "edl.step"]
    lo, hi = steps[0].start, steps[-1].end
    host = scopes.step_host_ms(prof.spans, lo, hi)
    mean_step = sum(s.end - s.start for s in steps) / len(steps) / 1e6
    assert 0 < host < mean_step
    assert scopes.adjust_move_ms(prof.spans, lo, hi) > 0


def test_span_records_nothing_without_a_profile(tmp_path):
    with span("edl.test.unrecorded", step=1):
        pass
    with pytest.raises(KeyError, match="passes"):
        with span("edl.test.raises"):
            raise KeyError("passes")

    def body():
        with span("edl.test.value", op="x"):
            return 42
    assert body() == 42
    jax.profiler.start_trace(str(tmp_path))
    with span("edl.test.recorded", step=2, op="y"):
        pass
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {s.name: s.args for s in scopes.read(str(path), "edl.test").spans}
    assert names == {"edl.test.recorded": {"step": 2, "op": "y"}}
