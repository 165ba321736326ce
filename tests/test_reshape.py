"""The state-resharding subsystem (repro.reshape) and the checkpoint-based
reparallelization fallback.

Fast tests run the planner and the numpy reference executor over a REAL
train state (the smoke config's params + adamw moments) for every
``(dp, mp)`` shape of a 4-device budget — device-free via
``StateSpec.for_config``. Property: applying ``plan(a, b)`` then
``plan(b, a)`` is the identity on every shard of every tensor
(deterministic exhaustive cases; no hypothesis dependency per repo
convention). The slow test drives the on-disk path on forced host
devices: a checkpoint saved at ``(dp=2, mp=2)`` resumes at ``(dp=4,
mp=1)`` with the loss trajectory of the uninterrupted run.
"""
import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.reshape import (StateSpec, apply_plan_host, assemble_state,
                           flatten_tree, plan_reshard, shard_state)
from repro.reshape.spec import TensorLayout

ROOT = os.path.join(os.path.dirname(__file__), "..")

# every (dp, mp) shape that fits a 4-device budget, incl. non-power-of-2
SHAPES = [(dp, mp) for dp, mp in itertools.product((1, 2, 3, 4), repeat=2)
          if dp * mp <= 4]


@pytest.fixture(scope="module")
def train_state():
    """A real train state (host copy): smoke-config params + adamw
    moments + counters — the exact tree the trainer reshards live."""
    import jax
    from repro.configs import get_config
    from repro.optim import adamw
    from repro.training.step import init_train_state
    cfg = get_config("edl-paper", smoke=True)
    opt = adamw(1e-3)
    state = jax.device_get(init_train_state(cfg, opt, jax.random.PRNGKey(0)))
    specs = {shape: StateSpec.for_config(cfg, opt, *shape)
             for shape in SHAPES}
    return state, specs


# ------------------------------------------------------------ StateSpec
def test_state_spec_layouts_follow_the_sharding_rules(train_state):
    state, specs = train_state
    spec = specs[(2, 2)]
    flat = flatten_tree(state)
    assert {t.path for t in spec.tensors} == set(flat)
    assert all(t.shape == flat[t.path].shape for t in spec.tensors)
    # replicated scalars stay replicated; some tensor uses each mesh axis
    assert spec.layout("step").axes == ()
    axes_used = {a for t in spec.tensors for a in t.axes if a}
    assert axes_used == {"data", "model"}
    # moments shard exactly like their parameters
    for t in spec.tensors:
        if t.path.startswith("params/"):
            mu = spec.layout("opt/mu/" + t.path[len("params/"):])
            assert mu.axes == t.axes


def test_state_spec_json_round_trip(train_state):
    _, specs = train_state
    for spec in specs.values():
        assert StateSpec.from_json(json.loads(
            json.dumps(spec.to_json()))) == spec


def test_shard_boxes_tile_the_tensor():
    t = TensorLayout("w", (8, 6), ("data", "model"))
    boxes = [t.box(2, 2, i) for i in range(4)]
    assert boxes[0] == ((0, 4), (0, 3)) and boxes[3] == ((4, 8), (3, 6))
    # non-divisible dims are left whole by construction (spec_for rule)
    t3 = TensorLayout("w", (8, 5), ("data", None))
    assert t3.box(2, 1, 1) == ((4, 8), (0, 5))


# ---------------------------------------------------------------- plans
def test_identity_plan_moves_nothing(train_state):
    _, specs = train_state
    for spec in specs.values():
        plan = plan_reshard(spec, spec)
        assert plan.bytes_moved == 0
        assert all(m.kind == "keep" for m in plan.moves)


def test_plan_classifies_pure_data_axis_moves(train_state):
    _, specs = train_state
    # dp 4 -> 2 with mp fixed: every data-sharded tensor coarsens
    plan = plan_reshard(specs[(4, 1)], specs[(2, 1)])
    kinds = {m.kind for m in plan.moves}
    assert kinds <= {"keep", "allgather"}
    assert any(m.kind == "allgather" for m in plan.moves)
    # and the reverse refines
    back = plan_reshard(specs[(2, 1)], specs[(4, 1)])
    assert any(m.kind == "slice" for m in back.moves)
    # trading data for model parallelism mixes both: a general reshard
    swap = plan_reshard(specs[(4, 1)], specs[(2, 2)])
    assert any(m.kind == "reshard" for m in swap.moves)
    assert swap.bytes_moved > 0 and swap.bytes_kept > 0


def test_plan_rejects_mismatched_collections(train_state):
    _, specs = train_state
    src = specs[(2, 1)]
    missing = StateSpec(2, 1, src.tensors[:-1])
    with pytest.raises(ValueError, match="lacks"):
        plan_reshard(src, missing)
    with pytest.raises(ValueError, match="missing from"):
        plan_reshard(missing, src)
    t0 = next(t for t in src.tensors if t.shape)     # first non-scalar
    resized = StateSpec(2, 1, tuple(
        TensorLayout(t.path, tuple(d + 1 for d in t.shape), t.axes)
        if t.path == t0.path else t for t in src.tensors))
    with pytest.raises(ValueError, match="shape changed"):
        plan_reshard(src, resized)


# ----------------------------------------------- round-trip properties
def test_reshard_round_trip_is_identity_for_every_shape_pair(train_state):
    """The acceptance property: for every (dp, mp) pair on <= 4 devices,
    apply(plan(a, b)) then apply(plan(b, a)) reproduces every source
    shard bit-for-bit, and the intermediate assembles to the original
    global state."""
    state, specs = train_state
    flat = flatten_tree(state)
    for sa, sb in itertools.permutations(SHAPES, 2):
        a, b = specs[sa], specs[sb]
        shards_a = shard_state(a, state)
        shards_b = apply_plan_host(plan_reshard(a, b), shards_a)
        asm = flatten_tree(assemble_state(b, shards_b))
        for path in flat:
            assert np.array_equal(flat[path], asm[path]), (sa, sb, path)
        back = apply_plan_host(plan_reshard(b, a), shards_b)
        for i, (orig, rt) in enumerate(zip(shards_a, back)):
            for path in orig:
                assert np.array_equal(orig[path], rt[path]), \
                    f"{sa}->{sb}->{sa} slot {i} corrupted {path}"


def test_moved_bytes_accounting_is_consistent(train_state):
    """bytes_moved + bytes_kept covers exactly the destination shards,
    and a same-device-count transpose keeps SOMETHING local (the planner
    is not allowed to claim everything moves)."""
    _, specs = train_state
    for sa, sb in [((4, 1), (2, 2)), ((2, 2), (4, 1)), ((2, 1), (1, 2))]:
        plan = plan_reshard(specs[sa], specs[sb])
        total = 0
        for t in specs[sb].tensors:
            per_slot = t.n_elements
            for f in t.factors(*sb):
                per_slot //= f
            total += per_slot * specs[sb].n_devices * 4
        assert plan.bytes_moved + plan.bytes_kept == total, (sa, sb)
        assert plan.bytes_kept > 0, (sa, sb)


# ------------------------------------- checkpoint-based reparallelization
@pytest.mark.slow
def test_checkpoint_saved_at_2x2_resumes_at_4x1_same_loss_trajectory():
    """Satellite regression: a checkpoint written at (dp=2, mp=2) restores
    onto (dp=4, mp=1) — the planner reshards the saved collection — and
    the resumed loss trajectory matches the uninterrupted (2, 2) run's.
    The dataset equals one global batch, so every step consumes the whole
    epoch and the batch content is shape-independent (loss differences
    can only come from a corrupted restore)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
import json, tempfile
import jax
from repro.configs import get_config
from repro.core import ElasticTrainer
from repro.core.stop_resume import checkpoint_save, resume_from_checkpoint
from repro.optim import adamw

def make(p, mp):
    return ElasticTrainer(
        get_config("edl-paper", smoke=True), global_batch=12, seq_len=32,
        init_parallelism=p, model_parallel=mp, optimizer=adamw(1e-3),
        n_samples=12, d_partitions=4, seed=0, devices=jax.devices(),
        use_aot=False)

t1 = make(2, 2)
for _ in range(3):
    t1.step()
ckpt = tempfile.mkdtemp(prefix="edl_reshape_ckpt_")
checkpoint_save(t1, ckpt)
ref = [t1.step()["loss"] for _ in range(3)]    # uninterrupted (2, 2)

t2 = make(4, 1)                                # fresh shape, same seed
meta = resume_from_checkpoint(t2, ckpt)
assert t2.step_idx == 3, t2.step_idx
got = [t2.step()["loss"] for _ in range(3)]
print(json.dumps({"ref": ref, "got": got,
                  "reshard": meta["reshard"],
                  "saved": meta["extra"]}))
"""
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["saved"]["p"] == 2 and res["saved"]["mp"] == 2
    assert res["reshard"]["from"] == [2, 2]
    assert res["reshard"]["to"] == [4, 1]
    np.testing.assert_allclose(res["got"], res["ref"], rtol=1e-4), \
        "cross-shape restore must not disturb the loss trajectory"


# ------------------------------------------- the live move, device to device
MOVES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from jax._src import array as jax_array
from repro.configs import get_config
from repro.core import ElasticTrainer
from repro.optim import adamw
from repro.reshape import StateMove, StateSpec, apply_plan, plan_reshard

fetches = [0]                       # calls of the host fetch, ArrayImpl._value
_value = jax_array.ArrayImpl._value


def counted(self):
    fetches[0] += 1
    return _value.fget(self)


jax_array.ArrayImpl._value = property(counted)


def equal(a, b):
    return all(jax.tree.leaves(jax.tree.map(
        lambda x, y: x.dtype == y.dtype and np.array_equal(x, y), a, b)))


tr = ElasticTrainer(get_config("edl-paper", smoke=True), global_batch=8,
                    seq_len=32, init_parallelism=4, optimizer=adamw(1e-3),
                    n_samples=512, d_partitions=8, seed=0,
                    devices=jax.devices(), time_allowance_s=0.0)
freed = []
tr.on_devices_released = lambda t, devs: freed.extend(devs)
tr.step()
out = {}
move_state = tr._move_state


def walk(name, request):
    seen = []

    def spy(rplan, move, handle):
        before = jax.device_get(tr.state)
        n0 = fetches[0]
        moved = move_state(rplan, move, handle)
        jax.block_until_ready(moved)
        n = fetches[0] - n0
        seen.append({
            "route": move.route, "fetches": n,
            "equal": equal(before, jax.device_get(moved)),
            "shardings": all(jax.tree.leaves(jax.tree.map(
                lambda x, s: x.sharding == s, moved,
                handle.state_shardings))),
            "host_bytes": tr.controller.plan.record.host_bytes})
        return moved

    tr._move_state = spy
    request()
    while tr.controller.plan is not None:
        tr.step()
    tr._move_state = move_state
    rec = tr.controller.history[-1]
    (got,) = seen
    out[name] = dict(got, to=[tr.p, tr.model_parallel], op=rec.op,
                     record_host_bytes=rec.host_bytes)
    tr.step()


walk("release", lambda: tr.release_devices(2))
walk("grant", lambda: tr.grant_devices(list(freed)))
walk("reshape_2x2", lambda: tr.reshape(2, 2))
walk("reshape_4x1", lambda: tr.reshape(4, 1))
walk("migrate", lambda: tr.migrate(1, block=False))

# the instrument sees the host path: plain device_put onto a release's layout
target = tr._build_exec(2, 1)
n0 = fetches[0]
jax.block_until_ready(jax.device_put(tr.state, target.state_shardings))
out["plain_device_put"] = {"fetches": fetches[0] - n0}

# host (numpy) input, as a checkpoint restore gives: the counted fallback
host = jax.device_get(tr.state)
spec = StateSpec.for_trainer(tr)
move = StateMove.between(host, tr.exec.state_shardings)
moved, host_bytes = apply_plan(plan_reshard(spec, spec), host,
                               tr.exec.state_shardings, move)
out["host_input"] = {
    "route": move.route, "host_bytes": host_bytes,
    "state_bytes": sum(x.nbytes for x in jax.tree.leaves(host)),
    "equal": equal(host, jax.device_get(moved)),
    "shardings": all(jax.tree.leaves(jax.tree.map(
        lambda x, s: x.sharding == s, moved, tr.exec.state_shardings)))}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def live_moves():
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MOVES_SCRIPT],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("adjustment,op,to,route", [
    ("release", "scale_in", [2, 1], "shrink"),
    ("grant", "scale_out", [4, 1], "grow"),
    ("reshape_2x2", "reshape", [2, 2], "reshard"),
    ("reshape_4x1", "reshape", [4, 1], "reshard"),
    ("migrate", "migrate", [4, 1], "keep"),
])
def test_every_switch_moves_the_state_device_to_device(live_moves, adjustment,
                                                       op, to, route):
    """Each switch's move is bitwise the state it read, lands on the new
    handle's shardings, and never fetches an array to the host."""
    got = live_moves[adjustment]
    assert (got["op"], got["to"], got["route"]) == (op, to, route)
    assert got["equal"] and got["shardings"]
    assert got["fetches"] == 0
    assert got["host_bytes"] == got["record_host_bytes"] == 0


def test_the_fetch_counter_sees_the_host_path(live_moves):
    """The instrument above is live: a plain ``device_put`` onto a release's
    layout fetches leaves to the host."""
    assert live_moves["plain_device_put"]["fetches"] > 0


@pytest.mark.parametrize("case", ["host_input"])
def test_host_input_takes_the_counted_fallback(live_moves, case):
    got = live_moves[case]
    assert got["route"] == "host"
    assert got["equal"] and got["shardings"]
    assert got["host_bytes"] == got["state_bytes"] > 0
