"""The adjustment-overhead pipeline: CompileService priority queue,
speculative shape prefetch, and the executor's prep-yield.

Fast tests exercise the service directly (threads + stub build fns, no
jax) and the executor's prefetch/yield paths through the FakeTrainer
protocol. The slow test runs a REAL trainer in a subprocess on a forced
multi-device host platform and proves the speculative-hit path end to
end: a reshape onto a prefetched shape commits with a warm handle
(``cache_hit``), zero steps of prep, and the reshard bytes staged during
the draining mini-batch."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.core.compile_service import CANCELLED, DONE, FAILED, \
    PRIO_COMMITTED, PRIO_SPECULATIVE, CompileService

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _blocked_service(workers=1):
    """A service whose (single) worker is parked inside a blocker ticket —
    later submits stay PENDING until ``release`` fires, making dequeue
    order observable."""
    svc = CompileService(workers=workers)
    release = threading.Event()
    order = []

    def blocker():
        release.wait(10)
        return "blocked"

    svc.submit("blocker", blocker, priority=PRIO_COMMITTED)
    time.sleep(0.05)            # let the worker pick the blocker up
    return svc, release, order


# ------------------------------------------------- a prep that fails
@pytest.mark.parametrize("engine", ["thread", "compile_service"])
def test_failed_background_prep_raises_at_next_step(engine):
    """A scaling operation whose background compile fails must not sit in
    PREPARING for ever: the next step() raises, the operation is dropped,
    and the job trains on at its old shape."""
    from repro.configs import get_config
    from repro.core import ElasticTrainer
    from repro.core.elastic_runtime import PrepFailed
    from repro.core.scaling import Phase
    trainer = ElasticTrainer(get_config("edl-paper", smoke=True),
                             global_batch=2, seq_len=16, init_parallelism=1,
                             n_samples=64, d_partitions=4)
    if engine == "compile_service":
        trainer.compile_service = CompileService(workers=1)

    def refuse(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: compile refused")
    trainer._build_exec = refuse
    trainer._exec_cache.clear()     # the shape is not warm: prep runs
    trainer.migrate(1, block=False)
    assert trainer.controller.phase is Phase.PREPARING
    assert trainer.join_prep(60)
    with pytest.raises(PrepFailed, match="RESOURCE_EXHAUSTED") as err:
        trainer.step()
    assert isinstance(err.value.__cause__, RuntimeError)
    assert trainer.controller.phase is Phase.IDLE
    assert trainer.step() is not None and trainer.step_idx == 1
    if trainer.compile_service is not None:
        trainer.compile_service.shutdown()


# ------------------------------------------------------------- the queue
def test_committed_outranks_speculative():
    svc, release, order = _blocked_service()
    svc.submit("spec", lambda: order.append("spec"),
               priority=PRIO_SPECULATIVE)
    svc.submit("commit", lambda: order.append("commit"),
               priority=PRIO_COMMITTED)
    release.set()
    assert svc.drain(10)
    assert order == ["commit", "spec"], \
        "a committed prep must dequeue before any speculative one"
    svc.shutdown()


def test_cancel_pending_ticket_never_runs():
    svc, release, order = _blocked_service()
    t = svc.submit("doomed", lambda: order.append("ran"),
                   priority=PRIO_SPECULATIVE)
    assert svc.cancel("doomed") is True
    release.set()
    assert svc.drain(10)
    assert order == [] and t.state == CANCELLED and t.done()
    with pytest.raises(RuntimeError, match="cancelled"):
        t.result(1)
    assert svc.stats()["cancelled"] == 1
    svc.shutdown()


def test_dedup_and_escalation_compile_once():
    svc, release, order = _blocked_service()
    svc.submit("other", lambda: order.append("other"),
               priority=PRIO_SPECULATIVE)
    t1 = svc.submit("k", lambda: order.append("k"),
                    priority=PRIO_SPECULATIVE)
    t2 = svc.submit("k", lambda: order.append("k-dup"),
                    priority=PRIO_SPECULATIVE)
    assert t2 is t1, "a live key dedups to the same ticket"
    t3 = svc.submit("k", lambda: order.append("k-committed"),
                    priority=PRIO_COMMITTED)
    assert t3 is t1 and t1.priority == PRIO_COMMITTED \
        and not t1.speculative, "committed submit escalates in place"
    release.set()
    assert svc.drain(10)
    # escalated "k" outranks the earlier-queued speculative "other",
    # and the original fn runs exactly once
    assert order == ["k", "other"]
    s = svc.stats()
    assert s["deduped"] == 2 and s["escalated"] == 1
    svc.shutdown()


def test_cancel_owner_spares_committed_and_kept():
    svc, release, _ = _blocked_service()
    svc.submit(("s", 1), lambda: 1, priority=PRIO_SPECULATIVE, owner="o")
    svc.submit(("s", 2), lambda: 2, priority=PRIO_SPECULATIVE, owner="o")
    svc.submit(("c", 0), lambda: 3, priority=PRIO_COMMITTED, owner="o")
    svc.submit(("s", 3), lambda: 4, priority=PRIO_SPECULATIVE, owner="x")
    n = svc.cancel_owner("o", keep={("s", 1)})
    assert n == 1, "only the owner's un-kept speculative tickets cancel"
    assert svc.pending_keys("o") == {("s", 1), ("c", 0)}
    assert svc.pending_keys("x") == {("s", 3)}
    release.set()
    assert svc.drain(10)
    svc.shutdown()


def test_done_callback_fires_immediately_when_settled():
    svc = CompileService(workers=1)
    t = svc.submit("k", lambda: 42, priority=PRIO_COMMITTED)
    assert t.result(10) == 42 and t.state == DONE
    fired = []
    t.add_done_callback(lambda tk: fired.append(tk.value))
    assert fired == [42], "callbacks on settled tickets fire inline — " \
        "the speculative-hit path must not wait for a worker"
    svc.shutdown()


def test_failed_compile_surfaces_the_error():
    svc = CompileService(workers=1)

    def boom():
        raise ValueError("no such mesh")

    t = svc.submit("bad", boom, priority=PRIO_COMMITTED)
    assert t.wait(10) and t.state == FAILED
    with pytest.raises(ValueError, match="no such mesh"):
        t.result(1)
    assert svc.stats()["failed"] == 1
    svc.shutdown()


def test_two_preps_make_concurrent_progress():
    """Two committed tickets (two tenants re-targeting at once) must
    overlap in wall time — neither waits for the other's full compile."""
    svc = CompileService(workers=2)
    spans = {}

    def build(owner, dur=0.25):
        t0 = time.monotonic()
        time.sleep(dur)
        spans[owner] = (t0, time.monotonic())

    ta = svc.submit("a", lambda: build("a"), priority=PRIO_COMMITTED,
                    owner="job-a")
    tb = svc.submit("b", lambda: build("b"), priority=PRIO_COMMITTED,
                    owner="job-b")
    assert ta.wait(10) and tb.wait(10)
    (a0, a1), (b0, b1) = spans["a"], spans["b"]
    assert a0 < b1 and b0 < a1, \
        f"preps must overlap in wall time, got a={spans['a']} b={spans['b']}"
    svc.shutdown()


def test_drain_ignores_stale_heap_entries():
    """Cancelled (and escalation-duplicated) heap entries are lazy-deleted
    tombstones; drain must not wait on them."""
    svc, release, _ = _blocked_service()
    svc.submit("stale", lambda: None, priority=PRIO_SPECULATIVE)
    svc.cancel("stale")
    release.set()
    t0 = time.monotonic()
    assert svc.drain(5), "drain hung on a cancelled ticket's heap entry"
    assert time.monotonic() - t0 < 5
    assert svc.stats()["queued"] == 0
    svc.shutdown()


# -------------------------------------------------- executor integration
def _executor(specs, policy, n_devices, **kw):
    from repro.cluster.executor import ClusterExecutor
    from test_cluster import FakeTrainer
    kw.setdefault("trainer_factory", FakeTrainer)
    return ClusterExecutor(specs, policy, devices=list(range(n_devices)),
                           **kw)


class PrefetchFakeTrainer:
    """FakeTrainer + the exec-cache surface ``_prefetch_shapes`` drives
    (``_exec_key`` / ``_exec_cache`` / ``_build_exec``)."""

    def __new__(cls, spec, devices):
        from test_cluster import FakeTrainer
        self = FakeTrainer(spec, devices)
        self._exec_cache = {}
        self.built = []

        def _exec_key(p, mp=None, devices=None):
            mpv = mp or self.model_parallel
            devs = tuple(devices if devices is not None else self.devices)
            return (p, mpv, devs[:p * mpv])

        def _build_exec(p, mp=None, devices=None):
            key = _exec_key(p, mp, devices)
            self.built.append(key)
            self._exec_cache[key] = handle = object()
            return handle

        self._exec_key = _exec_key
        self._build_exec = _build_exec
        return self


def test_executor_prefetch_warms_exec_cache():
    from repro.cluster.job import JobSpec
    from repro.sched.base import MaxThroughput
    ex = _executor([JobSpec("a", 2, 60)], MaxThroughput(), 3,
                   trainer_factory=PrefetchFakeTrainer,
                   resched_every=1, prefetch_shapes=True, prep_yield_s=0)
    ex.run(max_rounds=6)
    tr = ex.jobs[0].trainer
    assert ex.compile_service is not None
    ex.compile_service.drain(10)
    # the policy's likely-next shapes (±1 group) were compiled on idle
    # host threads into the trainer's own exec cache
    specs = [k for k in tr.built if k[0] != tr.p]
    assert specs, f"no speculative shape was prefetched (built={tr.built})"
    assert all(k in tr._exec_cache for k in specs)
    s = ex.compile_service.stats()
    assert s["compiled"] >= 1 and s["failed"] == 0
    ex.close()


def test_executor_prefetch_skips_cached_and_infeasible_shapes():
    from repro.cluster.job import JobSpec
    from repro.sched.base import MaxThroughput
    # 2 devices, both held: every growth shape is infeasible, the shrink
    # shape compiles once and is skipped (cache hit) on later rounds
    ex = _executor([JobSpec("a", 2, 60)], MaxThroughput(), 2,
                   trainer_factory=PrefetchFakeTrainer,
                   resched_every=1, prefetch_shapes=True, prep_yield_s=0)
    ex.run(max_rounds=8)
    tr = ex.jobs[0].trainer
    ex.compile_service.drain(10)
    assert len(tr.built) == len(set(tr.built)), \
        f"a cached shape was rebuilt: {tr.built}"
    assert all(k[0] * k[1] <= 2 for k in tr.built), \
        "prefetched a shape the device pool cannot back"
    ex.close()


def test_prep_yield_returns_when_the_prep_lands():
    """The old fixed sleep burned ``prep_yield_s`` every round even after
    the prep had finished; the yield must return the moment the handle is
    ready — and cost nothing when no job is PREPARING."""
    from repro.cluster.job import JobSpec
    from repro.core.scaling import Phase
    from repro.sched.base import StaticPolicy
    ex = _executor([JobSpec("a", 2, 60)], StaticPolicy(), 2,
                   prep_yield_s=2.0)
    ex.run(max_rounds=1)
    tr = ex.jobs[0].trainer

    # no prep in flight: the full 2 s quantum is NOT owed
    t0 = time.monotonic()
    ex._prep_yield()
    assert time.monotonic() - t0 < 0.2

    # prep in flight that lands after 50 ms: yield wakes with it
    tr.controller.phase = Phase.PREPARING
    landed = threading.Event()

    def join_prep(timeout=None):
        return landed.wait(timeout)

    tr.join_prep = join_prep
    threading.Timer(0.05, landed.set).start()
    t0 = time.monotonic()
    ex._prep_yield()
    elapsed = time.monotonic() - t0
    assert 0.03 < elapsed < 1.0, \
        f"yield should return with the prep (~0.05s), took {elapsed:.2f}s"
    tr.controller.phase = Phase.IDLE
    ex.close()


def test_serialize_prep_disables_the_service():
    from repro.cluster.job import JobSpec
    from repro.sched.base import StaticPolicy
    ex = _executor([JobSpec("a", 1, 10)], StaticPolicy(), 1,
                   serialize_prep=True)
    assert ex.compile_service is None and not ex.prefetch_shapes
    ex.close()
    ex2 = _executor([JobSpec("a", 1, 10)], StaticPolicy(), 1,
                    compile_workers=3)
    assert ex2.compile_service is not None \
        and ex2.compile_service.workers == 3
    assert ex2.stats()["compile_service"]["workers"] == 3
    ex2.close()


# ----------------------------------------------------- likely-next shapes
class _View:
    def __init__(self, n_gpus=8):
        self.n_gpus = n_gpus
        self.now = 0.0
        self.running = {}
        self.pending = []
        self.throughput_model = None


class _Job:
    def __init__(self, alloc=2, mp=1, requested_p=2, mp_auto=False):
        self.jid = 1
        self.alloc = alloc
        self.mp = mp
        self.requested_p = requested_p
        self.requested_mp = mp
        self.mp_auto = mp_auto
        self.inelastic = False
        self.arrival = 0.0
        self.attained_gpu_s = 0.0


def test_likely_next_shapes_default_neighborhood():
    from repro.sched.base import likely_next_shapes
    shapes = likely_next_shapes(object(), _View(), _Job(alloc=2))
    assert (3, 1) in shapes and (1, 1) in shapes
    assert (2, 1) not in shapes, "current shape is never a prediction"


def test_likely_next_shapes_respects_pool_and_limit():
    from repro.sched.base import likely_next_shapes
    shapes = likely_next_shapes(object(), _View(n_gpus=2), _Job(alloc=2),
                                limit=1)
    assert len(shapes) == 1
    assert all(p * mp <= 2 for p, mp in shapes)


def test_tiresias_likely_shapes_cover_its_own_rules():
    from repro.sched.base import likely_next_shapes
    from repro.sched.tiresias import ElasticTiresias
    pol = ElasticTiresias(r=0.5)
    job = _Job(alloc=4, requested_p=4)
    shapes = likely_next_shapes(pol, _View(), job, limit=4)
    assert (5, 1) in shapes, "R2 expansion target"
    assert (3, 1) in shapes, "R1 compaction step"
    assert (2, 1) in shapes, "the QoS floor ceil(r * requested)"


# ------------------------------------------------------------ live (slow)
_LIVE_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.configs import get_config
from repro.core import ElasticTrainer
from repro.core.compile_service import CompileService, PRIO_SPECULATIVE
from repro.optim import adamw

svc = CompileService(workers=2)
cfg = get_config("edl-paper", smoke=True)
tr = ElasticTrainer(cfg, global_batch=12, seq_len=64, init_parallelism=4,
                    optimizer=adamw(1e-3), n_samples=1 << 10,
                    d_partitions=16, devices=jax.devices(), seed=0,
                    compile_service=svc, time_allowance_s=0.1)
tr.run(4)
ticket = svc.submit(tr._exec_key(2, 2), lambda: tr._build_exec(2, 2),
                    priority=PRIO_SPECULATIVE, owner="spec")
spec_steps = 0
while not ticket.done():        # training continues through the compile
    tr.step(); spec_steps += 1
tr.reshape(2, 2, release=False)
rec = tr.wait_for_scaling()
tr.run(2)
loss = float(tr.metrics_log[-1]["loss"])
svc.shutdown()
print(json.dumps({"rec": rec.summary(), "spec_steps": spec_steps,
                  "loss_finite": loss == loss and abs(loss) < 1e9}))
"""


_LIVE_CONCURRENT = r"""
import json, os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.configs import get_config
from repro.core import ElasticTrainer
from repro.core.compile_service import CompileService, RUNNING
from repro.optim import adamw

svc = CompileService(workers=2)
cfg = get_config("edl-paper", smoke=True)
devs = jax.devices()

def mk(dd, seed):
    t = ElasticTrainer(cfg, global_batch=12, seq_len=64, init_parallelism=2,
                       optimizer=adamw(1e-3), n_samples=1 << 10,
                       d_partitions=16, devices=dd, seed=seed,
                       compile_service=svc, time_allowance_s=0.1)
    t.run(3)
    return t

ta, tb = mk(devs[:2], 0), mk(devs[2:], 1)
ta.reshape(1, 2, release=False)         # two tenants re-target at once
tb.reshape(1, 2, release=False)
tka, tkb = ta._prep_ticket, tb._prep_ticket
both_running = False
deadline = time.monotonic() + 120
while time.monotonic() < deadline and not (tka.done() or tkb.done()):
    if tka.state == RUNNING and tkb.state == RUNNING:
        both_running = True
        break
    time.sleep(0.01)
ra = ta.wait_for_scaling()
rb = tb.wait_for_scaling()
ta.run(2); tb.run(2)
svc.shutdown()
print(json.dumps({"a": ra.summary(), "b": rb.summary(),
                  "both_running": both_running}))
"""


@pytest.mark.slow
def test_simultaneous_retargets_commit_without_queueing():
    """The regression `serialize_prep=True` used to cause: with the
    compile service, two jobs' committed preps run CONCURRENTLY — both
    tickets observed in the RUNNING state at once — and both switches
    commit."""
    out = subprocess.run(
        [sys.executable, "-c", _LIVE_CONCURRENT], capture_output=True,
        text=True, timeout=900, cwd=ROOT,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": os.path.join(ROOT, "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    s = json.loads(out.stdout.strip().splitlines()[-1])
    assert s["both_running"], \
        "the two committed preps never compiled concurrently"
    for rec in (s["a"], s["b"]):
        assert rec["op"] == "reshape" and rec["to_mp"] == 2, rec
        assert rec["stop_s"] < 0.5, rec


@pytest.mark.slow
def test_speculative_hit_reshape_commits_warm():
    out = subprocess.run(
        [sys.executable, "-c", _LIVE_SCRIPT], capture_output=True,
        text=True, timeout=900, cwd=ROOT,
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "PYTHONPATH": os.path.join(ROOT, "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    s = json.loads(out.stdout.strip().splitlines()[-1])
    rec, spec_steps = s["rec"], s["spec_steps"]
    assert rec["cache_hit"] is True, rec
    assert rec["steps_during_prep"] == 0, \
        "a warm reshape needs no prep window"
    assert rec["prep_s"] < 0.5 and rec["stop_s"] < 0.05, rec
    assert rec["exec_cache_key"][:2] == [2, 2]
    assert spec_steps >= 1, \
        "training must continue while the speculative compile runs"
    assert s["loss_finite"], "job died after the warm switch"
