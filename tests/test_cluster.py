"""Multi-tenant cluster executor: policy-driven device transfers between
LIVE jobs (one job's scale-in funding another's scale-out), transient
loans, checkpoint-based full preemption + re-admission, straggler-triggered
migration, and device conservation (including while a preemption checkpoint
is in flight).

Fast tests drive the full executor loop with a FakeTrainer + FakeCheckpointer
implementing the ElasticTrainer hand-off / checkpointer protocols (no jax,
deterministic). The slow tests run the real driver (repro.launch.cluster) in
a subprocess on a forced multi-device host platform, under Tiresias and
throughput policies — including a real checkpoint-stop preemption to disk
and re-admission on a different device set.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.cluster.executor import ClusterExecutor
from repro.cluster.job import ClusterJob, JobSpec, JobState
from repro.cluster.policy import ScriptedPolicy, make_policy, plan_actions
from repro.core.profiling import ProfileTable, profile
from repro.core.scaling import Phase
from repro.sched.base import MaxThroughput
from repro.sched.throughput import MeasuredModel, step_time

ROOT = os.path.join(os.path.dirname(__file__), "..")


# --------------------------------------------------------------- fake layer
class _Controller:
    phase = Phase.IDLE


class FakeTrainer:
    """ElasticTrainer's executor-facing surface with instant (blocking)
    switches and the analytic step-time of the job's profile (overridable
    via ``step_time_fn`` to fake jobs whose REAL scaling contradicts their
    analytic prior). Owns ``devices``; ``p`` tracks active slices
    separately so a plain scale-in parks devices in the pool (like the
    real trainer) while ``release=True`` hands them back. Group-aware like
    the real trainer: one slice = ``model_parallel`` devices, and grants
    must move whole groups."""

    def __init__(self, spec, devices):
        self.spec = spec
        self.model_parallel = getattr(spec, "model_parallel", 1)
        assert len(devices) % self.model_parallel == 0
        self.devices = list(devices)
        self._p = len(self.devices) // self.model_parallel
        self.controller = _Controller()
        self.injected_delay = {}
        self._flagged_stragglers = []
        self.metrics_log = []
        self.on_devices_released = None
        self.step_count = 0
        self.step_time_fn = None

    @property
    def p(self):
        return self._p

    @property
    def global_batch(self):
        return self.spec.global_batch

    @property
    def worker_ids(self):
        return [f"w{i}" for i in range(self.p)]

    def _step_time(self):
        if self.step_time_fn is not None:
            return self.step_time_fn(self.p)
        return step_time(self.spec.profile, self.p)

    def step(self):
        self.step_count += 1
        m = {"loss": 1.0 / self.step_count, "step": self.step_count,
             "p": self.p, "step_time": self._step_time()}
        self.metrics_log.append(m)
        return m

    def grant_devices(self, devs, *, block=False):
        assert len(devs) % self.model_parallel == 0, \
            "grants move whole device groups"
        self.devices.extend(devs)
        self._p = len(self.devices) // self.model_parallel

    def release_devices(self, n, *, victims=None, block=False):
        assert n < self.p, "cannot release below one slice"
        k = n * self.model_parallel
        freed, self.devices = self.devices[-k:], self.devices[:-k]
        self._p = min(self._p, len(self.devices) // self.model_parallel)
        if self.on_devices_released:
            self.on_devices_released(self, freed)

    # ----- the subset of the elastic surface profile() sweeps drive
    def scale_in(self, n=1, *, victims=None, block=False, release=False):
        if release:
            self.release_devices(n, victims=victims, block=block)
        else:
            assert n < self.p, "cannot scale below one slice"
            self._p -= n            # devices stay parked in the pool

    def scale_out(self, n=1, *, block=False):
        assert self._p + n <= len(self.devices) // self.model_parallel, \
            "no devices in the pool"
        self._p += n

    def wait_for_scaling(self, max_steps=10_000):
        pass                        # fake switches commit instantly

    def run(self, n_steps, *, on_step=None):
        for _ in range(n_steps):
            self.step()
        return n_steps

    def throughput(self, last_n=20):
        return self.spec.global_batch / self._step_time()

    def migrate(self, n=1, *, victims=None, block=False):
        self._flagged_stragglers = []

    def reshape(self, p, mp, *, new_devices=None, block=False,
                release=False):
        """Instant-commit RESHAPE double: same device arithmetic as the
        real verb (grant first, release surplus at 'commit')."""
        if new_devices:
            self.devices.extend(new_devices)
        assert p >= 1 and mp >= 1 and p * mp <= len(self.devices)
        assert self.spec.global_batch % p == 0
        self.model_parallel = mp
        self._p = p
        if release and len(self.devices) > p * mp:
            freed = self.devices[p * mp:]
            self.devices = self.devices[:p * mp]
            if self.on_devices_released:
                self._releasing_op = "reshape"
                try:
                    self.on_devices_released(self, freed)
                finally:
                    self._releasing_op = None


class FakeCheckpointer:
    """Executor checkpointer-protocol double: snapshots the fake trainer's
    step counter in memory. Set ``hold = True`` to keep a save in flight so
    tests can observe CHECKPOINTING device accounting across rounds."""

    def __init__(self):
        self.hold = False
        self.saved: dict[int, int] = {}

    def begin(self, job):
        self.saved[job.jid] = job.trainer.step_count
        job.checkpoint = ("fake-ckpt", job.jid)

    def done(self, job):
        return not self.hold

    def teardown(self, job):
        freed, job.trainer.devices = list(job.trainer.devices), []
        return freed

    def restore(self, job, trainer):
        trainer.step_count = self.saved[job.jid]


def run_fake_cluster(specs, policy, *, rounds=40, resched_every=2,
                     checkpointer=None):
    ex = ClusterExecutor(specs, policy, devices=list(range(4)),
                         resched_every=resched_every,
                         trainer_factory=FakeTrainer,
                         checkpointer=checkpointer or FakeCheckpointer())
    stats = ex.run(max_rounds=rounds)
    return ex, stats


def _find(events, op, name):
    return [e for e in events if e["op"] == op and e["job"] == name]


# ------------------------------------------------- funding under throughput
def test_throughput_policy_scale_in_funds_scale_out():
    """A (vgg19, over-provisioned at requested 3) scales in; the freed
    devices fund B's (resnet50) scale-out past its requested 1 — a
    transient loan — with the device count conserved throughout."""
    specs = [JobSpec("a", 3, 60, profile="vgg19"),
             JobSpec("b", 1, 60, profile="resnet50")]
    ex, stats = run_fake_cluster(specs, MaxThroughput(), rounds=8)
    sin, sout = _find(stats["events"], "scale_in", "a")[0], \
        _find(stats["events"], "scale_out", "b")
    grow = [e for e in sout if e["from_p"] > 0]
    assert grow, "B must scale OUT from its running parallelism"
    assert sin["from_p"] == 3 and sin["to_p"] == 1
    assert grow[0]["to_p"] == 3 and grow[0]["loaned"] == 2, \
        "the grant beyond requested_p is a transient loan"
    assert stats["events"].index(sin) < stats["events"].index(grow[0]), \
        "the scale-in must fund (precede) the scale-out"
    assert stats["conserved"] and stats["max_loaned"] == 2


def test_throughput_loan_reclaimed_on_demand():
    """A later arrival reclaims B's loaned devices via graceful scale-in:
    the loan is transient, not permanent."""
    specs = [JobSpec("a", 3, 60, profile="vgg19"),
             JobSpec("b", 1, 60, profile="resnet50"),
             JobSpec("c", 2, 30, profile="googlenet", arrival=6.0)]
    ex, stats = run_fake_cluster(specs, MaxThroughput(), rounds=16)
    reclaim = _find(stats["events"], "scale_in", "b")
    assert reclaim, "B's loan must be reclaimed after C arrives"
    assert reclaim[0]["round"] >= 6
    c_start = _find(stats["events"], "scale_out", "c")
    assert c_start and c_start[0]["from_p"] == 0, \
        "the reclaimed devices admit C"
    assert stats["conserved"]


# -------------------------------------------------- funding under Tiresias
def test_tiresias_compaction_preempts_and_funds_queued_job():
    """Elastic-Tiresias R1: a queued arrival triggers compaction — the
    lowest-priority donor whose floor cannot be met is preempted outright
    (checkpoint-stop to 0 GPUs, no clamp), another donor shrinks to its QoS
    floor, and the freed devices fund the newcomer's admission."""
    specs = [JobSpec("a", 2, 60, profile="vgg19"),
             JobSpec("b", 2, 60, profile="resnet50"),
             JobSpec("c", 2, 30, profile="googlenet", arrival=6.0)]
    pol = make_policy("elastic-tiresias", quanta=(1.0, 50.0))
    ex, stats = run_fake_cluster(specs, pol, rounds=16)
    pre = _find(stats["events"], "preempt", "b")
    assert pre and pre[0]["to_p"] == 0, "donor b is FULLY preempted"
    shr = _find(stats["events"], "scale_in", "a")
    assert shr and shr[0]["to_p"] == 1, "donor a shrinks to its QoS floor"
    c_start = _find(stats["events"], "scale_out", "c")
    assert c_start and c_start[0]["to_p"] == 2
    assert stats["events"].index(pre[0]) < stats["events"].index(c_start[0]), \
        "the preemption must fund (precede) the admission"
    assert stats["conserved"]


def test_tiresias_expansion_regrows_after_finish():
    """Elastic-Tiresias R2: when the short job finishes, its devices are
    granted back to the running jobs (expansion while gain positive); a
    donor preempted during compaction is re-admitted from its checkpoint
    along the way."""
    specs = [JobSpec("a", 2, 60, profile="vgg19"),
             JobSpec("b", 2, 60, profile="resnet50"),
             JobSpec("c", 2, 6, profile="googlenet", arrival=6.0)]
    pol = make_policy("elastic-tiresias", quanta=(1.0, 50.0))
    ex, stats = run_fake_cluster(specs, pol, rounds=40)
    fin = _find(stats["events"], "finish", "c")
    assert fin, "short job must finish"
    regrow = [e for e in stats["events"] if e["op"] == "scale_out"
              and e["from_p"] > 0 and e["round"] > fin[0]["round"]]
    assert regrow, "freed devices must be re-granted to running jobs"
    assert _find(stats["events"], "preempt", "b"), \
        "compaction fully preempts the donor below its floor"
    b_re = _find(stats["events"], "readmit", "b")
    assert b_re, "the preempted donor is re-admitted from its checkpoint"
    assert ex.jobs[1].summary()["final_step"] == ex.jobs[1].steps_done, \
        "step-count continuity across b's preempt -> re-admit round trip"
    assert stats["conserved"]


# ----------------------------------------------------- straggler migration
def test_straggler_flag_triggers_migration():
    specs = [JobSpec("a", 3, 60, profile="resnet50")]
    ex = ClusterExecutor(specs, make_policy("static"),
                         devices=list(range(3)), trainer_factory=FakeTrainer)
    ex.run(max_rounds=3)
    ex.jobs[0].trainer._flagged_stragglers = ["w1"]
    ex.run(max_rounds=6)
    mig = _find(ex.events, "migrate", "a")
    assert mig, "flagged straggler must trigger a migrate"
    assert ex.jobs[0].n_migrations == 1
    assert ex.jobs[0].trainer._flagged_stragglers == []


# ----------------------------------------------- preemption & re-admission
def test_forced_preempt_readmit_continuity_and_device_set():
    """A scripted 0-GPU round checkpoint-stops the job and returns ALL of
    its devices; re-admission lands on a DIFFERENT device set and training
    continues from the saved step count (no reset, no lost steps)."""
    pol = ScriptedPolicy({2: {0: 0}, 4: {0: 2}})
    ex = ClusterExecutor([JobSpec("a", 2, 12)], pol,
                         devices=list(range(4)), resched_every=2,
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=40)
    pre = _find(stats["events"], "preempt", "a")
    re_ = _find(stats["events"], "readmit", "a")
    assert pre and pre[0]["to_p"] == 0
    assert re_ and re_[0]["to_p"] == 2
    assert set(pre[0]["devices"]) == {0, 1}
    assert set(re_[0]["devices"]) == {2, 3}, \
        "re-admission restores onto a different device set"
    job = ex.jobs[0]
    assert job.state is JobState.FINISHED and job.steps_done == 12
    assert job.summary()["final_step"] == 12, \
        "trainer step count continues across the checkpoint round trip"
    steps = [m["step"] for m in job.trainer.metrics_log]
    assert steps == list(range(steps[0], steps[0] + len(steps))), \
        "strictly consecutive steps after restore (no reset, no skip)"
    assert stats["preemptions"] == 1 and stats["readmissions"] == 1
    assert stats["conserved"]


def test_device_conservation_while_checkpoint_in_flight():
    """While a preemption checkpoint save is in flight the job still OWNS
    its devices: they are neither free nor grantable, and the per-round
    conservation assert accounts them to the CHECKPOINTING job."""
    ck = FakeCheckpointer()
    ck.hold = True
    pol = ScriptedPolicy({2: {0: 0, 1: 4}})
    ex = ClusterExecutor([JobSpec("a", 2, 40), JobSpec("b", 2, 40)], pol,
                         devices=list(range(4)), resched_every=2,
                         trainer_factory=FakeTrainer, checkpointer=ck)
    ex.run(max_rounds=6)        # preemption begins at round 2; save held
    job = ex.jobs[0]
    assert job.state is JobState.CHECKPOINTING
    assert job.jid in ex.checkpointing
    assert job.alloc == 2, "devices stay with the job until the save lands"
    assert len(ex.free) == 0, "held devices are not grantable"
    assert ex.jobs[1].alloc == 2, "b's pending grant cannot be satisfied yet"
    ex._assert_conserved()
    ck.hold = False             # the save lands
    stats = ex.run(max_rounds=20)
    assert ex.jobs[0].state is JobState.PREEMPTED
    assert ex.jobs[0] in ex.pending, "parked jobs are re-admittable demand"
    assert ex.jobs[1].alloc == 4, "the landed checkpoint funds b's grant"
    assert _find(stats["events"], "preempt", "a")
    assert stats["conserved"]


def test_tiresias_demotion_preempts_and_readmits_both_ways():
    """Plain (non-elastic) Tiresias preemptive time-sharing for real: the
    fresh G0 arrival preempts the demoted running job wholesale; once the
    newcomer demotes too, the older job wins its GPUs back — each job is
    re-admitted from its checkpoint and both run to completion."""
    specs = [JobSpec("a", 2, 20, profile="resnet50"),
             JobSpec("b", 4, 12, profile="vgg19", arrival=4.0)]
    pol = make_policy("tiresias", quanta=(0.5, 100.0))
    ex, stats = run_fake_cluster(specs, pol, rounds=80)
    assert stats["finished"] == 2, stats["jobs"]
    for name in ("a", "b"):
        assert _find(stats["events"], "preempt", name), name
        assert _find(stats["events"], "readmit", name), name
    a_pre = _find(stats["events"], "preempt", "a")[0]
    a_re = _find(stats["events"], "readmit", "a")[0]
    assert set(a_re["devices"]) != set(a_pre["devices"]), \
        "a re-admits on the devices its preemptor vacated"
    assert ex.jobs[0].steps_done == 20 and ex.jobs[1].steps_done == 12
    assert ex.jobs[0].summary()["final_step"] == 20
    assert ex.jobs[1].summary()["final_step"] == 12
    assert stats["preemptions"] >= 2 and stats["readmissions"] >= 2
    assert stats["conserved"]


# ------------------------------------------------------- plan_actions unit
def test_plan_actions_preempts_first_and_funds_grows():
    a, b, c = (ClusterJob(i, JobSpec(n, 2, 10, global_batch=12))
               for i, n in enumerate("abc"))
    a.trainer = FakeTrainer(a.spec, [0, 1, 2])     # running at 3
    b.trainer = FakeTrainer(b.spec, [3])           # running at 1
    jobs = {0: a, 1: b, 2: c}
    acts = plan_actions(jobs, {0: 0, 1: 2, 2: 1}, 4)
    kinds = [(x.kind, x.jid) for x in acts]
    assert kinds[0] == ("preempt", 0), "preemptions come first (they fund)"
    assert acts[0].target_p == 0, "a 0-GPU target is a FULL preemption"
    assert ("scale_out", 1) in kinds and ("start", 2) in kinds


def test_plan_actions_leaves_parked_jobs_parked():
    """A 0 target for a job with no live trainer (pending or preempted) is
    a no-op, not an action."""
    j = ClusterJob(0, JobSpec("a", 2, 10))
    assert plan_actions({0: j}, {0: 0}, 4) == []


def test_tiresias_starvation_guard_promotes_parked_job():
    """A preempted job that loses every round to a stream of fresh G0
    arrivals is eventually promoted by the starvation guard and
    re-admitted — full preemption must not let parked jobs starve on disk
    forever (pre-preemption the guard only covered never-started jobs)."""
    specs = [JobSpec("a", 2, 40, profile="resnet50"),
             JobSpec("c1", 4, 6, profile="googlenet", arrival=8.0),
             JobSpec("c2", 4, 6, profile="googlenet", arrival=14.0),
             JobSpec("c3", 4, 6, profile="googlenet", arrival=20.0)]
    pol = make_policy("tiresias", quanta=(0.5, 2.0), starvation_s=15.0)
    ex, stats = run_fake_cluster(specs, pol, rounds=100)
    pre = _find(stats["events"], "preempt", "a")
    re_ = _find(stats["events"], "readmit", "a")
    assert pre, "the fresh G0 arrival preempts demoted a"
    assert re_, "parked a must come back via the starvation guard"
    assert re_[0]["round"] >= 16, \
        "promotion fires only once the starvation threshold passes"
    assert ex.jobs[0].state is JobState.FINISHED
    assert stats["conserved"]


def test_revoked_start_want_does_not_launch_later():
    """A start-want the policy later revokes with an explicit 0 target must
    NOT launch once devices free up — the stale want would override the
    policy's current decision."""
    pol = ScriptedPolicy({2: {0: 2, 1: 2},     # b wanted, but no free devs
                          4: {0: 2, 1: 0},     # ...and revoked before any
                          6: {0: 0, 1: 0}})    # a's preemption frees devs
    ex = ClusterExecutor([JobSpec("a", 2, 40), JobSpec("b", 2, 40)], pol,
                         devices=list(range(2)), resched_every=2,
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    ex.run(max_rounds=10)
    assert ex.jobs[0].state is JobState.PREEMPTED
    assert ex.jobs[1].trainer is None, \
        "b's revoked want must not admit it against the 0 target"
    assert len(ex.free) == 2
    ex._assert_conserved()


def test_close_discards_unreachable_checkpoints(tmp_path):
    """close() drops parked-job checkpoint dirs — their handles live only
    in this process, so nothing can re-admit them after it exits."""
    from repro.cluster.executor import DiskCheckpointer
    ex = ClusterExecutor([JobSpec("a", 2, 40)], make_policy("static"),
                         devices=list(range(2)), trainer_factory=FakeTrainer,
                         checkpointer=DiskCheckpointer())
    d = tmp_path / "ckpt"
    d.mkdir()
    (d / "state.npz").write_bytes(b"x")
    ex.jobs[0].checkpoint = str(d)
    ex.close()
    assert ex.jobs[0].checkpoint is None and not d.exists()


def test_checkpoint_stop_resume_real_trainer_continuity():
    """core.stop_resume mid-run entry points on a REAL trainer: stop to
    disk, tear down (devices returned), rebuild fresh, resume — step
    counter, loss trajectory and data-pipeline progress all continue."""
    import tempfile
    from repro.cluster.executor import default_trainer_factory
    from repro.core import Busy, checkpoint_stop, resume_from_checkpoint

    import jax
    spec = JobSpec("a", 1, 10, global_batch=4, n_samples=64, d_partitions=4)
    t1 = default_trainer_factory(spec, jax.devices()[:1])
    for _ in range(3):
        t1.step()
    samples_before = t1.samples_seen
    with tempfile.TemporaryDirectory() as ckpt:
        # Busy guard: a checkpoint mid-switch would capture a dying topology
        t1.controller.admit("scale_out", 1, 1)
        with pytest.raises(Busy):
            checkpoint_stop(t1, ckpt)
        t1.controller.abort()
        devices = checkpoint_stop(t1, ckpt)
        assert devices and t1.devices == [] and t1.state is None
        t2 = default_trainer_factory(spec, devices)
        resume_from_checkpoint(t2, ckpt)
        assert t2.step_idx == 3 and t2.samples_seen == samples_before
        m = t2.step()
        assert m["step"] == 4, "step counter continues, no reset"
        assert m["loss"] < 12.0 and m["loss"] == m["loss"], "finite loss"


def test_partial_grant_lands_on_feasible_parallelism():
    """A grant truncated by pool availability must itself divide the
    global batch: job at p=2 wanting 6 with only 3 free gets +2 (to 4),
    never +3 (12 % 5 != 0 would raise inside the trainer)."""
    specs = [JobSpec("a", 2, 40, profile="resnet50", global_batch=12),
             JobSpec("hog", 1, 4, profile="vgg19", global_batch=12)]
    ex = ClusterExecutor(specs, make_policy("static"),
                         devices=list(range(6)), trainer_factory=FakeTrainer)
    ex.run(max_rounds=2)            # a=2, hog=1 -> 3 free
    ex._wants[0] = (6, 1)           # wants are (groups, mp)
    ex._satisfy_wants()
    assert ex.jobs[0].alloc == 4
    ex._assert_conserved()


def test_plan_actions_respects_batch_divisibility():
    j = ClusterJob(0, JobSpec("a", 1, 10, global_batch=12))
    j.trainer = FakeTrainer(j.spec, [0])
    acts = plan_actions({0: j}, {0: 5}, 8)      # 12 % 5 != 0 -> 4
    assert acts[0].target_p == 4


# ------------------------------- device groups (model-parallel tenants)
def test_mixed_mp_canonical_packing():
    """The canonical mixed-mp scenario: a 4-GPU mp=2 tenant competing with
    four mp=1 tenants on an 8-device pool. Policies count groups, the pool
    counts devices — everyone is admitted, every grant to the mp=2 tenant
    moves a whole 2-device group, and conservation holds in devices."""
    specs = [JobSpec("big", 2, 40, profile="resnet50", model_parallel=2),
             *(JobSpec(f"s{i}", 1, 40, profile="googlenet")
               for i in range(4))]
    ex = ClusterExecutor(specs, MaxThroughput(), devices=list(range(8)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    ex.run(max_rounds=8)
    big = ex.jobs[0]
    assert big.alloc >= 1 and big.devices_held == 2 * big.alloc, \
        "the mp=2 tenant holds exactly 2 devices per replica"
    assert all(ex.jobs[i].alloc >= 1 for i in range(1, 5)), \
        "every mp=1 tenant is admitted alongside the group tenant"
    for e in ex.events:
        if e["jid"] == 0 and "devices" in e:
            assert len(e["devices"]) % 2 == 0, \
                f"group tenant moved a partial group: {e}"
    ex._assert_conserved()


def test_mixed_mp_loan_reclaim_conserves_devices():
    """Transient loans in group units: the mp=2 tenant is loaned a whole
    extra group (2 devices at once) beyond its requested 1; the reclaim
    releases the same whole group, which then funds an mp=1 grant."""
    pol = ScriptedPolicy({2: {0: 2, 1: 1},    # loan big a 2nd group
                          6: {0: 1, 1: 3}})   # reclaim funds s0's growth
    specs = [JobSpec("big", 1, 60, profile="resnet50", model_parallel=2),
             JobSpec("s0", 1, 60, profile="googlenet")]
    ex = ClusterExecutor(specs, pol, devices=list(range(5)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    ex.run(max_rounds=10)
    loan = [e for e in _find(ex.events, "scale_out", "big")
            if e["from_p"] == 1]
    assert loan and len(loan[0]["devices"]) == 2 and loan[0]["mp"] == 2, \
        "the loan arrives as one whole 2-device group"
    assert loan[0]["loaned"] == 1, "loaned counts GROUPS beyond requested"
    reclaim = _find(ex.events, "scale_in", "big")
    assert reclaim and len(reclaim[0]["devices"]) == 2, \
        "the reclaim frees the whole group at once"
    assert ex.jobs[1].alloc == 3, "the freed group funds the mp=1 grant"
    assert ex.jobs[0].devices_held == 2
    ex._assert_conserved()


def test_mixed_mp_preempt_readmit_holds_group_devices():
    """Preemption with mp=2: while the checkpoint save is in flight the
    job's whole GROUP (2 devices, 1 replica) stays accounted to it; the
    landed save frees both devices, and re-admission lands on a whole
    group with the step counter intact."""
    ck = FakeCheckpointer()
    ck.hold = True
    pol = ScriptedPolicy({2: {0: 0, 1: 2},    # preempt big, grow s
                          6: {0: 1, 1: 1}})   # shrink s, re-admit big
    specs = [JobSpec("big", 1, 30, profile="resnet50", model_parallel=2),
             JobSpec("s", 1, 30, profile="googlenet")]
    ex = ClusterExecutor(specs, pol, devices=list(range(3)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=ck)
    ex.run(max_rounds=4)
    big = ex.jobs[0]
    assert big.state is JobState.CHECKPOINTING
    assert big.devices_held == 2 and big.alloc == 1, \
        "the whole in-flight group counts against the checkpointing job"
    assert len(ex.free) == 0
    ex._assert_conserved()
    ck.hold = False                 # the save lands
    ex.run(max_rounds=40)
    pre = _find(ex.events, "preempt", "big")
    re_ = _find(ex.events, "readmit", "big")
    assert pre and len(pre[0]["devices"]) == 2, \
        "landing the save frees BOTH group devices"
    assert re_ and len(re_[0]["devices"]) == 2 and re_[0]["to_p"] == 1, \
        "re-admission grants one whole group"
    steps = [m["step"] for m in big.trainer.metrics_log]
    assert steps == list(range(steps[0], steps[0] + len(steps))), \
        "step counter continues across the group preempt round trip"
    ex._assert_conserved()


def test_plan_actions_clamps_mp_target_to_device_capacity():
    """A policy target of 3 groups for an mp=2 tenant on a 4-device pool
    is clamped to the 2 groups that physically fit."""
    j = ClusterJob(0, JobSpec("big", 1, 10, global_batch=12,
                              model_parallel=2))
    j.trainer = FakeTrainer(j.spec, [0, 1])
    acts = plan_actions({0: j}, {0: 3}, 4)
    assert acts[0].target_p == 2


def test_parse_jobs_mp_grammar():
    """Spec grammar: ``name=profile:p:steps[:mp=M]@arrival``."""
    from repro.launch.cluster import parse_jobs
    kw = dict(batch=12, seq=64, n_samples=1 << 10, d_partitions=16)
    specs = parse_jobs("big=vgg19:1:12:mp=2@3,a=resnet50:2:8@0", **kw)
    assert specs[0].model_parallel == 2 and specs[0].arrival == 3.0
    assert specs[0].requested_p == 1
    assert specs[1].model_parallel == 1, "mp defaults to 1"
    with pytest.raises(ValueError, match="unknown spec field"):
        parse_jobs("a=resnet50:1:8:zz=3@0", **kw)
    with pytest.raises(ValueError, match="model_parallel"):
        parse_jobs("a=resnet50:1:8:mp=0@0", **kw)
    assert parse_jobs("a=resnet50:2:8@0", default_mp=2,
                      **kw)[0].model_parallel == 2


def test_executor_rejects_infeasible_mp():
    """An mp no pool group can ever satisfy is a configuration error, not
    a job that silently queues forever."""
    with pytest.raises(ValueError, match="infeasible"):
        ClusterExecutor([JobSpec("big", 1, 10, model_parallel=8)],
                        make_policy("static"), devices=list(range(4)),
                        trainer_factory=FakeTrainer)


def test_profile_sweep_steps_by_groups():
    """profile() on an mp=2 trainer: the sweep steps whole groups and the
    table's per_gpu column is per DEVICE (throughput / (p * mp))."""
    tr = FakeTrainer(JobSpec("big", 2, 60, profile="resnet50",
                             model_parallel=2), [0, 1, 2, 3])
    table = profile(tr, 1, 2, steps_per_p=2)
    assert sorted(table.entries) == [1, 2]
    assert table[2].per_gpu == pytest.approx(table[2].throughput / 4)
    assert tr.p == 2 and len(tr.devices) == 4, \
        "trainer restored with all group devices"


def test_executor_profile_sweep_borrows_whole_groups():
    """Opt-in sweep on an mp=2 tenant: idle devices are borrowed two at a
    time, the measured curve lands, and every device comes home."""
    mm = MeasuredModel()
    ex = ClusterExecutor(
        [JobSpec("big", 1, 40, profile="resnet50", model_parallel=2)],
        make_policy("static"), devices=list(range(6)),
        trainer_factory=FakeTrainer, checkpointer=FakeCheckpointer(),
        throughput_model=mm, profile_sweeps=True)
    ex.run(max_rounds=6)
    job = ex.jobs[0]
    assert {2, 3} <= set(mm.curve(job)), \
        "the sweep visits every group count the idle pool allowed"
    assert job.alloc == 1 and job.devices_held == 2 and len(ex.free) == 4
    prof = [e for e in ex.events if e["op"] == "profile"]
    assert prof and prof[0]["from_p"] == 3 and prof[0]["to_p"] == 1
    ex._assert_conserved()


# ------------------------------------- live reparallelization (RESHAPE)
def test_plan_actions_emits_reshape_for_mp_retarget():
    """A tuple target whose mp differs from the running job's live degree
    becomes a reshape action — on the shrink side of the ledger when the
    footprint does not grow, so its freed devices fund grows."""
    j = ClusterJob(0, JobSpec("flex", 4, 20, global_batch=12, mp_auto=True))
    j.trainer = FakeTrainer(j.spec, [0, 1, 2, 3])
    other = ClusterJob(1, JobSpec("b", 2, 20, global_batch=12))
    acts = plan_actions({0: j, 1: other}, {0: (1, 2), 1: 2}, 4)
    kinds = [(a.kind, a.jid) for a in acts]
    assert kinds[0] == ("reshape", 0), "footprint-shrinking reshape first"
    assert acts[0].target_p == 1 and acts[0].target_mp == 2
    assert kinds[1] == ("start", 1), "the freed devices fund the start"
    # footprint-growing reshape sorts with the grows (and the group count
    # is clamped to batch divisibility: 8 -> 6 for a global batch of 12)
    grow = plan_actions({0: j}, {0: (8, 2)}, 16)
    assert grow[0].kind == "reshape" and grow[0].target_p == 6


def test_plan_actions_never_reshapes_rigid_tenants():
    """A (groups, mp) tuple against an mp-rigid job is reinterpreted as a
    device budget at the pinned degree — the spec's 'rigid tenants keep
    their degree for life' contract holds against any policy output."""
    j = ClusterJob(0, JobSpec("rigid", 4, 20, global_batch=12))
    j.trainer = FakeTrainer(j.spec, [0, 1, 2, 3])
    acts = plan_actions({0: j}, {0: (1, 2)}, 4)     # 2-device budget
    assert [a.kind for a in acts] == ["scale_in"]
    assert acts[0].target_p == 2, "the budget lands at the pinned mp=1"


def test_scripted_reshape_shrink_frees_devices_for_admission():
    """RESHAPE (4, mp=1) -> (1, mp=2): the re-mesh halves the job's
    footprint; the 2 freed devices come home through the release hook and
    fund the waiting tenant's admission. Conservation in devices holds
    throughout and the job's live mp flips."""
    pol = ScriptedPolicy({2: {0: (1, 2), 1: 2}})
    specs = [JobSpec("flex", 4, 60, profile="vgg19", mp_auto=True),
             JobSpec("b", 2, 30, profile="googlenet", arrival=1.0)]
    ex = ClusterExecutor(specs, pol, devices=list(range(4)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=12)
    flex = ex.jobs[0]
    re_ = _find(stats["events"], "reshape", "flex")
    assert re_ and re_[0]["from_p"] == 4 and re_[0]["to_p"] == 1
    assert re_[0]["from_mp"] == 1 and re_[0]["to_mp"] == 2
    assert flex.mp == 2 and flex.alloc == 1 and flex.devices_held == 2
    freed = _find(stats["events"], "reshape_release", "flex")
    assert freed and len(freed[0]["devices"]) == 2, \
        "the footprint shrink releases exactly the surplus devices"
    assert not _find(stats["events"], "scale_in", "flex"), \
        "a reshape surplus must not masquerade as a data-parallel scale_in"
    b_start = _find(stats["events"], "scale_out", "b")
    assert b_start and b_start[0]["from_p"] == 0, \
        "the freed devices admit the waiting tenant"
    assert stats["events"].index(re_[0]) < stats["events"].index(b_start[0])
    assert stats["reshapes"] == 1 and stats["conserved"]


def test_scripted_reshape_grow_grants_devices_up_front():
    """RESHAPE (1, mp=2) -> (4, mp=1): the footprint doubles; the delta is
    granted from the free pool on the reshape event itself (ownership
    moves at request, like any grant)."""
    pol = ScriptedPolicy({2: {0: (4, 1)}})
    specs = [JobSpec("flex", 1, 60, profile="vgg19", model_parallel=2,
                     mp_auto=True)]
    ex = ClusterExecutor(specs, pol, devices=list(range(4)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=8)
    flex = ex.jobs[0]
    re_ = _find(stats["events"], "reshape", "flex")
    assert re_ and (re_[0]["from_p"], re_[0]["to_p"]) == (1, 4)
    assert (re_[0]["from_mp"], re_[0]["to_mp"]) == (2, 1)
    assert len(re_[0]["devices"]) == 2, "the grant rides the reshape event"
    assert flex.mp == 1 and flex.alloc == 4 and len(ex.free) == 0
    assert stats["conserved"]


def test_reshape_short_on_devices_waits_as_want():
    """A footprint-growing reshape with nothing free parks as a want and
    fires once another job's finish frees the devices."""
    pol = ScriptedPolicy({2: {0: (4, 1), 1: 1}})
    specs = [JobSpec("flex", 1, 60, profile="vgg19", model_parallel=2,
                     mp_auto=True),
             JobSpec("short", 2, 3, profile="googlenet")]
    ex = ClusterExecutor(specs, pol, devices=list(range(4)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=16)
    fin = _find(stats["events"], "finish", "short")
    re_ = _find(stats["events"], "reshape", "flex")
    assert fin and re_, "the reshape must wait for the finish"
    assert re_[0]["round"] >= fin[0]["round"]
    assert ex.jobs[0].mp == 1 and ex.jobs[0].alloc == 4
    assert stats["conserved"]


def test_preempted_auto_job_readmits_onto_different_mp():
    """The checkpoint fallback path at the executor level: an mp=auto job
    preempted at (2, mp=1) is re-admitted at (1, mp=2) — the restore lands
    on a different degree than the save, step counter intact."""
    pol = ScriptedPolicy({2: {0: 0, 1: 4},      # preempt flex, grow b
                          6: {0: (1, 2), 1: 2}})  # readmit at mp=2
    specs = [JobSpec("flex", 2, 30, profile="vgg19", mp_auto=True),
             JobSpec("b", 2, 60, profile="googlenet")]
    ex = ClusterExecutor(specs, pol, devices=list(range(4)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=20)
    flex = ex.jobs[0]
    assert _find(stats["events"], "preempt", "flex")
    re_ = _find(stats["events"], "readmit", "flex")
    assert re_ and re_[0]["to_p"] == 1 and re_[0]["mp"] == 2, \
        "re-admission lands one 2-device group"
    assert len(re_[0]["devices"]) == 2
    assert flex.trainer.model_parallel == 2
    steps = [m["step"] for m in flex.trainer.metrics_log]
    assert steps == list(range(steps[0], steps[0] + len(steps))), \
        "step counter continues across the cross-shape round trip"
    assert stats["conserved"]


def test_elastic_tiresias_compacts_auto_tenant_live_under_pressure():
    """End-to-end policy flow on the fake executor: a fresh arrival
    squeezes the running mp=auto vgg tenant — instead of a full
    preemption it RESHAPEs onto the denser (1, mp=2) mesh, freeing half
    its devices for the newcomer; when the newcomer finishes, the tenant
    reshapes back toward plain data parallelism."""
    specs = [JobSpec("flex", 4, 200, profile="vgg19", mp_auto=True),
             JobSpec("goog", 2, 8, profile="googlenet", arrival=4.0)]
    pol = make_policy("elastic-tiresias", quanta=(0.5, 50.0))
    ex, stats = run_fake_cluster(specs, pol, rounds=60)
    compact = [e for e in _find(stats["events"], "reshape", "flex")
               if e["to_mp"] == 2]
    assert compact and compact[0]["from_p"] == 4 and \
        compact[0]["to_p"] == 1, "pressure compacts (4,1) -> (1,2)"
    assert not _find(stats["events"], "preempt", "flex"), \
        "the flexible tenant is reshaped, not checkpoint-stopped"
    g_start = _find(stats["events"], "scale_out", "goog")
    assert g_start and g_start[0]["from_p"] == 0, \
        "the freed half funds the arrival"
    fin = _find(stats["events"], "finish", "goog")
    expand = [e for e in _find(stats["events"], "reshape", "flex")
              if e["to_mp"] == 1 and e["round"] > fin[0]["round"]]
    assert expand, "freed devices expand the tenant back to mp=1"
    assert ex.jobs[0].mp == 1 and ex.jobs[0].alloc == 4
    assert stats["conserved"]


def test_parse_jobs_mp_auto_grammar():
    from repro.launch.cluster import parse_jobs
    kw = dict(batch=12, seq=64, n_samples=1 << 10, d_partitions=16)
    specs = parse_jobs("flex=vgg19:4:20:mp=auto@0,b=resnet50:1:8:mp=2@0",
                       **kw)
    assert specs[0].mp_auto and specs[0].model_parallel == 1
    assert not specs[1].mp_auto and specs[1].model_parallel == 2


def test_workload_auto_mp_choice_draws_reshapeable_tenants():
    from repro.launch.cluster import parse_workload
    specs = parse_workload("trace=philly seed=1 jobs=8 steps=4:8 mp=1:auto",
                           devices=4, batch=12, seq=64, n_samples=1 << 10,
                           d_partitions=16)
    assert any(s.mp_auto for s in specs), "some tenants must be mp=auto"
    assert all(s.model_parallel == 1 for s in specs if s.mp_auto)


# ------------------------------------------- profiling sweeps (EDL §5.2)
def test_profile_restores_parallelism_and_returns_table():
    """Bugfix regression: profile() used to leave the trainer parked at
    min_p; it must restore the entry parallelism (devices retained) and
    return a structured ProfileTable."""
    tr = FakeTrainer(JobSpec("a", 4, 60, profile="resnet50"), [0, 1, 2, 3])
    table = profile(tr, 1, 4, steps_per_p=3)
    assert isinstance(table, ProfileTable)
    assert sorted(table.entries) == [1, 2, 3, 4]
    assert tr.p == 4 and len(tr.devices) == 4, \
        "trainer restored to its entry parallelism, not parked at min_p"
    assert max(pt.efficiency for pt in table.entries.values()) == 1.0
    assert table[1].per_gpu >= table[4].per_gpu, \
        "analytic fake step times: per-GPU throughput decays with p"


def test_profile_skips_infeasible_parallelisms():
    """Parallelisms that do not divide the global batch are skipped, not
    crashed into (the real trainer refuses them)."""
    tr = FakeTrainer(JobSpec("a", 4, 60, global_batch=8), [0, 1, 2, 3])
    table = profile(tr, 1, 4, steps_per_p=3)
    assert sorted(table.entries) == [1, 2, 4]       # 8 % 3 != 0
    assert tr.p == 4


def test_executor_profile_sweeps_prefill_measured_curves():
    """Opt-in profiling mode: idle devices are loaned to a running job for
    ONE scale-in sweep; the measured curve lands in the model, the job
    returns to its scheduled parallelism, and every borrowed device comes
    home (conservation)."""
    mm = MeasuredModel()
    ex = ClusterExecutor([JobSpec("a", 2, 40, profile="resnet50")],
                         make_policy("static"), devices=list(range(4)),
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer(),
                         throughput_model=mm, profile_sweeps=True)
    ex.run(max_rounds=6)
    job = ex.jobs[0]
    assert {2, 3, 4} <= set(mm.curve(job)), \
        "the sweep must prefill every parallelism idle devices allowed"
    assert job.alloc == 2 and len(ex.free) == 2, \
        "the job is back at its scheduled parallelism, loans returned"
    prof = [e for e in ex.events if e["op"] == "profile"]
    assert prof and prof[0]["from_p"] == 4 and prof[0]["to_p"] == 2
    assert prof[0]["loaned"] == 2, \
        "the sweep's borrowed devices are a transient loan (requested 2, " \
        "swept at 4)"
    assert len(prof) == 1, "each job is swept at most once"
    ex._assert_conserved()


def test_profile_ttl_resweeps_stale_curves():
    """Satellite: with a finite profile_ttl the executor re-sweeps a job
    once its measured curve ages out (default stays once-per-lifetime —
    asserted by test_executor_profile_sweeps_prefill_measured_curves)."""
    mm = MeasuredModel()
    ex = ClusterExecutor([JobSpec("a", 2, 200, profile="resnet50")],
                         make_policy("static"), devices=list(range(4)),
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer(),
                         throughput_model=mm, profile_sweeps=True,
                         profile_ttl=4.0)
    ex.run(max_rounds=12)
    prof = [e for e in ex.events if e["op"] == "profile"]
    assert len(prof) >= 2, "the stale curve must be re-swept"
    assert prof[1]["round"] - prof[0]["round"] >= 4, \
        "re-sweep waits out the TTL"
    job = ex.jobs[0]
    assert mm.n_observations(job)[4] >= 2, \
        "the re-sweep re-ingests into the same EMA stream"
    assert job.alloc == 2 and len(ex.free) == 2
    ex._assert_conserved()


def test_executor_free_observations_feed_measured_model():
    """Every live mini-batch is a free observation at the job's current
    parallelism — no sweep needed for the visited point to converge."""
    mm = MeasuredModel()
    ex = ClusterExecutor([JobSpec("a", 2, 40, profile="resnet50")],
                         make_policy("static"), devices=list(range(2)),
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer(),
                         throughput_model=mm)
    ex.run(max_rounds=5)
    job = ex.jobs[0]
    assert mm.n_observations(job).get(2, 0) >= 4
    want = job.spec.global_batch / step_time("resnet50", 2)
    assert abs(mm.throughput(job, 2) - want) < 1e-9


def test_measured_observations_flip_live_allocation():
    """Acceptance: the SAME MaxThroughput policy on the SAME live workload
    allocates differently once measured curves contradict the analytic
    priors — the fake vgg19 job REALLY scales linearly (so it keeps its
    GPUs) while the fake resnet50 job is REALLY flat (so it never gets
    the loan the analytic model would have granted it)."""
    def factory(spec, devices):
        tr = FakeTrainer(spec, devices)
        tr.step_time_fn = ((lambda p: 0.3 / p) if spec.name == "a"
                           else (lambda p: 0.05))
        return tr

    def run(model):
        specs = [JobSpec("a", 3, 60, profile="vgg19"),
                 JobSpec("b", 1, 60, profile="resnet50")]
        ex = ClusterExecutor(specs, MaxThroughput(),
                             devices=list(range(4)), resched_every=2,
                             trainer_factory=factory,
                             checkpointer=FakeCheckpointer(),
                             throughput_model=model)
        if isinstance(model, MeasuredModel):
            # curves as a prior sweep would have measured them
            model.ingest(ex.jobs[0], ProfileTable.from_throughputs(
                {p: 40.0 * p for p in (1, 2, 3, 4)}, batch=12))
            model.ingest(ex.jobs[1], ProfileTable.from_throughputs(
                {p: 240.0 for p in (1, 2, 3, 4)}, batch=12))
        stats = ex.run(max_rounds=8)
        return ex, stats

    ex_a, sa = run(None)        # default analytic
    assert _find(sa["events"], "scale_in", "a"), \
        "analytic prior: vgg19 knees, so a is scaled in"
    assert [e for e in _find(sa["events"], "scale_out", "b")
            if e["from_p"] > 0], "analytic prior: b gets the loan"
    assert (ex_a.jobs[0].alloc, ex_a.jobs[1].alloc) == (1, 3)

    ex_m, sm = run(MeasuredModel())
    assert not _find(sm["events"], "scale_in", "a"), \
        "measured curves keep the real linear scaler at its GPUs"
    assert not [e for e in _find(sm["events"], "scale_out", "b")
                if e["from_p"] > 0], "the flat scaler never gets the loan"
    assert (ex_m.jobs[0].alloc, ex_m.jobs[1].alloc) == (3, 1)
    assert sa["conserved"] and sm["conserved"]


def test_parse_workload_synthesizes_live_specs():
    """--workload feeds the sched.workload trace generators into the LIVE
    executor's spec grammar."""
    from repro.launch.cluster import parse_workload
    specs = parse_workload("trace=philly seed=1 jobs=5 steps=4:8",
                           devices=4, batch=12, seq=64, n_samples=1 << 10,
                           d_partitions=16)
    assert len(specs) == 5
    assert all(4 <= s.total_steps <= 8 for s in specs)
    assert all(12 % s.requested_p == 0 and s.requested_p <= 4
               for s in specs)
    # mp=1:2 draws a mixed-mp population; groups still fit the pool
    mixed = parse_workload("trace=philly seed=1 jobs=8 steps=4:8 mp=1:2",
                           devices=4, batch=12, seq=64, n_samples=1 << 10,
                           d_partitions=16)
    assert {s.model_parallel for s in mixed} == {1, 2}
    assert all(s.requested_p * s.model_parallel <= 4 for s in mixed)
    with pytest.raises(ValueError):
        parse_workload("trace=nope", devices=4, batch=12, seq=64,
                       n_samples=1 << 10, d_partitions=16)


def test_compile_cache_option_configures_jax(tmp_path):
    import jax
    from repro.launch.devices import enable_compile_cache
    old = {k: getattr(jax.config, k) for k in
           ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
    try:
        path = enable_compile_cache(str(tmp_path / "cc"))
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isdir(path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in old.items():
            jax.config.update(k, v)


CACHE_PROBE = """
import os, sys, jax
from repro.launch.devices import DEFAULT_CACHE_DIR, enable_compile_cache
d = enable_compile_cache(sys.argv[1] if len(sys.argv) > 1 else None)
jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(d == str(DEFAULT_CACHE_DIR), d, len(os.listdir(d)))
"""


@pytest.mark.parametrize("source", ["env", "option", "default"])
def test_compile_cache_directory_choice(tmp_path, source):
    """JAX_COMPILATION_CACHE_DIR wins (and is left to JAX itself), then
    the --compile-cache directory, then the fixed .jax_cache/ of the
    checkout; the compile lands in the one chosen."""
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    argv = []
    if source == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "env")
        argv = [str(tmp_path / "option")]
    elif source == "option":
        argv = [str(tmp_path / "option")]
    out = subprocess.run([sys.executable, "-c", CACHE_PROBE, *argv],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    is_default, where, n_entries = out.stdout.split()
    assert int(n_entries) >= 1, "the compile was not cached there"
    if source == "default":
        assert is_default == "True"
    else:
        assert where == str(tmp_path / source)
        assert not (tmp_path / "option").exists() or source == "option"


# ------------------------------------ one policy interface, two substrates
def test_max_throughput_drives_the_simulator_too():
    """The same policy object schedules the discrete-event simulator —
    the shared view interface of sched.base."""
    from repro.sched.simulator import ClusterSimulator, ScalingCosts
    from repro.sched.workload import synthetic_16
    stats = ClusterSimulator(32, synthetic_16(), MaxThroughput(),
                             costs=ScalingCosts(mode="edl")).run()
    assert stats["finished"] == 16


def test_static_policy_never_resizes():
    specs = [JobSpec("a", 2, 30, profile="vgg19"),
             JobSpec("b", 2, 30, profile="resnet50")]
    ex, stats = run_fake_cluster(specs, make_policy("static"), rounds=40)
    resizes = [e for e in stats["events"]
               if e["op"] in ("scale_in",)
               or (e["op"] == "scale_out" and e["from_p"] > 0)]
    assert resizes == []
    assert stats["finished"] == 2


# ----------------------------------------------------------- live (slow)
def run_cluster_driver(*extra, devices=4, timeout=900):
    cmd = [sys.executable, "-m", "repro.launch.cluster", "--json",
           "--devices", str(devices), *extra]
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_live_cluster_throughput_policy_transfers_devices():
    s = run_cluster_driver(
        "--policy", "throughput",
        "--jobs", "a=vgg19:3:20@0,b=resnet50:1:25@0,c=googlenet:1:12@6")
    assert s["conserved"] is True
    assert s["finished"] == 3, s["jobs"]
    sin = [e for e in s["events"] if e["op"] == "scale_in"]
    grow = [e for e in s["events"] if e["op"] == "scale_out"
            and e["from_p"] > 0]
    assert sin and grow, "need a live scale_in funding a live scale_out"
    assert any(s["events"].index(i) < s["events"].index(g)
               and i["jid"] != g["jid"] for i in sin for g in grow)
    assert s["max_loaned"] >= 1, "transient loan must occur"
    for j in s["jobs"]:     # all three trained for real
        assert j["final_loss"] is not None


@pytest.mark.slow
def test_live_cluster_preempts_to_checkpoint_and_readmits():
    """Tiresias preemptive time-sharing on REAL trainers: the big G0
    arrival checkpoint-stops the running tenant to disk (all devices
    returned), and the parked tenant is later re-admitted on a different
    device set with its step count / train state restored — per-round
    device conservation holding throughout."""
    s = run_cluster_driver(
        "--policy", "tiresias", "--quanta", "0.1,1000",
        "--jobs", "a=resnet50:2:20@0,b=vgg19:4:12@6",
        timeout=1200)
    assert s["conserved"] is True
    assert s["finished"] == 2, s["jobs"]
    a_pre = [e for e in s["events"]
             if e["op"] == "preempt" and e["job"] == "a"]
    a_re = [e for e in s["events"]
            if e["op"] == "readmit" and e["job"] == "a"]
    assert a_pre, "the 0-GPU target must checkpoint-stop the live job"
    assert a_re, "the parked job must be re-admitted from its checkpoint"
    assert s["events"].index(a_pre[0]) < s["events"].index(a_re[0])
    assert a_pre[0]["to_p"] == 0 and len(a_pre[0]["devices"]) == 2, \
        "preemption returns ALL devices, not all-but-one"
    assert set(a_re[0]["devices"]) != set(a_pre[0]["devices"]), \
        "re-admission restores onto a different device set"
    for j in s["jobs"]:
        want = {"a": 20, "b": 12}[j["name"]]
        assert j["steps_done"] == want, j
        assert j["final_step"] == want, \
            "restored trainer continues its step count (state continuity)"
        assert j["final_loss"] is not None
    assert s["preemptions"] >= 1 and s["readmissions"] >= 1


@pytest.mark.slow
def test_live_cluster_measured_model_on_workload_trace(tmp_path):
    """Live end-to-end of the new seams: a synthesized arrival trace
    (--workload) drives REAL trainers scheduled from a MeasuredModel fed
    by live step times, with a persistent compilation cache enabled."""
    cache = tmp_path / "xla-cache"
    s = run_cluster_driver(
        "--policy", "throughput", "--throughput-model", "measured",
        "--workload", "trace=synthetic seed=0 jobs=2 steps=3:6",
        "--compile-cache", str(cache), "--max-rounds", "250",
        timeout=1200)
    assert s["conserved"] is True
    assert s["throughput_model"] == "MeasuredModel"
    assert s["finished"] == 2, s["jobs"]
    for j in s["jobs"]:
        assert j["final_loss"] is not None
    assert cache.is_dir() and any(cache.iterdir()), \
        "the persistent compilation cache must be written to"


@pytest.mark.slow
@pytest.mark.parametrize("model", ["analytic", "measured"])
def test_bench_smoke_cluster_under_both_models(model):
    """`make bench-smoke` contract: the cluster benchmark runs a tiny live
    config under BOTH --throughput-model settings and emits its CSV."""
    cmd = [sys.executable, "benchmarks/cluster_bench.py",
           "--policies", "throughput", "--throughput-model", model,
           "--jobs", "a=vgg19:2:6@0,b=resnet50:1:8@0",
           "--max-rounds", "150"]
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"cluster_throughput_{model}," in out.stdout


@pytest.mark.slow
def test_live_cluster_mixed_mp_tenants_conserve_device_groups():
    """Acceptance: one mp=2 tenant (2-D data x model mesh) and two mp=1
    tenants share a 4-device pool under the throughput policy. All three
    run stop-free to completion, every device movement of the group
    tenant is a whole 2-device group, per-round device conservation held
    (the run would have died on the executor's assert otherwise), and the
    group tenant scales live at least once."""
    s = run_cluster_driver(
        "--policy", "throughput",
        "--jobs", "big=vgg19:1:20:mp=2@0,a=resnet50:1:8@0,"
                  "b=googlenet:1:6@0",
        timeout=1200)
    assert s["conserved"] is True
    assert s["finished"] == 3, s["jobs"]
    big = [j for j in s["jobs"] if j["name"] == "big"][0]
    assert big["model_parallel"] == 2
    for j in s["jobs"]:
        assert j["final_loss"] is not None, "all three trained for real"
    big_ev = [e for e in s["events"] if e["job"] == "big"]
    assert all(e["mp"] == 2 for e in big_ev)
    for e in big_ev:
        if "devices" in e:
            assert len(e["devices"]) % 2 == 0, \
                f"group tenant moved a partial group: {e}"
            assert len(e["devices"]) == 2 * abs(e["to_p"] - e["from_p"]) \
                or e["op"] == "finish", e
    resizes = [e for e in big_ev
               if e["op"] == "scale_out" and e["from_p"] > 0
               or e["op"] == "scale_in"]
    assert resizes, "the mp=2 tenant must scale live (whole groups)"


@pytest.mark.slow
def test_live_reshape_round_trip_stop_free_with_device_audit():
    """Acceptance: the executor drives a REAL trainer through RESHAPE
    (dp=4, mp=1) -> (dp=2, mp=2) -> (dp=1, mp=2) -> (dp=4, mp=1) at
    mini-batch boundaries, stop-free (training continues through every
    background context prep). Step counters, optimizer state and the
    data pipeline's exactly-once accounting survive every re-mesh, and
    whole-group device conservation is asserted from the event audit
    (the shrink frees a whole group, the expand-back grants it back)."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=4"
import json
import jax
from repro.cluster import ClusterExecutor, JobSpec
from repro.cluster.executor import default_trainer_factory

SHAPES = [(2, 2), (1, 2), (4, 1)]

class ReshapeDriver:
    # target the next shape once the previous one committed: robust to
    # compile latency (Busy reshapes are simply re-planned)
    def __init__(self):
        self.stage = 0
    def __call__(self, view):
        if not view.running:
            return {}
        j = next(iter(view.running.values()))
        if self.stage < len(SHAPES) and (j.alloc, j.mp) == SHAPES[self.stage]:
            self.stage += 1
        if self.stage < len(SHAPES):
            return {j.jid: SHAPES[self.stage]}
        return {j.jid: (j.alloc, j.mp)}

spec = JobSpec("flex", 4, 250, profile="vgg19", mp_auto=True,
               global_batch=12, seq_len=32, n_samples=1 << 10,
               d_partitions=16)
ex = ClusterExecutor([spec], ReshapeDriver(), resched_every=2)
stats = ex.run(max_rounds=2000)
job = ex.jobs[0]
tr = job.trainer
out = {
    "stats": {k: stats[k] for k in ("reshapes", "conserved", "finished")},
    "events": stats["events"],
    "job": job.summary(),
    "samples_seen": tr.samples_seen,
    "opt_count": int(jax.device_get(tr.state["opt"]["count"])),
    "reshape_records": [r.summary() for r in tr.controller.history
                        if r.op == "reshape"],
}
ex.close()
print(json.dumps(out))
"""
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])

    assert res["stats"]["conserved"] is True
    assert res["stats"]["finished"] == 1
    # the middle (2,2)->(1,2) step keeps the degree, so it is correctly a
    # plain mp=2 scale_in, not a reshape — two true re-meshes round-trip
    assert res["stats"]["reshapes"] == 2
    shapes = [((e["from_p"], e["from_mp"]), (e["to_p"], e["to_mp"]))
              for e in res["events"] if e["op"] == "reshape"]
    assert shapes == [((4, 1), (2, 2)), ((1, 2), (4, 1))], shapes

    # stop-free: training continued through every real context prep, and
    # the switch window is far below the prep it hides
    recs = res["reshape_records"]
    assert len(recs) == 2
    assert any(r["steps_during_prep"] >= 1 for r in recs), recs
    assert all(r["stop_s"] < 1.0 for r in recs), recs
    assert all(r["reshard_bytes_moved"] > 0 for r in recs)

    # continuity: step counter, optimizer state, exactly-once accounting
    assert res["job"]["steps_done"] == 250
    assert res["job"]["final_step"] == 250
    assert res["opt_count"] == 250, "optimizer state survived every re-mesh"
    assert res["samples_seen"] == 250 * 12, \
        "exactly-once data accounting: every step consumed one global batch"
    assert res["job"]["final_loss"] is not None
    assert res["job"]["reshapes"] == 2 and res["job"]["mp_now"] == 1

    # whole-group device audit from the events alone
    owned = set()
    for e in res["events"]:
        devs = set(e.get("devices", []))
        if e["op"] in ("scale_out", "readmit"):
            assert not devs & owned
            owned |= devs
        elif e["op"] == "reshape" and devs:
            assert not devs & owned, "a grant must come from outside"
            owned |= devs
        elif e["op"] in ("scale_in", "reshape_release", "preempt",
                         "finish"):
            assert devs <= owned, "cannot free devices the job never owned"
            owned -= devs
        if devs:
            assert len(devs) % e["mp"] == 0 or e["op"] == "reshape", \
                f"partial-group movement: {e}"
    assert owned == set(), "every granted device must come home"
    shrink = [e for e in res["events"] if e["op"] == "scale_in"]
    assert shrink and len(shrink[0]["devices"]) == 2, \
        "the (2,2)->(1,2) shrink frees exactly one whole 2-device group"
    grow = [e for e in res["events"]
            if e["op"] == "reshape" and e.get("devices")]
    assert grow and len(grow[0]["devices"]) == 2, \
        "the (1,2)->(4,1) expand-back grants the group back"


@pytest.mark.slow
def test_reshape_bench_beats_checkpoint_stop_resume():
    """`cluster_bench --reshape` contract: the in-memory RESHAPE's stop
    window is strictly below checkpoint-stop-resume on the SAME
    (4,1)->(2,2) transition, and the CSV lines are emitted."""
    cmd = [sys.executable, "benchmarks/cluster_bench.py", "--reshape"]
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "reshape_in_memory_stop," in out.stdout
    assert "reshape_checkpoint_stop," in out.stdout
    with open(os.path.join(ROOT, "experiments", "bench_reshape.json")) as f:
        res = json.load(f)
    assert res["reshape_beats_checkpoint"] is True
    assert res["in_memory"]["stop_s"] < res["checkpoint"]["stop_s"]
    assert res["in_memory"]["from_mp"] == 1
    assert res["in_memory"]["to_mp"] == 2


@pytest.mark.slow
def test_live_cluster_tiresias_policy_transfers_devices():
    # a outlives the background compile of its shrunk shape even when the
    # persistent compile cache serves c's launch and the rounds run fast
    s = run_cluster_driver(
        "--policy", "elastic-tiresias",
        "--jobs", "a=vgg19:2:40@0,b=resnet50:2:25@0,c=googlenet:2:12@6")
    assert s["conserved"] is True
    assert s["finished"] == 3, s["jobs"]
    sin = [e for e in s["events"] if e["op"] == "scale_in"]
    souts = [e for e in s["events"] if e["op"] == "scale_out"]
    assert sin, "compaction must shrink a donor"
    funded = [o for o in souts for i in sin
              if s["events"].index(i) < s["events"].index(o)
              and i["jid"] != o["jid"]]
    assert funded, "a scale_in must fund another job's scale_out"
