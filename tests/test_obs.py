"""Observability layer (repro.obs): the typed telemetry bus mirrors the
executor's legacy event log 1:1, every COMMITTED parallelism adjustment
becomes a well-nested span tree whose stop-window duration IS the
ScalingRecord's, the Chrome-trace export loads as valid Trace Event
JSON, the Prometheus exposition parses, and the JSONL telemetry stream
validates against the event schema.

The fake cluster here uses an ObsFakeTrainer — a FakeTrainer whose
resizes run through a REAL ScalingController — so committed switches
produce genuine ScalingRecords and fire the executor-attached obs
listener, without any jax in the loop.
"""
import json
import re
import urllib.request

import pytest

from repro.cluster.executor import ClusterExecutor
from repro.cluster.job import JobSpec
from repro.core.scaling import ScalingController
from repro.obs import Observability, SCHEMA_VERSION, validate_event
from repro.obs import report
from repro.obs.audit import assert_ownership, audit_device_ownership
from repro.sched.base import MaxThroughput
from test_cluster import FakeCheckpointer, FakeTrainer


# --------------------------------------------------------------- fake layer
class ObsFakeTrainer(FakeTrainer):
    """FakeTrainer + a REAL ScalingController: every executor-driven
    resize/reshape runs admit -> prepared -> begin_switch -> commit ->
    complete, so it lands a genuine ScalingRecord in ``history`` and
    fires ``controller.listeners`` (where the executor hangs the obs
    adjustment hook). Switches still commit instantly."""

    def __init__(self, spec, devices):
        super().__init__(spec, devices)
        self.controller = ScalingController()

    def _admit(self, op, to_p, to_mp=None):
        plan = self.controller.admit(op, self.p, to_p)
        plan.record.from_mp = self.model_parallel
        plan.record.to_mp = (to_mp if to_mp is not None
                             else self.model_parallel)
        self.controller.prepared(self.step_count + 1, None)
        self.controller.begin_switch()

    def _commit(self, body):
        try:
            body()
        except BaseException:
            self.controller.abort()
            raise
        self.controller.complete()

    def grant_devices(self, devs, *, block=False):
        self._admit("scale_out", self.p + len(devs) // self.model_parallel)
        self._commit(lambda: FakeTrainer.grant_devices(self, devs,
                                                       block=block))

    def release_devices(self, n, *, victims=None, block=False):
        self._admit("scale_in", self.p - n)
        self._commit(lambda: FakeTrainer.release_devices(
            self, n, victims=victims, block=block))

    def reshape(self, p, mp, *, new_devices=None, block=False,
                release=False):
        self._admit("reshape", p, to_mp=mp)
        self._commit(lambda: FakeTrainer.reshape(
            self, p, mp, new_devices=new_devices, block=block,
            release=release))


def run_obs_cluster(specs=None, *, rounds=12, obs=None, n_devices=4,
                    policy=None):
    specs = specs or [JobSpec("a", 3, 60, profile="vgg19"),
                      JobSpec("b", 1, 60, profile="resnet50")]
    obs = obs or Observability()
    ex = ClusterExecutor(specs, policy or MaxThroughput(),
                         devices=list(range(n_devices)), resched_every=2,
                         trainer_factory=ObsFakeTrainer,
                         checkpointer=FakeCheckpointer(), obs=obs)
    stats = ex.run(max_rounds=rounds)
    return ex, stats, obs


@pytest.fixture(scope="module")
def obs_run():
    """One instrumented funding run (A scales in, the freed devices fund
    B's loaned scale-out) shared by the read-only acceptance tests."""
    return run_obs_cluster()


def _committed_records(ex):
    out = []
    for job in ex.jobs.values():
        ctrl = getattr(job.trainer, "controller", None)
        if isinstance(ctrl, ScalingController):
            out.extend((job.spec.name, rec) for rec in ctrl.history)
    return out


# ------------------------------------------------------- bus 1:1 mirroring
def test_bus_mirrors_every_legacy_event(obs_run):
    """Every ``executor.events`` dict has exactly one typed bus event —
    same op, round, tenant and shape — in the same order (``_event`` is
    the single append point and mirrors unconditionally)."""
    ex, stats, obs = obs_run
    assert ex.events, "the run must produce legacy events"
    # mirrored legacy events are the only bus events carrying ``loaned``
    # (adjust/compile/fault events ride their own payloads)
    mirrored = [ev for ev in obs.events() if "loaned" in ev.data]
    assert len(mirrored) == len(ex.events)
    for legacy, ev in zip(ex.events, mirrored):
        assert ev.name == legacy["op"]
        assert ev.round == legacy["round"]
        assert ev.job == legacy["job"]
        assert ev.jid == legacy["jid"]
        assert ev.data["from_p"] == legacy["from_p"]
        assert ev.data["to_p"] == legacy["to_p"]
        assert ev.data["mp"] == legacy["mp"]
        assert ev.schema == SCHEMA_VERSION
        assert validate_event(ev.to_dict()) == []


def test_adjust_events_ride_the_bus_per_committed_switch(obs_run):
    ex, stats, obs = obs_run
    recs = _committed_records(ex)
    assert recs, "the funding workload must commit switches"
    adjust = [ev for ev in obs.events() if ev.kind == "adjust"]
    assert len(adjust) == len(recs)
    for (name, rec), ev in zip(recs, adjust):
        assert ev.job == name and ev.name == rec.op
        assert ev.data["from_p"] == rec.from_p
        assert ev.data["to_p"] == rec.to_p


# ------------------------------------------------------------- span trees
def test_committed_switches_produce_well_nested_span_trees(obs_run):
    """For every ScalingRecord in every trainer's history there is a span
    tree plan|prep|drain|stop_window tiling the root exactly — and the
    stop_window span's duration IS ``rec.stop_time`` (same floats, not a
    re-measurement)."""
    ex, stats, obs = obs_run
    recs = _committed_records(ex)
    assert recs
    spans = obs.tracer.spans
    child_names = {"plan", "prep", "drain", "stop_window", "staged_reshard"}
    roots = [s for s in spans
             if s["cat"] == "adjust" and s["name"] not in child_names]
    assert len(roots) == len(recs)

    def find(tid, name, t0, t1):
        hits = [s for s in spans if s["tid"] == tid and s["name"] == name
                and s["t0"] == t0 and s["t1"] == t1]
        assert len(hits) == 1, (tid, name, t0, t1, hits)
        return hits[0]

    for name, rec in recs:
        label = f"{rec.op} {rec.from_p}->{rec.to_p}"
        if (rec.from_mp, rec.to_mp) != (1, 1):
            label += f" (mp {rec.from_mp}->{rec.to_mp})"
        root = find(name, label, rec.t_request, rec.t_switch_end)
        plan = find(name, "plan", rec.t_request, rec.t_prep_start)
        prep = find(name, "prep", rec.t_prep_start, rec.t_prep_end)
        drain = find(name, "drain", rec.t_prep_end, rec.t_switch_start)
        stop = find(name, "stop_window", rec.t_switch_start,
                    rec.t_switch_end)
        # well-nested: the children tile the root with no gaps/overlaps
        assert root["t0"] == plan["t0"]
        assert plan["t1"] == prep["t0"]
        assert prep["t1"] == drain["t0"]
        assert drain["t1"] == stop["t0"]
        assert stop["t1"] == root["t1"]
        # the acceptance criterion: trace agrees with the record exactly
        assert stop["t1"] - stop["t0"] == rec.stop_time
        commits = [m for m in obs.tracer.instants
                   if m["name"] == "commit" and m["tid"] == name
                   and m["t"] == rec.t_switch_end]
        assert commits, "every committed switch drops a commit marker"

    # the latency histograms observed exactly one sample per record
    stop_h = obs.metrics.families["edl_stop_window_ms"]
    assert stop_h.snapshot()["count"] == len(recs)


# ------------------------------------------------------ chrome trace export
def test_chrome_trace_export_loads(obs_run, tmp_path):
    ex, stats, obs = obs_run
    trace = json.loads(json.dumps(obs.tracer.chrome_trace()))
    evs = trace["traceEvents"]
    assert evs and trace["displayTimeUnit"] == "ms"
    for t in evs:
        assert t["ph"] in ("X", "i")
        assert t["ts"] >= 0.0
        if t["ph"] == "X":
            assert t["dur"] >= 0.0
    xs = [t for t in evs if t["ph"] == "X"]
    assert all(a["ts"] <= b["ts"] for a, b in zip(xs, xs[1:])), \
        "complete events must be sorted so parents precede children"
    # save() writes the same thing as loadable JSON
    out = tmp_path / "trace.json"
    obs.tracer.save(str(out))
    assert json.load(open(out))["traceEvents"]


# --------------------------------------------------- prometheus exposition
_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?[0-9.eE+-]+|NaN|\+Inf|-Inf)$')


def _parse_exposition(text):
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        samples.append((m.group(1), m.group(2) or "", float(m.group(3))))
    return types, samples


def test_prometheus_exposition_parses(obs_run):
    ex, stats, obs = obs_run
    types, samples = _parse_exposition(obs.metrics.exposition())
    assert types["edl_rounds_total"] == "counter"
    assert types["edl_pool_utilization"] == "gauge"
    assert types["edl_stop_window_ms"] == "histogram"
    base = lambda n: re.sub(r"_(bucket|sum|count)$", "", n)  # noqa: E731
    for name, _, _ in samples:
        assert base(name) in types or name in types, \
            f"sample {name} lacks a # TYPE declaration"
    # histogram buckets are cumulative and +Inf == _count
    buckets = [(labels, v) for name, labels, v in samples
               if name == "edl_stop_window_ms_bucket"]
    vals = [v for _, v in buckets]
    assert vals == sorted(vals), "bucket counts must be cumulative"
    count = next(v for name, labels, v in samples
                 if name == "edl_stop_window_ms_count")
    assert buckets[-1][0].endswith('le="+Inf"}') and \
        buckets[-1][1] == count
    rounds = next(v for name, _, v in samples
                  if name == "edl_rounds_total")
    assert rounds == stats["rounds"]


def test_reshard_host_bytes_counter_sums_the_committed_moves():
    """``edl_reshard_host_bytes_total`` adds each committed switch's
    ``host_bytes``: 0 for a move that stayed on the devices."""
    from types import SimpleNamespace
    from repro.core.scaling import ScalingRecord
    obs = Observability()
    job = SimpleNamespace(spec=SimpleNamespace(name="a"), jid=0)
    for host_bytes in (0, 4096, 0):
        rec = ScalingRecord("scale_in", 4, 2, host_bytes=host_bytes)
        obs.on_adjustment(SimpleNamespace(round=1), job, rec)
    types, samples = _parse_exposition(obs.metrics.exposition())
    assert types["edl_reshard_host_bytes_total"] == "counter"
    assert [v for name, _, v in samples
            if name == "edl_reshard_host_bytes_total"] == [4096]
    adjust = [ev for ev in obs.events() if ev.kind == "adjust"]
    assert [ev.data["host_bytes"] for ev in adjust] == [0, 4096, 0]
    obs.close()


def test_prom_http_endpoint_serves_exposition():
    obs = Observability(prom_port=0)     # ephemeral loopback port
    try:
        obs.metrics.counter("edl_rounds_total", "r").inc(3)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{obs.prom_port}/metrics", timeout=5
        ).read().decode()
        assert "# TYPE edl_rounds_total counter" in body
        assert "edl_rounds_total 3" in body
    finally:
        obs.close()
        obs.close()     # idempotent


# ----------------------------------------------------- JSONL stream + report
def test_telemetry_jsonl_validates_and_renders(tmp_path):
    telemetry = tmp_path / "telemetry.jsonl"
    trace = tmp_path / "trace.json"
    obs = Observability(telemetry_out=str(telemetry),
                        trace_out=str(trace), metrics_every=2)
    ex, stats, obs = run_obs_cluster(obs=obs)
    obs.close()
    records = report.load(str(telemetry))
    assert report.validate(records) == []
    n_events = sum(1 for r in records if r.get("type") == "event")
    assert n_events == obs.bus.emitted     # emit_raw snapshots not counted
    assert any(r.get("type") == "metrics" for r in records), \
        "periodic snapshots must land in the stream"
    s = report.summarize(records)
    assert s["adjustments"] > 0
    assert s["adjustment_latency"]["stop_ms"]["n"] == s["adjustments"]
    text = report.render(records)
    assert "job a:" in text and "job b:" in text
    assert "stop_ms" in text
    assert json.load(open(trace))["traceEvents"]


def test_validate_flags_corrupt_and_unversioned_records(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "event", "kind": "sched"}\n'
                   "not json at all\n"
                   '{"type": "mystery"}\n')
    problems = report.validate(report.load(str(bad)))
    assert any("unparseable" in p for p in problems)
    assert any("mystery" in p for p in problems)
    assert any("schema" in p or "missing" in p for p in problems)


# ------------------------------------------------- satellite: mixed-mp loans
def test_max_loaned_counts_devices_through_event_time_mp():
    """``stats()["max_loaned"]`` converts loaned GROUPS to devices via the
    event-time mp — a strict ``e["mp"]`` lookup, not a silent mp=1
    default that would under-count an mp>1 tenant's loan. Every _event
    call site stamps mp."""
    specs = [JobSpec("a", 2, 40, profile="vgg19"),
             JobSpec("wide", 1, 40, profile="resnet50", model_parallel=2)]
    ex = ClusterExecutor(specs, MaxThroughput(), devices=list(range(6)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=12)
    assert all("mp" in e for e in ex.events), \
        "every event carries its event-time mp"
    for e in ex.events:
        if e["jid"] is not None:        # static-mp workload: mp == job's
            assert e["mp"] == ex.jobs[e["jid"]].mp
    wide = next(j for j in ex.jobs.values() if j.spec.name == "wide")
    assert wide.mp == 2
    base = stats["max_loaned"]
    # a 2-GROUP loan to the mp=2 tenant is 4 DEVICES on loan
    ex._event("scale_out", wide, wide.alloc, wide.requested_p + 2)
    assert ex.stats()["max_loaned"] == max(base, 4)
    ex.close()


def test_pool_level_events_carry_explicit_mp():
    """job=None events (free-pool revocation) must stamp mp explicitly —
    the loan stat iterates EVERY event."""
    from repro.cluster.policy import make_policy
    specs = [JobSpec("a", 1, 40, profile="resnet50")]
    ex = ClusterExecutor(specs, make_policy("static"),
                         devices=list(range(4)), resched_every=2,
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    ex.run(max_rounds=4)
    assert ex.free, "the 1-group tenant leaves free devices"
    ex.revoke_devices(1)
    e = ex.events[-1]
    assert e["op"] == "revoke" and e["jid"] is None
    assert e["mp"] == 1 and e["loaned"] == 0
    assert ex.stats()["max_loaned"] >= 0     # strict lookup never raises
    ex.close()


# -------------------------------------------------- satellite: close() once
def test_close_is_idempotent():
    specs = [JobSpec("a", 1, 6, profile="resnet50")]
    ex = ClusterExecutor(specs, MaxThroughput(), devices=list(range(2)),
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    ex.run(max_rounds=10)
    discarded = []
    ex.checkpointer.discard = lambda job: discarded.append(job.jid)
    job = next(iter(ex.jobs.values()))
    job.checkpoint = ("fake-ckpt", job.jid)
    ex.close()
    ex.close()                       # second close: no re-drain
    ex.__del__()                     # and the finalizer path is a no-op
    assert discarded == [job.jid]


def test_close_safe_after_failed_run():
    class _Boom(Exception):
        pass

    class BoomPolicy(MaxThroughput):
        def __call__(self, view):
            raise _Boom("policy exploded mid-round")

    specs = [JobSpec("a", 1, 40, profile="resnet50")]
    ex = ClusterExecutor(specs, BoomPolicy(), devices=list(range(2)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    with pytest.raises(_Boom):
        ex.run(max_rounds=10)
    ex.close()                       # error-path cleanup
    ex.close()                       # ... and again from __del__/atexit
    ex.__del__()


# ------------------------------------- satellite: generic ownership auditor
def test_auditor_flags_double_grant_free_theft_and_resurrection():
    events = [
        {"round": 0, "op": "scale_out", "job": "a", "jid": 0,
         "devices": [0, 1]},
        {"round": 1, "op": "scale_out", "job": "b", "jid": 1,
         "devices": [1]},                        # owned by a: violation
        {"round": 2, "op": "scale_in", "job": "b", "jid": 1,
         "devices": [3]},                        # never granted: violation
        {"round": 3, "op": "worker_dead", "job": "a", "jid": 0,
         "devices": [0]},                        # condemn, still owned
        {"round": 4, "op": "scale_in", "job": "a", "jid": 0,
         "devices": [0]},                        # comes home -> retired
        {"round": 5, "op": "scale_out", "job": "b", "jid": 1,
         "devices": [0]},                        # resurrection: violation
    ]
    res = audit_device_ownership(events)
    assert not res["ok"] and len(res["violations"]) == 3
    assert 0 in res["retired"]
    with pytest.raises(AssertionError):
        assert_ownership(events)


def test_auditor_accepts_a_clean_log():
    events = [
        {"round": 0, "op": "scale_out", "job": "a", "jid": 0,
         "devices": [0, 1]},
        {"round": 1, "op": "scale_in", "job": "a", "jid": 0,
         "devices": [1]},
        {"round": 2, "op": "finish", "job": "a", "jid": 0,
         "devices": [0]},
    ]
    res = assert_ownership(events, require_empty=True)
    assert res["ok"] and res["n_audited"] == 3


_AUDIT_WORKLOADS = {
    "funding": lambda: [JobSpec("a", 3, 60, profile="vgg19"),
                        JobSpec("b", 1, 60, profile="resnet50")],
    "churn": lambda: [JobSpec("a", 2, 30, profile="vgg19"),
                      JobSpec("b", 2, 30, profile="resnet50", arrival=3),
                      JobSpec("c", 1, 20, profile="resnet50", arrival=6)],
    "mixed_mp": lambda: [JobSpec("a", 2, 40, profile="vgg19"),
                         JobSpec("w", 1, 40, profile="resnet50",
                                 model_parallel=2)],
}


@pytest.mark.parametrize("policy_name", ["throughput", "tiresias"])
@pytest.mark.parametrize("workload", sorted(_AUDIT_WORKLOADS))
def test_event_log_is_a_valid_interval_partition(policy_name, workload):
    """Property-style replacement for the hand-rolled per-test audits:
    whatever the policy does, the event log must describe a valid
    interval partition of the device pool — no device in two jobs at
    once, condemned devices never reappear."""
    from repro.cluster.policy import make_policy
    specs = _AUDIT_WORKLOADS[workload]()
    n = 6 if workload != "funding" else 4
    ex = ClusterExecutor(specs, make_policy(policy_name),
                         devices=list(range(n)), resched_every=2,
                         trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer())
    stats = ex.run(max_rounds=40)
    res = assert_ownership(stats["events"])
    assert res["n_audited"] > 0
    if stats["finished"] == len(specs) and stats["capacity_lost"] == 0:
        assert not res["owned_at_end"], \
            "every device must come home when all tenants finish"
    ex.close()


@pytest.mark.parametrize("seed", range(3))
def test_event_log_partition_holds_under_revocation_chaos(seed):
    """Seeded device revocations condemn capacity mid-run; the ownership
    discipline (condemned devices retire, never re-fund grants) must
    survive every schedule."""
    from repro.chaos import FaultPlan
    plan = FaultPlan.random(seed, rounds=30, n_jobs=2, kills=0,
                            revokes=2, max_devices=2)
    specs = [JobSpec("a", 2, 40, profile="vgg19"),
             JobSpec("b", 1, 40, profile="resnet50")]
    ex = ClusterExecutor(specs, MaxThroughput(), devices=list(range(4)),
                         resched_every=2, trainer_factory=FakeTrainer,
                         checkpointer=FakeCheckpointer(), faults=plan)
    stats = ex.run(max_rounds=40)
    assert_ownership(stats["events"])
    ex.close()
