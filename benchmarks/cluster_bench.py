"""Cluster-scheduling benchmark: the SAME three-tenant live workload under
static / elastic-tiresias / throughput policies on a shared 4-device pool
(Fig-11 analogue at smoke scale, but on real ElasticTrainers).

Reports mean JCT (scheduling rounds) and wall time per policy; derived
field records the JCT reduction of the best elastic policy vs static.

``--throughput-model`` picks what the policies schedule from — the static
analytic t(p) curves or per-job measured curves fed by live step times
(``--profile-sweeps`` additionally prefills them via EDL-profile scale-in
sweeps on idle devices). ``--policies`` shrinks the sweep for smoke runs
(``make bench-smoke`` runs one tiny policy under BOTH models).
``--model-parallel M`` makes every tenant without an explicit ``:mp=``
field model-parallel: allocations then move M-device groups, measuring
what 2-D (data x model) packing costs relative to the mp=1 baseline on
the same pool; per-job degrees mix via the job grammar's ``:mp=`` field.

``--reshape`` runs the live-reparallelization overhead scenario instead:
ONE real trainer is driven through the same ``(dp=4, mp=1) -> (dp=2,
mp=2)`` transition twice — once with the in-memory RESHAPE verb (state
resharded at a mini-batch boundary, context prep hidden in the
background) and once the checkpoint-stop-resume way (save to disk, tear
everything down, rebuild at the new shape, restore). Reported stop times
are the windows training is actually paused; the in-memory path must
come in strictly below the checkpoint path on the same transition.

``--reshape-determinism`` runs the bitwise-elasticity check on the same
transition: with a fixed virtual-worker count the reshaped run's loss
trajectory must equal the static run's EXACTLY (max divergence 0.0);
any divergence is a regression and the bench exits nonzero.

  PYTHONPATH=src python benchmarks/cluster_bench.py
  PYTHONPATH=src python benchmarks/cluster_bench.py \
      --throughput-model measured --policies throughput
  PYTHONPATH=src python benchmarks/cluster_bench.py --devices 8 \
      --policies throughput --model-parallel 2
  PYTHONPATH=src python benchmarks/cluster_bench.py --reshape
  PYTHONPATH=src python benchmarks/cluster_bench.py --reshape-determinism
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
from common import emit, save  # noqa: E402


def run_reshape_bench(args, devices):
    """In-memory RESHAPE vs checkpoint-stop-resume on one transition."""
    from repro.core.stop_resume import stop_resume_rescale
    from common import make_trainer  # noqa: E402 (benchmarks path)

    from_shape, to_shape = (4, 1), (2, 2)

    def fresh():
        t = make_trainer(from_shape[0], batch=12, seq=64,
                         devices=devices, seed=0,
                         time_allowance_s=0.1)
        t.run(4)                    # settle the step-time EMA
        return t

    # in-memory RESHAPE: prep hidden in the background, training keeps
    # stepping, the state reshards at the scheduled batch boundary
    tr = fresh()
    tr.reshape(*to_shape, release=False)
    rec_mem = tr.wait_for_scaling()
    tr.run(2)                       # prove the job is alive at (2, 2)

    # checkpoint fallback: same transition, everything stopped throughout
    tr2 = fresh()
    rec_ckpt = stop_resume_rescale(tr2, to_shape[0], target_mp=to_shape[1])
    tr2.run(2)

    results = {
        "transition": {"from": list(from_shape), "to": list(to_shape)},
        "in_memory": rec_mem.summary(),
        "checkpoint": rec_ckpt.summary(),
        "stop_ratio": (rec_ckpt.stop_time / rec_mem.stop_time
                       if rec_mem.stop_time > 0 else None),
        "reshape_beats_checkpoint":
            rec_mem.stop_time < rec_ckpt.stop_time,
    }
    emit("reshape_in_memory_stop", rec_mem.stop_time * 1e6,
         f"steps_during_prep={rec_mem.steps_during_prep}")
    emit("reshape_checkpoint_stop", rec_ckpt.stop_time * 1e6,
         f"ratio={results['stop_ratio']:.1f}x")
    save("reshape", results)
    print(f"in-memory reshape stop: {rec_mem.stop_time * 1e3:.1f} ms "
          f"(e2e {rec_mem.e2e_time:.2f} s, "
          f"{rec_mem.steps_during_prep} steps trained during prep); "
          f"checkpoint-stop-resume: {rec_ckpt.stop_time:.2f} s — "
          f"{'OK' if results['reshape_beats_checkpoint'] else 'REGRESSION'}")


def run_reshape_determinism_bench(args, devices):
    """Determinism mode of the reshape bench: with virtual workers on, a
    live RESHAPE (4,1) -> (2,2) mid-run must produce ZERO loss-trajectory
    divergence against the static run — bitwise, not tolerance-equal.
    Writes experiments/bench_reshape_determinism.json."""
    from common import make_trainer  # noqa: E402 (benchmarks path)

    nv, steps = 8, 10
    from_shape, to_shape = (4, 1), (2, 2)

    def fresh():
        return make_trainer(from_shape[0], batch=8, seq=64,
                            devices=devices, seed=0,
                            virtual_workers=nv, time_allowance_s=0.1)

    static = fresh()
    static.run(steps)
    ref = [m["loss"] for m in static.metrics_log]

    tr = fresh()
    tr.run(4)
    tr.reshape(*to_shape, release=False)
    rec = tr.wait_for_scaling()
    while tr.step_idx < steps:
        tr.step()
    got = [m["loss"] for m in tr.metrics_log][:steps]

    divergence = max(abs(a - b) for a, b in zip(ref, got))
    results = {
        "virtual_workers": nv,
        "transition": {"from": list(from_shape), "to": list(to_shape)},
        "static_trajectory": ref,
        "reshaped_trajectory": got,
        "max_divergence": divergence,
        "bitwise_identical": ref == got,
        "reshape": rec.summary() if rec else None,
    }
    emit("reshape_determinism_divergence", divergence * 1e6,
         f"bitwise={results['bitwise_identical']}")
    save("reshape_determinism", results)
    print(f"reshape {from_shape} -> {to_shape} with {nv} virtual workers: "
          f"max trajectory divergence {divergence} — "
          f"{'OK (bitwise)' if results['bitwise_identical'] else 'REGRESSION'}")
    return 0 if results["bitwise_identical"] else 1


def run_faults_bench(args, devices):
    """Churn mode (``--faults``): replay a FaultPlan — a JSON revocation/
    kill trace or an inline ``random:`` spec — against the live workload,
    and run the SAME workload undisturbed as the baseline. Reports
    recovery latency per fault and goodput-under-churn (total steps
    completed per scheduling round, faulted vs baseline) and writes the
    churn artifact to experiments/bench_chaos.json."""
    from repro.chaos import FaultPlan
    from repro.cluster import ClusterExecutor, make_policy
    from repro.launch.cluster import parse_jobs

    policy = args.policies.split(",")[0]
    plan = FaultPlan.parse(args.faults)

    def run(faults):
        specs = parse_jobs(args.jobs, batch=12, seq=64, n_samples=1 << 10,
                           d_partitions=16, default_mp=args.model_parallel)
        ex = ClusterExecutor(specs, make_policy(policy), devices=devices,
                             faults=faults)
        t0 = time.monotonic()
        stats = ex.run(max_rounds=args.max_rounds)
        stats["wall_s"] = round(time.monotonic() - t0, 2)
        ex.close()
        return ex, stats

    _, base = run(None)
    ex, churn = run(plan)

    def goodput(stats):
        steps = sum(j["steps_done"] for j in stats["jobs"])
        return steps / max(1, stats["rounds"])

    recoveries = [e for e in churn["events"] if e["op"] == "recovered"]
    results = {
        "policy": policy,
        "fault_plan": {"seed": plan.seed,
                       "events": [e.to_dict() for e in plan.events]},
        "baseline": {"goodput_steps_per_round": round(goodput(base), 3),
                     "finished": base["finished"],
                     "mean_jct": base["mean_jct"],
                     "wall_s": base["wall_s"]},
        "churn": {"goodput_steps_per_round": round(goodput(churn), 3),
                  "finished": churn["finished"],
                  "mean_jct": churn["mean_jct"],
                  "wall_s": churn["wall_s"],
                  "workers_killed": churn["workers_killed"],
                  "devices_revoked": churn["devices_revoked"],
                  "capacity_lost": churn["capacity_lost"],
                  "pool": [churn["n_gpus_initial"], churn["n_gpus"]],
                  "recoveries": [
                      {"job": e["job"], "mode": e["mode"],
                       "latency_s": e["latency_s"]} for e in recoveries],
                  "mean_recovery_latency_s":
                      churn["mean_recovery_latency_s"],
                  "injector_log": ex.injector.log},
        "conserved": churn["conserved"],
        "goodput_retained": (round(goodput(churn) / goodput(base), 3)
                             if goodput(base) else None),
    }
    lat = churn["mean_recovery_latency_s"]
    emit("cluster_chaos_recovery",
         (lat or 0.0) * 1e6,
         f"goodput_retained={results['goodput_retained']}")
    save("chaos", results)
    print(f"churn replay ({len(plan.events)} faults, seed {plan.seed}): "
          f"pool {churn['n_gpus_initial']} -> {churn['n_gpus']}, "
          f"{churn['recoveries']} recoveries"
          + (f" (mean latency {lat}s)" if lat is not None else "")
          + f"; goodput retained {results['goodput_retained']} "
          f"vs fault-free baseline — "
          f"{'OK' if churn['conserved'] else 'LEAK'}")
    return 0 if churn["conserved"] else 1


def run_serving_bench(args, devices):
    """Serving-tier mode (``--serving-trace``): replay a diurnal request
    trace against one live ``ServingJob`` (real ``serve_batch`` waves,
    measured latency) sharing the pool with the ``--jobs`` training
    tenants under a reclaim-priority policy. The lull loans idle replica
    groups to the trainers; every spike reclaims them. Reports p99 SLO
    attainment vs training goodput (steps per scheduling round) and
    writes experiments/bench_serving.json."""
    from repro.cluster import ClusterExecutor, make_policy
    from repro.launch.cluster import parse_jobs
    from repro.sched.serving import CrossTierPolicy
    from repro.sched.throughput import AnalyticModel, MeasuredModel

    policy_name = args.policies.split(",")[0]
    rounds = args.serving_rounds
    knobs = (f":period={args.serving_period}:base={args.serving_base}"
             f":peak={args.serving_peak}"
             if args.serving_trace == "diurnal" else "")
    text = (f"api=resnet50:1:{rounds}:serve={args.serving_trace}{knobs}"
            f":cap={args.serving_cap}:slo={args.serving_slo}@0,"
            + args.jobs)
    specs = parse_jobs(text, batch=12, seq=64, n_samples=1 << 10,
                       d_partitions=16, default_mp=args.model_parallel)
    model = (MeasuredModel() if args.throughput_model == "measured"
             else AnalyticModel())
    policy = CrossTierPolicy(make_policy(policy_name))
    t0 = time.monotonic()
    ex = ClusterExecutor(specs, policy, devices=devices,
                         throughput_model=model, resched_every=2)
    stats = ex.run(max_rounds=args.max_rounds)
    wall = round(time.monotonic() - t0, 2)
    ex.close()

    serving = [j for j in stats["jobs"] if j.get("tier") == "serving"]
    training = [j for j in stats["jobs"] if j.get("tier") != "serving"]
    train_steps = sum(j["steps_done"] for j in training)
    goodput = round(train_steps / max(1, stats["rounds"]), 3)
    ops = lambda kind, jids: sum(     # noqa: E731
        1 for e in stats["events"] if e["op"] == kind and e["jid"] in jids)
    sjids = {j["jid"] for j in serving}
    tjids = {j["jid"] for j in training}
    results = {
        "policy": f"cross-tier({policy_name})",
        "throughput_model": args.throughput_model,
        "trace": {"kind": args.serving_trace, "rounds": rounds,
                  "period": args.serving_period, "base": args.serving_base,
                  "peak": args.serving_peak, "cap": args.serving_cap},
        "slo_ms": args.serving_slo,
        "serving": {"rounds_served": stats.get("rounds_served", 0),
                    "slo_breaches": stats.get("slo_breaches", 0),
                    "slo_attainment": stats.get("slo_attainment"),
                    "scale_outs": ops("scale_out", sjids),
                    "scale_ins": ops("scale_in", sjids),
                    "jobs": serving},
        "training": {"steps_done": train_steps,
                     "goodput_steps_per_round": goodput,
                     "loan_reclaims": ops("scale_in", tjids),
                     "preemptions": stats["preemptions"],
                     "jobs": training},
        "max_loaned": stats["max_loaned"],
        "rounds": stats["rounds"],
        "wall_s": wall,
        "conserved": stats["conserved"],
    }
    att = results["serving"]["slo_attainment"]
    emit("serving_slo_attainment", (att or 0.0) * 1e6,
         f"goodput={goodput}_steps_per_round")
    save("serving", results)
    print(f"serving trace {args.serving_trace} x{rounds} rounds under "
          f"cross-tier({policy_name}): p99 SLO attainment "
          + (f"{att:.1%}" if att is not None else "-")
          + f" ({results['serving']['slo_breaches']} breach(es)), "
          f"training goodput {goodput} steps/round, max loan "
          f"{stats['max_loaned']} device(s), "
          f"{results['training']['loan_reclaims']} loan reclaim(s) — "
          f"{'OK' if stats['conserved'] else 'LEAK'}")
    return 0 if stats["conserved"] and att is not None else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--jobs", default="a=vgg19:3:20@0,b=resnet50:1:25@0,"
                                      "c=googlenet:1:12@6")
    ap.add_argument("--policies",
                    default="static,elastic-tiresias,throughput",
                    help="comma-separated policy subset to run")
    ap.add_argument("--throughput-model", default="analytic",
                    choices=["analytic", "measured"])
    ap.add_argument("--model-parallel", type=int, default=1, metavar="M",
                    help="default model-parallel degree for jobs without "
                         "an explicit :mp= field — allocations move "
                         "M-device groups")
    ap.add_argument("--profile-sweeps", action="store_true")
    ap.add_argument("--reshape", action="store_true",
                    help="run the live-reparallelization overhead scenario "
                         "(in-memory RESHAPE vs checkpoint-stop-resume) "
                         "instead of the policy sweep")
    ap.add_argument("--reshape-determinism", action="store_true",
                    help="determinism mode: the same (4,1) -> (2,2) live "
                         "reshape with virtual workers on must produce "
                         "ZERO loss-trajectory divergence vs the static "
                         "run (exit 1 on any divergence)")
    ap.add_argument("--faults", default=None, metavar="PATH_OR_SPEC",
                    help="churn mode: replay a FaultPlan (JSON trace file "
                         "or 'random:seed=0,kills=1,...' spec) against "
                         "the workload and report recovery latency + "
                         "goodput-under-churn vs the fault-free baseline "
                         "(writes experiments/bench_chaos.json)")
    ap.add_argument("--serving-trace", default=None, metavar="TRACE",
                    help="serving-tier mode: replay this request trace "
                         "('diurnal' or a '/'-separated rate list) on one "
                         "live ServingJob sharing the pool with --jobs, "
                         "reporting p99 SLO attainment vs training "
                         "goodput (writes experiments/bench_serving.json)")
    ap.add_argument("--serving-rounds", type=int, default=36)
    ap.add_argument("--serving-period", type=int, default=12)
    ap.add_argument("--serving-base", type=float, default=6.0)
    ap.add_argument("--serving-peak", type=float, default=30.0)
    ap.add_argument("--serving-cap", type=int, default=12,
                    help="requests one replica serves per wave")
    ap.add_argument("--serving-slo", type=float, default=250.0,
                    metavar="MS")
    ap.add_argument("--report", action="store_true",
                    help="attach the observability layer (repro.obs) to "
                         "each policy run and print its per-job timeline "
                         "+ adjustment-latency summary (the same renderer "
                         "as tools/obs_report.py)")
    ap.add_argument("--max-rounds", type=int, default=300)
    ap.add_argument("--compile-cache", default=None, metavar="DIR")
    args = ap.parse_args()

    from repro.launch.devices import enable_compile_cache, pick_devices
    devices = pick_devices(args.devices)
    if args.compile_cache:
        enable_compile_cache(args.compile_cache)
    if args.reshape:
        return run_reshape_bench(args, devices)
    if args.reshape_determinism:
        return run_reshape_determinism_bench(args, devices)
    if args.faults:
        return run_faults_bench(args, devices)
    if args.serving_trace:
        return run_serving_bench(args, devices)
    from repro.cluster import ClusterExecutor, make_policy
    from repro.launch.cluster import parse_jobs
    from repro.sched.throughput import AnalyticModel, MeasuredModel

    results = {}
    for name in args.policies.split(","):
        specs = parse_jobs(args.jobs, batch=12, seq=64, n_samples=1 << 10,
                           d_partitions=16, default_mp=args.model_parallel)
        model = (MeasuredModel() if args.throughput_model == "measured"
                 else AnalyticModel())
        obs = None
        if args.report:
            from repro.obs import Observability
            obs = Observability()
        t0 = time.monotonic()
        ex = ClusterExecutor(specs, make_policy(name), devices=devices,
                             throughput_model=model,
                             profile_sweeps=args.profile_sweeps, obs=obs)
        stats = ex.run(max_rounds=args.max_rounds)
        ex.close()
        wall = time.monotonic() - t0
        if obs is not None:
            from repro.obs import report as obs_report
            obs.close()
            print(f"--- obs report: policy {name} ---")
            print(obs_report.render(obs.records()))
        jct = stats["mean_jct"]     # None when nothing finished in budget
        results[name] = {"mean_jct": jct,
                         "makespan": stats["makespan"],
                         "finished": stats["finished"],
                         "max_loaned": stats["max_loaned"],
                         "preemptions": stats["preemptions"],
                         "readmissions": stats["readmissions"],
                         "profile_sweeps": stats["profile_sweeps"],
                         "events": len(stats["events"]),
                         "wall_s": round(wall, 2)}
        tag = f"cluster_{name}_{args.throughput_model}" + (
            f"_mp{args.model_parallel}" if args.model_parallel != 1 else "")
        emit(tag, wall * 1e6,
             f"mean_jct={jct:.1f}_rounds" if jct is not None
             else "mean_jct=unfinished")

    base = results.get("static", {}).get("mean_jct")
    elastic = [results[n]["mean_jct"]
               for n in ("elastic-tiresias", "throughput")
               if n in results and results[n]["mean_jct"] is not None]
    # only meaningful when the static baseline AND an elastic policy ran
    # (a --policies smoke subset must not fabricate a 0% comparison)
    red = 1 - min(elastic) / base if base and elastic else None
    if red is not None:
        emit("cluster_elastic_vs_static", 0.0, f"jct_reduction={red:.1%}")
    # keyed by mp too: an mp>1 run must not overwrite the mp=1 baseline
    # it is meant to be compared against
    save(f"cluster_{args.throughput_model}" + (
         f"_mp{args.model_parallel}" if args.model_parallel != 1 else ""),
         {"throughput_model": args.throughput_model,
          "model_parallel": args.model_parallel, "results": results,
          "jct_reduction": red})


if __name__ == "__main__":
    sys.exit(main())
