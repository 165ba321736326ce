"""Elastic training driver (end-to-end example + integration-test target).

Trains an elastic job under a scaling schedule and reports metrics + scaling
records + exactly-once data accounting as JSON.

  PYTHONPATH=src python -m repro.launch.train --arch edl-paper --steps 200 \
      --batch 8 --seq 128 --init-p 2 --devices 8 \
      --schedule out:2@30,in:2@120

Schedule grammar: ``<op>:<n>@<step>`` with op in {out, in, migrate,
stop_resume_out, stop_resume_in, stop_resume_mp, straggler, fail, kill,
kill_leader}. ``kill:n`` crashes the last n workers WITHOUT an explicit
recovery call: they stop sending gradient-syncs, the leader's liveness
view flags them dead after ``miss_threshold`` missed steps, and the
driver's detection loop triggers an automatic stop-free
``handle_failure`` scale-in (``kill_leader`` crashes the current leader
instead, forcing a re-election at the commit). ``fail`` is the legacy
blocking path (immediate ``recover`` under USE_APPX_RECOVERY).
``stop_resume_mp:M`` checkpoint-stops the job and resumes it reparallelized
at model-parallel degree M (device footprint held constant) — with
``--virtual-workers`` on, the restored run continues the bitwise-exact
trajectory on the new (dp, mp).

``--virtual-workers K`` (or ``auto``) turns on deterministic elasticity:
the loss trajectory (reported in the JSON ``losses`` field) is
bitwise-identical across every parallelism and every elastic schedule.

``--devices N`` takes the first N devices of JAX's backend; on the CPU
N host devices are emulated. The run fails when the
backend has fewer, when a background compile fails, or when the
``EDL_WALL_LIMIT_S`` deadline (default 600 s) passes before it ends.
"""
import argparse
import json
import os
import sys
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="edl-paper")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--init-p", type=int, default=2)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int,
                    default=int(os.environ.get("EDL_DEVICES", "8")))
    ap.add_argument("--schedule", default="")
    ap.add_argument("--n-samples", type=int, default=1 << 14)
    ap.add_argument("--d-partitions", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--json", action="store_true", help="machine output")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--virtual-workers", default=None, metavar="K",
                    help="fixed virtual-worker count (int or 'auto') — "
                         "deterministic elasticity: bitwise-identical "
                         "trajectories at every (dp, mp)")
    args = ap.parse_args(argv)
    if args.virtual_workers not in (None, "auto"):
        args.virtual_workers = int(args.virtual_workers)
    return args


def build_trainer(args, devices):
    """The elastic job the driver trains, on ``devices``."""
    from repro.configs import get_config
    from repro.core import ElasticTrainer
    from repro.optim import adamw
    cfg = get_config(args.arch, smoke=args.smoke)
    return ElasticTrainer(
        cfg, global_batch=args.batch, seq_len=args.seq,
        init_parallelism=args.init_p, model_parallel=args.model_parallel,
        optimizer=adamw(args.lr), n_samples=args.n_samples,
        d_partitions=args.d_partitions, seed=args.seed,
        virtual_workers=args.virtual_workers, devices=devices)


def train(trainer, args, log=print) -> dict:
    """Run ``args.steps`` steps under ``args.schedule``, then drain pending
    schedule entries and any in-flight switch. Returns the summary."""
    from repro.core import stop_resume_rescale
    from repro.core.failure import fail_worker, recover

    def _apply_op(trainer, opn, n):
        if opn == "out":
            trainer.scale_out(n)
        elif opn == "in":
            trainer.scale_in(n)
        elif opn == "migrate":
            trainer.migrate(n)
        elif opn == "stop_resume_out":
            stop_resume_rescale(trainer, trainer.p + n)
        elif opn == "stop_resume_in":
            stop_resume_rescale(trainer, trainer.p - n)
        elif opn == "stop_resume_mp":
            # checkpoint-based reparallelization onto mp=n at a constant
            # device footprint: (p, mp) -> (p*mp/n, n)
            stop_resume_rescale(
                trainer, max(1, trainer.p * trainer.model_parallel // n),
                target_mp=n)
        elif opn == "straggler":
            trainer.injected_delay[trainer.worker_ids[-1]] = 0.05
        elif opn == "fail":
            fail_worker(trainer, trainer.worker_ids[-1])
            recover(trainer)
        elif opn == "kill":
            # no recovery call here: detection (below) must find them
            for wid in list(reversed(trainer.worker_ids))[:n]:
                trainer.inject_worker_failure(wid)
        elif opn == "kill_leader":
            trainer.inject_worker_failure(trainer.leader_id)
        else:
            raise ValueError(f"unknown schedule op {opn!r}")

    schedule: dict[int, list[tuple[str, int]]] = {}
    if args.schedule:
        for item in args.schedule.split(","):
            opn, rest = item.split(":")
            n, at = rest.split("@")
            schedule.setdefault(int(at), []).append((opn, int(n)))

    consumed_ids: list = []
    t0 = time.monotonic()
    from repro.core.scaling import Busy, Phase
    limit_s = float(os.environ.get("EDL_WALL_LIMIT_S", "600"))

    def pending_ops():
        return any(k >= trainer.step_idx and v for k, v in schedule.items())

    # main loop runs to --steps, then drains: pending (retried) schedule
    # entries and any in-flight background scaling commit before exit
    try:
        while (trainer.step_idx < args.steps or pending_ops()
               or trainer.controller.phase is not Phase.IDLE):
            if time.monotonic() - t0 > limit_s:
                raise TimeoutError(
                    f"EDL_WALL_LIMIT_S={limit_s:g} s passed at step "
                    f"{trainer.step_idx} of {args.steps} (scaling phase "
                    f"{trainer.controller.phase.value})")
            for opn, n in schedule.pop(trainer.step_idx, []):
                try:
                    _apply_op(trainer, opn, n)
                except Busy:    # paper: scheduler retries after a delay
                    schedule.setdefault(trainer.step_idx + 5, []).append(
                        (opn, n))
            m = trainer.step()
            # automatic dead-worker recovery: the leader's liveness view
            # (missed gradient-syncs) drives a stop-free scale-in; training
            # continues through the background prep and the trajectory is
            # bitwise-preserved under --virtual-workers
            dead = trainer.dead_workers()
            if dead and trainer.controller.phase is Phase.IDLE:
                try:
                    trainer.handle_failure(dead)
                except (Busy, ValueError):
                    pass    # retried next step / no feasible survivor shape
            if m is None:
                if trainer.controller.phase is Phase.SCHEDULED:
                    trainer._commit_switch()
                continue
            consumed_ids.append(trainer._last_sample_ids)
            # straggler mitigation: leader removes flagged workers (§5.2)
            for wid in getattr(trainer, "_flagged_stragglers", []):
                trainer.injected_delay.pop(wid, None)
                try:
                    trainer.scale_in(1, victims=[wid])
                except Busy:
                    pass    # flagged again after the op in flight commits
            if m["step"] % 20 == 0:
                log(f"step {m['step']:5d} p={m['p']} loss={m['loss']:.4f} "
                    f"thr={trainer.throughput():.1f} samp/s")
    finally:
        # a compile still running in a daemon thread at interpreter exit
        # aborts the process
        trainer.join_prep(120)
    wall = time.monotonic() - t0

    import numpy as np
    ids = np.concatenate(consumed_ids) if consumed_ids else np.array([])
    epochs_done = trainer.pipeline.epoch
    summary = {
        "arch": trainer.cfg.name, "steps": trainer.step_idx,
        "final_p": trainer.p, "wall_s": round(wall, 2),
        "final_loss": trainer.metrics_log[-1]["loss"],
        "first_loss": trainer.metrics_log[0]["loss"],
        # the full per-step trajectory: with --virtual-workers this is the
        # bitwise-determinism contract surface (exact-equality tests
        # compare it across parallelisms and elastic schedules)
        "losses": [m["loss"] for m in trainer.metrics_log],
        "virtual_workers": trainer.n_virtual,
        "throughput": trainer.throughput(),
        "scaling_events": [r.summary() for r in trainer.controller.history],
        "samples_seen": int(trainer.samples_seen),
        "unique_sample_frac": (float(len(set(ids.tolist())) / len(ids))
                               if len(ids) else 0.0),
        "epochs_done": epochs_done,
        "leader": trainer.leader_id,
    }
    # exactly-once check over any FULL epochs completed
    if epochs_done >= 1 and len(ids) >= trainer.dataset.n_samples:
        first_epoch = ids[:trainer.dataset.n_samples]
        summary["epoch0_exactly_once"] = bool(
            sorted(first_epoch.tolist()) ==
            list(range(trainer.dataset.n_samples)))
    return summary


def main(argv=None):
    args = parse_args(argv)
    from repro.launch.devices import describe, enable_compile_cache, \
        pick_devices
    devices = pick_devices(args.devices)
    enable_compile_cache()
    trainer = build_trainer(args, devices)
    summary = train(trainer, args,
                    log=(lambda *a, **k: None) if args.json else print)
    summary["device"] = describe(devices)
    print(json.dumps(summary) if args.json else
          json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
