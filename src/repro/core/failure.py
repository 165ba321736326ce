"""Failure recovery (EDL §4.2): forced exit is a special case of scale-in.

* consistent recovery — resume from the latest periodic checkpoint (model
  consistency guaranteed);
* approximate recovery — drop the failed worker, rebuild the topology with
  the survivors and redo the current mini-batch (bounded error, the model
  may have partially-aggregated gradients; acceptable for SGD).

Selected via USE_APPX_RECOVERY, mirroring the paper's env-var switch.
"""
from __future__ import annotations

import os
import time

import jax

from repro.core.scaling import ScalingRecord


def use_approximate() -> bool:
    return os.environ.get("USE_APPX_RECOVERY", "0") not in ("0", "", "false")


def fail_worker(trainer, worker_id: str) -> None:
    """Simulate a worker crash: it stops syncing; the leader detects it via
    missing gradient-sync requests (Membership.dead_workers). The failure
    is persistent — the step loop skips the crashed worker's sync from now
    on (without that, the next step() would re-sync it back to life and
    mask the crash from any detection later than one step) — and its
    liveness record is aged out so detection can fire immediately."""
    getattr(trainer, "failed_workers", set()).add(worker_id)
    trainer.membership.workers[worker_id].last_sync_step = -10**9


def recover(trainer, *, checkpoint_dir: str | None = None) -> ScalingRecord:
    """Detect dead workers and recover with the chosen protocol."""
    dead = trainer.membership.dead_workers(trainer.step_idx)
    if not dead:
        return None
    if use_approximate():
        return _approximate(trainer, dead)
    return _consistent(trainer, dead, checkpoint_dir)


def _approximate(trainer, dead) -> ScalingRecord:
    rec = ScalingRecord("approx_recovery", trainer.p,
                        trainer.p - len(dead), t_request=time.monotonic())
    rec.t_prep_start = rec.t_request
    for wid in dead:
        trainer._remove_worker(wid, dead=True)
    leader_died = trainer.leader_id in dead
    if leader_died:
        trainer.election.resign()
        from repro.core.election import LeaderElection
        trainer.election = LeaderElection(trainer.store, trainer.job_handle,
                                          trainer.worker_ids[0])
        trainer.leader_id = trainer.election.elect().leader_id
    handle = trainer._build_exec(len(trainer.worker_ids))
    rec.t_prep_end = time.monotonic()
    rec.t_switch_start = rec.t_prep_end
    trainer.state = jax.device_put(trainer.state, handle.state_shardings)
    jax.block_until_ready(jax.tree.leaves(trainer.state)[0])
    trainer.exec = handle
    trainer.p = handle.p
    rec.t_switch_end = time.monotonic()
    trainer.controller.history.append(rec)
    return rec


def _consistent(trainer, dead, checkpoint_dir) -> ScalingRecord:
    """Reload the latest periodic checkpoint and restart with survivors."""
    assert checkpoint_dir, "consistent recovery needs a periodic checkpoint"
    from repro.checkpoint import load_checkpoint
    from repro.training.step import init_train_state
    rec = ScalingRecord("consistent_recovery", trainer.p,
                        trainer.p - len(dead), t_request=time.monotonic())
    rec.t_prep_start = rec.t_request
    for wid in dead:
        trainer._remove_worker(wid, dead=True)
    target_p = len(trainer.worker_ids)
    trainer.state = None
    trainer.exec = None
    jax.clear_caches()
    handle = trainer._build_exec(target_p)
    rec.t_prep_end = time.monotonic()
    rec.t_switch_start = rec.t_prep_end
    with jax.set_mesh(handle.mesh):
        template = init_train_state(trainer.cfg, trainer.optimizer,
                                    jax.random.PRNGKey(0))
    restored, meta = load_checkpoint(checkpoint_dir,
                                     like=jax.device_get(template))
    trainer.state = jax.device_put(restored, handle.state_shardings)
    jax.block_until_ready(jax.tree.leaves(trainer.state)[0])
    trainer.pipeline.load_state_dict(meta["pipeline"])
    for it in trainer.iters.values():
        it.assignment = None
        it._buf = None
    trainer.step_idx = meta["step"]
    trainer.exec = handle
    trainer.p = target_p
    rec.t_switch_end = time.monotonic()
    rec.t_switch_start = rec.t_request   # everything was stopped
    trainer.controller.history.append(rec)
    return rec
