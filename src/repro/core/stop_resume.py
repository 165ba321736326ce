"""Checkpoint-stop / resume-from-disk entry points, and the stop-resume
rescale baseline (the approach EDL replaces, §2.2).

Two consumers share the primitives in this module:

  * ``stop_resume_rescale`` — the paper's Table-2 baseline: checkpoint, tear
    EVERYTHING down (state, executables, compilation cache), rebuild at the
    new parallelism from scratch, restore, resume. All workers are stopped
    for the whole duration.
  * the cluster executor's full preemption path (repro.cluster.executor):
    ``checkpoint_save`` + ``teardown_trainer`` stop a RUNNING job to disk
    mid-run and return all of its devices to the shared pool;
    ``resume_from_checkpoint`` re-admits it later onto a freshly built
    trainer — possibly on a different device set and at a different
    parallelism — restoring optimizer/model state, the dynamic-data-pipeline
    permutation (in-flight partition remainders included), and the step /
    sample counters so training continues exactly where it stopped.
"""
from __future__ import annotations

import tempfile
import time

import jax

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core.scaling import Busy, Phase, ScalingRecord


def checkpoint_save(trainer, checkpoint_dir: str) -> None:
    """Write ``trainer``'s full restorable state to ``checkpoint_dir``:
    train state (params + optimizer moments), step / sample counters, and
    the dynamic data pipeline's ``state_dict`` — whose serialization folds
    every in-flight partition assignment back into the returned-work queue
    (replayed from the last reported offset), so a restore resumes
    exactly-once data consumption no matter how many workers were mid-read.

    The metadata also records the writer's parallelization: ``(p, mp)``
    plus the full ``reshape.StateSpec`` layout, so a restore onto a
    DIFFERENT shape can plan the reshard (checkpoint-based
    reparallelization — the fallback path when the in-memory RESHAPE verb
    is unavailable because the process is gone).

    Read-only with respect to the trainer: safe to run from a background
    thread while the job is parked (not stepping)."""
    from repro.reshape import StateSpec
    save_checkpoint(
        checkpoint_dir, trainer.state, step=trainer.step_idx,
        pipeline_state=trainer.pipeline.state_dict(),
        extra={"samples_seen": trainer.samples_seen, "p": trainer.p,
               "mp": trainer.model_parallel,
               "job_handle": trainer.job_handle,
               "virtual_workers": getattr(trainer, "n_virtual", 0),
               "seed": getattr(trainer, "seed", 0),
               "state_spec": StateSpec.for_trainer(trainer).to_json()})


def teardown_trainer(trainer) -> list:
    """Release everything a stopped job holds: drop the train state, the
    live executable, and the per-topology compiled-executable cache, and
    return the job's whole device pool to the caller. Does NOT touch the
    process-global jax caches — other tenants in the same process keep
    their compiled executables."""
    devices, trainer.devices = list(trainer.devices), []
    trainer.state = None
    trainer.exec = None
    trainer._exec_cache.clear()
    trainer._move_cache.clear()
    return devices


def checkpoint_stop(trainer, checkpoint_dir: str) -> list:
    """Stop a RUNNING job to disk mid-run: checkpoint, then tear down.
    Returns the devices the job owned. Raises ``Busy`` (the paper's RETRY)
    while a scaling operation is in flight — a checkpoint taken mid-switch
    would capture a topology that no longer exists at restore time."""
    if trainer.controller.phase is not Phase.IDLE:
        raise Busy("scaling in flight; checkpoint-stop after it commits")
    checkpoint_save(trainer, checkpoint_dir)
    return teardown_trainer(trainer)


def resume_from_checkpoint(trainer, checkpoint_dir: str) -> dict:
    """Restore a checkpoint into a freshly built trainer (any device set,
    any feasible parallelism, any model-parallel degree). The trainer's
    execution context (``trainer.exec``) must already target the NEW
    topology. When the checkpoint records the writer's layout
    (``extra.state_spec``), the restore is planned as a reshard from the
    saved ``(dp, mp)`` onto the trainer's — validating tensor-collection
    compatibility up front and reporting the move accounting under
    ``meta["reshard"]`` — before the arrays land via ``apply_plan``.
    Restores the data pipeline's permutation + progress and the step /
    sample counters, and invalidates the worker iterators' local buffers
    so the first post-resume draw fetches fresh assignments from the
    restored pipeline."""
    from repro.reshape import StateSpec, apply_plan, plan_reshard
    from repro.training.step import init_train_state
    with jax.set_mesh(trainer.exec.mesh):
        template = init_train_state(trainer.cfg, trainer.optimizer,
                                    jax.random.PRNGKey(0))
    restored, meta = load_checkpoint(checkpoint_dir,
                                     like=jax.device_get(template))
    saved_spec = (meta.get("extra") or {}).get("state_spec")
    if saved_spec is not None:
        src = StateSpec.from_json(saved_spec)
        dst = StateSpec.from_shardings(trainer.p, trainer.model_parallel,
                                       trainer.exec.state_shardings,
                                       restored)
        rplan = plan_reshard(src, dst)      # raises on collection mismatch
        meta["reshard"] = rplan.summary()
        trainer.state, _ = apply_plan(rplan, restored,
                                      trainer.exec.state_shardings)
    else:   # pre-reshape checkpoint: layout-blind restore
        trainer.state = jax.device_put(restored,
                                       trainer.exec.state_shardings)
    jax.block_until_ready(jax.tree.leaves(trainer.state)[0])
    # deterministic elasticity: the virtual-worker count is part of the
    # trajectory's identity — a restore must keep it (the pipeline's own
    # load_state_dict then validates cursors against block layout)
    saved_nv = int((meta.get("extra") or {}).get("virtual_workers", 0) or 0)
    trainer_nv = int(getattr(trainer, "n_virtual", 0) or 0)
    if saved_nv != trainer_nv:
        raise ValueError(
            f"checkpoint was written with virtual_workers={saved_nv} but "
            f"the target trainer runs virtual_workers={trainer_nv}; "
            f"bitwise trajectory preservation requires the same fixed "
            f"virtual-worker count at every shape")
    trainer.pipeline.load_state_dict(meta["pipeline"])
    for it in trainer.iters.values():
        it.assignment = None
        it._buf = None
    trainer.step_idx = int(meta.get("step", 0))
    extra = meta.get("extra") or {}
    trainer.samples_seen = int(extra.get("samples_seen",
                                         trainer.samples_seen))
    return meta


def stop_resume_rescale(trainer, target_p: int,
                        *, target_mp: int | None = None,
                        checkpoint_dir: str | None = None
                        ) -> ScalingRecord:
    """Adjust ``trainer`` to ``target_p`` (and optionally a new
    model-parallel degree ``target_mp`` — the checkpoint-based
    reparallelization fallback the in-memory RESHAPE verb is benchmarked
    against) the stop-resume way. Training is fully stopped from
    t_request to t_switch_end (stop_time == e2e_time)."""
    if trainer.controller.plan is not None:
        raise Busy("scaling already in flight; retry")   # paper: RETRY
    target_mp = (target_mp if target_mp is not None
                 else trainer.model_parallel)
    if target_p * target_mp > len(trainer.devices):
        raise ValueError(f"shape ({target_p}, {target_mp}) needs "
                         f"{target_p * target_mp} devices, trainer owns "
                         f"{len(trainer.devices)}")
    nv = getattr(trainer, "n_virtual", 0)
    if nv and nv % target_p:
        raise ValueError(f"p={target_p} must divide virtual_workers={nv}")
    rec = ScalingRecord("stop_resume", trainer.p, target_p,
                        t_request=time.monotonic(),
                        from_mp=trainer.model_parallel, to_mp=target_mp)
    rec.t_prep_start = rec.t_request
    ckpt = checkpoint_dir or tempfile.mkdtemp(prefix="edl_sr_")

    # 1. checkpoint and stop
    checkpoint_save(trainer, ckpt)
    # 2. tear down: drop state, executables, compilation cache — a restarted
    #    process pays context preparation from zero. Unlike preemption
    #    teardown, the baseline also clears the global jax caches to model a
    #    full process restart.
    trainer.state = None
    trainer.exec = None
    trainer._exec_cache.clear()
    trainer._move_cache.clear()
    jax.clear_caches()

    # 3. rebuild execution context at the new shape (foreground!)
    while len(trainer.worker_ids) > target_p:
        trainer._remove_worker(trainer.worker_ids[-1])
    while len(trainer.worker_ids) < target_p:
        trainer._add_worker()
    handle = trainer._build_exec(target_p, target_mp)
    rec.t_prep_end = time.monotonic()

    # 4. restore model + pipeline state onto the rebuilt topology
    rec.t_switch_start = rec.t_prep_end
    trainer.exec = handle
    trainer.p = target_p
    trainer.model_parallel = target_mp
    meta = resume_from_checkpoint(trainer, ckpt)
    rec.reshard_bytes_moved = (meta.get("reshard") or {}).get(
        "bytes_moved", 0)
    rec.t_switch_end = time.monotonic()
    # stop-resume stops everything: stop time is the whole window
    rec.t_switch_start = rec.t_request
    trainer.controller.history.append(rec)
    return rec
