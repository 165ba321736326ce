"""ElasticTrainer — EDL's elasticity on a JAX device mesh.

The TPU-native mapping (DESIGN.md §2/§4): a *worker* is one data-parallel
slice of a ``(data, model)`` mesh; elasticity resizes the ``data`` axis.
The global batch is constant at every parallelism (per-slice batch =
global / p), so a training step computes the same math regardless of p.

Stop-free scale-out: the expensive execution-context preparation on TPU is
the XLA compile for the new mesh — it runs in a background thread via AOT
``jit(...).lower().compile()`` while the current executable keeps stepping.
When ready, the leader schedules the switch at mini-batch ``t_cur + k``
(k = ceil(T_allowance / T_batch), T_allowance = 500 ms — paper default); at
that boundary the train state is resharded onto the new mesh (the "model
broadcast") and the executable swapped. Scale-in (graceful exit) returns the
exiting slices' partition remainders to the dynamic data pipeline.
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable

import jax
import numpy as np

from repro.core.coordination import CoordinationStore
from repro.core.election import LeaderElection
from repro.core.membership import Membership, StragglerDetector
from repro.core.scaling import Busy, Phase, ScalingController, ScalingRecord
from repro.data.pipeline import DynamicDataPipeline, VirtualWorkerPipeline
from repro.data.synthetic import SyntheticTokenDataset
from repro.data.worker import WorkerDataIterator
from repro.launch.mesh import make_mesh
from repro.obs.trace import span
from repro.optim import Optimizer, adamw
from repro.reshape import StateMove, StateSpec, apply_plan, plan_reshard
from repro.training.step import batch_sharding, init_train_state, \
    make_train_step, state_sharding

TIME_ALLOWANCE_S = 0.5      # paper's T_a
EXEC_CACHE_MAX = 8          # compiled topologies retained per job (LRU)


class PrepFailed(RuntimeError):
    """The background context prep (the compile) of a scaling operation
    failed; the operation was dropped."""


@dataclasses.dataclass
class ExecHandle:
    """Everything tied to one (data, model) shape: the 'communication
    topology'. ``p`` is the data-parallel replica count, ``mp`` the
    model-parallel degree — ``p * mp`` devices back the mesh."""
    p: int
    mp: int
    mesh: object
    step_fn: Callable
    state_shardings: object
    batch_shardings: object

    @property
    def key(self) -> tuple:
        """The exec-cache key this handle was built under."""
        return (self.p, self.mp, tuple(d.id for d in self.mesh.devices.flat))


class ElasticTrainer:
    """One elastic training job: a synchronous data-parallel trainer whose
    parallelism can be changed stop-free while it runs.

    Public control surface (all scaling entry points raise ``Busy`` — the
    paper's RETRY — while another operation is in flight, and commit at the
    next mini-batch boundary after their background context prep lands):

      step()                 — one synchronous mini-batch on the current
                               topology; also the commit point for any
                               scheduled switch (``notify_batch_end``).
      scale_out/scale_in     — resize within the devices the job already
                               owns (victims exit gracefully, returning
                               their data-partition remainders).
      migrate()              — fused scale-in + scale-out at constant p,
                               one topology switch (straggler mitigation).
      reshape(p, mp)         — live reparallelization: trade data-parallel
                               for model-parallel degree in one stop-free
                               switch; the train state is resharded along
                               a repro.reshape plan at the boundary.
      grant_devices(devs)    — a scheduler HANDS the job extra devices; the
                               job owns them immediately and scales out onto
                               them stop-free. A grant beyond the job's
                               requested parallelism is a transient-resource
                               loan the scheduler may reclaim at any time.
      release_devices(n)     — graceful scale-in that RETURNS device
                               ownership: the freed devices leave
                               ``self.devices`` when the switch commits and
                               are handed to ``on_devices_released`` (the
                               reclaim side of a loan, or any scheduler
                               shrink).

    Full preemption (checkpoint-stop to disk and later re-admission on a
    different device set) is layered on top by ``core.stop_resume``:
    ``checkpoint_stop`` is the one-call synchronous entry point
    (``checkpoint_save`` + ``teardown_trainer``, which the cluster
    executor's DiskCheckpointer drives separately so the save can run in
    the background), and ``resume_from_checkpoint`` restores into a fresh
    trainer — the trainer itself always runs at p >= 1.

    ``virtual_workers=K`` (or ``"auto"``) turns on DETERMINISTIC
    elasticity: data, RNG and reduction order are all keyed to K fixed
    virtual workers instead of the physical slices, so any elastic
    trajectory — resizes, reshapes, checkpoint round trips — is
    bitwise-identical to the fixed-shape run. Every dp the job runs at
    must divide K (resize targets that don't are rejected with the same
    ValueError contract as batch divisibility). See docs/architecture.md,
    "Deterministic elasticity".
    """

    def __init__(self, cfg, *, global_batch: int, seq_len: int,
                 init_parallelism: int, model_parallel: int = 1,
                 optimizer: Optimizer | None = None,
                 dataset: SyntheticTokenDataset | None = None,
                 n_samples: int = 1 << 14, d_partitions: int = 64,
                 job_handle: str = "job0",
                 store: CoordinationStore | None = None, seed: int = 0,
                 devices=None, use_aot: bool = True,
                 virtual_workers: int | str | None = None,
                 time_allowance_s: float = TIME_ALLOWANCE_S,
                 compile_service=None, overlap_reshard: bool = True):
        self.cfg = cfg
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.model_parallel = model_parallel
        self.optimizer = optimizer or adamw(1e-3)
        self.devices = list(devices if devices is not None else jax.devices())
        self.job_handle = job_handle
        self.store = store or CoordinationStore()
        self.use_aot = use_aot
        self.seed = seed
        # paper default 500 ms; the cluster executor, whose tenants share
        # one process, uses 0 (commit at the first boundary after prep)
        self.time_allowance_s = time_allowance_s
        # adjustment-overhead pipeline: when a CompileService is attached
        # (ctor arg, or set by the cluster executor after launch), context
        # preps run as priority tickets in its bounded pool instead of a
        # private daemon thread; overlap_reshard stages the switch's state
        # move during the draining mini-batch (see step())
        self.compile_service = compile_service
        self.overlap_reshard = overlap_reshard

        # deterministic elasticity (EasyScale-style virtual workers):
        # n_virtual fixes the logical parallelism for the job's lifetime;
        # every feasible dp must divide it. "auto" = the largest feasible
        # dp on the job's device pool, so every power-of-two shrink from
        # a full scale-out stays admissible.
        self.n_virtual = self._resolve_virtual(virtual_workers,
                                               init_parallelism)

        # data substrate: leader-side pipeline (+ per-slice iterators in
        # dynamic mode; virtual mode assembles batches leader-side from
        # per-virtual-worker cursors, so slices carry no data state)
        self.dataset = dataset or SyntheticTokenDataset(
            n_samples, seq_len, cfg.vocab, seed=seed,
            d_model=cfg.d_model, embeds=(cfg.frontend == "embeds"))
        if self.n_virtual:
            self.pipeline = VirtualWorkerPipeline(
                self.dataset.n_samples, self.n_virtual, seed=seed)
        else:
            self.pipeline = DynamicDataPipeline(self.dataset.n_samples,
                                                d_partitions, seed=seed)

        # control plane
        self.membership = Membership()
        self.controller = ScalingController()
        self.straggler_detector = StragglerDetector()
        self.injected_delay: dict[str, float] = {}
        # chaos surface: a worker in this set has crashed — it sends no
        # more gradient-sync requests, so the leader's liveness view
        # (membership) goes stale until dead-worker detection fires
        self.failed_workers: set[str] = set()

        # bring up the initial topology (this is job launch, not scaling)
        self._exec_cache: dict[tuple, ExecHandle] = {}
        # compiled state moves, keyed on the (source, destination) pair of
        # exec keys; built in an adjustment's prep beside the step
        self._move_cache: dict[tuple, StateMove] = {}
        self._exec_lock = threading.Lock()
        self.p = init_parallelism
        self._worker_seq = 0
        self.worker_ids: list[str] = []
        self.iters: dict[str, WorkerDataIterator] = {}
        for _ in range(init_parallelism):
            self._add_worker()
        self.election = LeaderElection(self.store, job_handle,
                                       self.worker_ids[0])
        res = self.election.elect()
        self.leader_id = res.leader_id

        self.exec = self._build_exec(init_parallelism)
        key = jax.random.PRNGKey(seed)
        with jax.set_mesh(self.exec.mesh):
            state = init_train_state(cfg, self.optimizer, key)
        self.state = jax.device_put(state, self.exec.state_shardings)

        self.step_idx = 0
        self.samples_seen = 0
        self.step_time_ema: float | None = None
        self.metrics_log: list[dict] = []
        self.throughput_log: list[tuple[float, int, float]] = []
        self._prep_thread: threading.Thread | None = None
        self._prep_ticket = None        # CompileTicket when service-backed
        self._prep_error: BaseException | None = None
        # cluster-executor hand-off: called with (trainer, freed_devices)
        # when a release_devices() scale-in commits
        self.on_devices_released: Callable | None = None

    # ------------------------------------------------------------- workers
    def _resolve_virtual(self, virtual_workers, init_p: int) -> int:
        """0 = dynamic-pipeline mode. "auto" picks the max feasible dp on
        the job's device pool; an int is validated against the batch and
        launch shape (every dp the job ever runs at must divide it —
        later resize targets are checked in ``_request``)."""
        if not virtual_workers:
            return 0
        if virtual_workers == "auto":
            from repro.cluster.job import feasible_parallelism
            nv = feasible_parallelism(
                self.global_batch,
                max(1, len(self.devices) // self.model_parallel))
        else:
            nv = int(virtual_workers)
        if nv < 1:
            raise ValueError(f"virtual_workers must be >= 1, got {nv}")
        if self.global_batch % nv:
            raise ValueError(f"global batch {self.global_batch} not "
                             f"divisible by virtual_workers={nv}")
        if nv % init_p:
            raise ValueError(f"init parallelism {init_p} must divide "
                             f"virtual_workers={nv}")
        return nv

    def _add_worker(self) -> str:
        wid = f"w{self._worker_seq}"
        self._worker_seq += 1
        self.worker_ids.append(wid)
        if not self.n_virtual:
            self.iters[wid] = WorkerDataIterator(
                wid, self.pipeline, self.dataset, prefetch=False)
        self.membership.register(wid, len(self.worker_ids) - 1,
                                 at_step=getattr(self, "step_idx", 0))
        return wid

    def _remove_worker(self, wid: str, *, dead: bool = False):
        self.failed_workers.discard(wid)
        it = self.iters.pop(wid, None)
        if it is None:              # virtual mode: no per-slice data state
            self.pipeline.release(wid, dead=dead)
        elif dead:
            self.pipeline.release(wid, dead=True)
        else:
            it.graceful_exit()      # return data remainder
        self.worker_ids.remove(wid)
        self.membership.remove(wid)
        self.straggler_detector.reset(wid)

    # ---------------------------------------------------------- executables
    def _exec_key(self, p: int, mp: int | None = None,
                  devices=None) -> tuple:
        """The exec-cache identity of shape (p, mp) on a device prefix.
        Order matters: mesh layout and shardings are position-dependent,
        so the same device set in a different order is a different
        executable."""
        mp = mp if mp is not None else self.model_parallel
        devs = devices if devices is not None else self.devices
        return (p, mp, tuple(d.id for d in devs[: p * mp]))

    def _build_exec(self, p: int, mp: int | None = None,
                    devices=None, *, adj: int | None = None) -> ExecHandle:
        """Execution-context preparation for shape (p, mp): mesh +
        shardings + AOT-compiled step. This is the cost stop-free scaling
        hides. ``mp`` defaults to the job's current model-parallel degree;
        the RESHAPE verb passes a different one. ``devices`` overrides the
        job's live pool — the speculative-prefetch path builds for a
        PREDICTED device set (e.g. the job's pool plus the free devices a
        growth grant would append) without touching trainer state.

        Handles are cached per (p, mp, exact ordered devices).
        Re-scaling to a topology this job already ran on (compact/expand
        cycles under a cluster policy, migrate at constant p, a prefetched
        shape) skips the recompile entirely; the cache is LRU-bounded so a
        long-lived job cycling through loaner combinations cannot pin
        unbounded compiled executables. The stop-resume baseline clears
        the cache — a restarted process pays context preparation from
        zero. Cache access is lock-guarded: the compile service may build
        speculative handles on a worker thread while the main thread
        steps; the expensive compile itself runs outside the lock. ``adj``
        is the admission number of the adjustment the build serves, where
        there is one (the ``edl.adjust.prep`` span carries it)."""
        mp = mp if mp is not None else self.model_parallel
        devs = list(devices if devices is not None else self.devices)
        key = self._exec_key(p, mp, devs)
        known = {} if adj is None else {"adj": adj}
        with span("edl.adjust.prep", shape=f"{p}x{mp}", **known):
            with self._exec_lock:
                cached = self._exec_cache.get(key)
                if cached is not None:
                    self._exec_cache[key] = self._exec_cache.pop(key)  # LRU
                    return cached
            mesh = make_mesh(p, mp, devices=np.array(devs[: p * mp]))
            step_fn, abstract_args, st_sh, b_sh = jit_step(
                self.cfg, self.optimizer, mesh, seq_len=self.seq_len,
                global_batch=self.global_batch, n_virtual=self.n_virtual,
                seed=self.seed)
            if self.use_aot:
                with jax.set_mesh(mesh):
                    step_fn = step_fn.lower(*abstract_args).compile()
            handle = ExecHandle(p, mp, mesh, step_fn, st_sh, b_sh)
            with self._exec_lock:
                handle = self._exec_cache.setdefault(key, handle)
                while len(self._exec_cache) > EXEC_CACHE_MAX:
                    self._exec_cache.pop(next(iter(self._exec_cache)))
            return handle

    def _state_move(self, src: ExecHandle, dst: ExecHandle, *,
                    adj: int | None = None) -> StateMove:
        """The compiled move of the state from ``src``'s layout onto
        ``dst``'s, built once per pair (LRU-bounded like the exec cache)
        in the adjustment's prep, so that no move compiles in a stop
        window."""
        key = (src.key, dst.key)
        with self._exec_lock:
            cached = self._move_cache.get(key)
            if cached is not None:
                self._move_cache[key] = self._move_cache.pop(key)  # LRU
                return cached
        known = {} if adj is None else {"adj": adj}
        with span("edl.adjust.prep_move", **known,
                  **{"from": f"{src.p}x{src.mp}", "to": f"{dst.p}x{dst.mp}"}):
            move = StateMove(src.state_shardings, dst.state_shardings,
                             _abstract_state(self.cfg, self.optimizer))
        with self._exec_lock:
            move = self._move_cache.setdefault(key, move)
            while len(self._move_cache) > EXEC_CACHE_MAX:
                self._move_cache.pop(next(iter(self._move_cache)))
        return move

    # -------------------------------------------------------------- stepping
    def _assemble_batch(self) -> dict | None:
        """Draw global_batch samples as p per-worker draws (the per-worker
        data flow of the paper; progress offsets update leader-side).

        Epoch tails: draws never cross an epoch boundary, so the final batch
        of an epoch may come up short — it is padded by cycling the drawn
        samples (recorded sample_ids stay un-padded, preserving the
        exactly-once accounting; only the SGD step sees a few duplicates at
        the boundary, the paper-accepted consistency semantics).

        Virtual mode instead assembles the batch leader-side from the
        per-virtual-worker cursors, in fixed virtual order: identical
        sample sequence at every dp, always full (per-vw epoch wrap), no
        padding — the data half of the bitwise-determinism contract."""
        if self.n_virtual:
            if self.pipeline.exhausted:
                return None
            per_vw = self.global_batch // self.n_virtual
            ids = np.concatenate([
                self.pipeline.draw_block(w, self.p, per_vw)
                for w in range(self.p)])
            batch = self.dataset.read_ids(ids)
            self._last_sample_ids = batch.pop("sample_ids")
            if self.cfg.frontend == "embeds":
                batch = {"embeds": batch["embeds"],
                         "labels": batch["labels"]}
            return batch
        per = self.global_batch // self.p
        parts = []
        for wid in self.worker_ids:
            d = self.iters[wid].draw(per)
            if d is not None:
                parts.append(d)
        if not parts:
            return None         # epoch boundary, nothing drawn
        batch = {k: np.concatenate([p_[k] for p_ in parts])
                 for k in parts[0]}
        self._last_sample_ids = batch.pop("sample_ids")
        n = len(self._last_sample_ids)
        if n < self.global_batch:
            reps = -(-self.global_batch // n)
            batch = {k: np.concatenate([v] * reps)[:self.global_batch]
                     for k, v in batch.items()}
        if self.cfg.frontend == "embeds":
            batch = {"embeds": batch["embeds"], "labels": batch["labels"]}
        return batch

    def step(self) -> dict | None:
        """One synchronous mini-batch across the current topology. Raises
        ``PrepFailed`` (and drops the operation) when the background context
        prep of the scaling operation in flight failed.

        Profiler spans, each with ``step``: ``edl.step`` (the whole call),
        and inside it ``.batch`` (assembly), ``.put`` (host-to-device),
        ``.dispatch`` (the step program's call), ``.wait`` (until the loss
        is ready) and ``.post`` (host bookkeeping and read-back, up to the
        boundary's commit check)."""
        with span("edl.step", step=self.step_idx, p=self.p,
                  mp=self.model_parallel):
            return self._step()

    def _step(self) -> dict | None:
        if self._prep_error is not None:
            err, self._prep_error = self._prep_error, None
            op = self.controller.plan.record.op
            self.controller.abort()
            raise PrepFailed(f"{self.job_handle}: background context prep "
                             f"for {op} failed: {err!r}") from err
        t0 = time.monotonic()
        n = self.step_idx
        with span("edl.step.batch", step=n, rows=self.global_batch):
            batch = self._assemble_batch()
        if batch is None:
            return None
        with span("edl.step.put", step=n,
                  bytes=sum(v.nbytes for v in batch.values())):
            dev_batch = jax.device_put(batch, self.exec.batch_shardings)
        with span("edl.step.dispatch", step=n):
            self.state, metrics = self.exec.step_fn(self.state, dev_batch)
        # first chance: the switch is already due at this step's boundary
        # (the DRAINING mini-batch). JAX dispatch is async — step_fn's
        # outputs are futures — so the state move onto the new mesh can be
        # issued NOW and overlap the device compute itself.
        self._maybe_stage_switch()
        with span("edl.step.wait", step=n):
            jax.block_until_ready(metrics["loss"])
        with span("edl.step.post", step=n):
            out = self._post_step(t0, metrics)
        self.notify_batch_end()
        return out

    def _post_step(self, t0: float, metrics) -> dict:
        # second chance: the prep landed DURING this step (typical when
        # k = 1: the switch commits at the very boundary the handle
        # arrives before). Issued here, the transfers still overlap the
        # straggler wait + host bookkeeping below instead of running
        # inside the stop window.
        self._maybe_stage_switch()
        # simulated per-worker sync times (straggler injection adds delay)
        base = time.monotonic() - t0
        sync_times = {wid: base + self.injected_delay.get(wid, 0.0)
                      for wid in self.worker_ids}
        slowest = max(sync_times.values())
        if slowest > base:      # synchronous training waits for the straggler
            time.sleep(min(slowest - base, 0.05))
        t_step = time.monotonic() - t0
        self.step_idx += 1
        self.samples_seen += self.global_batch
        self.step_time_ema = (t_step if self.step_time_ema is None
                              else 0.7 * self.step_time_ema + 0.3 * t_step)
        for wid in self.worker_ids:
            if wid in self.failed_workers:
                continue    # a crashed worker sends no gradient-sync: its
                # membership record ages out and dead_workers() flags it
                # after miss_threshold steps (EDL §4.1 liveness)
            self.membership.sync(wid, self.step_idx, sync_times[wid])
        self.throughput_log.append(
            (time.monotonic(), self.p, self.global_batch / t_step))
        out = {k: float(v) for k, v in metrics.items()}
        out.update(step=self.step_idx, p=self.p, step_time=t_step)
        self.metrics_log.append(out)
        return out

    # --------------------------------------------------- EDL control plane
    def notify_batch_end(self):
        """The paper's notify_batch_end(): scaling switches happen only at
        mini-batch boundaries; this is where a scheduled switch commits."""
        flagged = self.straggler_detector.observe(
            {w.worker_id: (w.step_times[-1] if w.step_times else 0.0)
             for w in self.membership.workers.values()})
        self._flagged_stragglers = flagged
        plan = self.controller.plan
        if plan is not None and plan.ready and \
                self.step_idx >= plan.switch_step:
            self._commit_switch()

    def scale_out(self, n_new: int = 1, *, block: bool = False
                  ) -> ScalingRecord | None:
        """scale_out(): add n_new data-parallel slices, stop-free. Raises
        Busy (the paper's RETRY) if another scaling op is in flight."""
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        return self._request("scale_out", self.p + n_new, block=block)

    def scale_in(self, n_remove: int = 1, *, victims: list[str] | None = None,
                 block: bool = False, release: bool = False
                 ) -> ScalingRecord | None:
        """scale_in(): remove slices via graceful exit. Raises Busy (the
        paper's RETRY) if another scaling op is in flight."""
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        if self.p - n_remove < 1:
            raise ValueError(f"cannot scale below 1 (p={self.p})")
        return self._request("scale_in", self.p - n_remove, block=block,
                             victims=victims, release=release)

    def migrate(self, n: int = 1, *, victims: list[str] | None = None,
                block: bool = True):
        """Fused scale-in + scale-out: one topology switch (§5.2). Pass
        ``victims`` to cycle specific workers (straggler mitigation)."""
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        victims = victims if victims is not None else self.worker_ids[-n:]
        return self._request("migrate", self.p, block=block,
                             victims=victims, n_join=len(victims))

    # ------------------------------------------------------ failure surface
    def inject_worker_failure(self, worker_id: str | None = None) -> str:
        """Chaos entry point: crash a worker. From now on it sends no
        gradient-sync requests, so ``membership.dead_workers`` flags it
        after ``miss_threshold`` missed steps — DETECTION, not injection,
        is what triggers recovery (the injector only breaks things)."""
        wid = worker_id if worker_id is not None else self.worker_ids[-1]
        if wid not in self.worker_ids:
            raise ValueError(f"unknown worker {wid!r}")
        self.failed_workers.add(wid)
        return wid

    def dead_workers(self) -> list[str]:
        """Workers the leader's liveness view currently believes dead."""
        return [w for w in self.membership.dead_workers(self.step_idx)
                if w in self.worker_ids]

    def handle_failure(self, dead: list[str], *, release: bool = True,
                       block: bool = False) -> ScalingRecord | None:
        """Automatic stop-free recovery (EDL §4.2: forced exit is a
        special case of scale-in). The dead workers' device groups are
        moved to the tail of the pool so the survivor mesh is built from
        live devices only, then a scale-in is requested with the dead
        workers as victims — plus, when the feasibility clamp (batch /
        ``n_virtual`` divisibility) skips the shape right below, extra
        graceful victims. Training keeps stepping through the background
        context prep; at commit the dead workers' data partitions return
        via ``pipeline.release(dead=True)`` (replay from the original
        offset) and the freed devices go to ``on_devices_released`` when
        ``release`` is set.

        Raises ``Busy`` while another operation is in flight (caller
        retries) and ``ValueError`` when no feasible survivor shape
        exists — the caller's fallback is a checkpoint-stop."""
        dead = [w for w in dead if w in self.worker_ids]
        if not dead:
            return None
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        target = self.p - len(dead)
        while target >= 1 and (self.global_batch % target or
                               (self.n_virtual and
                                self.n_virtual % target)):
            target -= 1
        if target < 1:
            raise ValueError(
                f"no feasible parallelism below p={self.p} without the "
                f"{len(dead)} dead worker(s) (batch={self.global_batch}, "
                f"virtual_workers={self.n_virtual})")
        survivors = [w for w in self.worker_ids if w not in dead]
        victims = survivors[target:] + dead     # clamp-forced extras exit
        # re-order the pool: victims' groups to the tail, so the survivor
        # mesh uses devices[:target*mp] (all live) and the commit frees
        # exactly the victims' (and any parked surplus) devices. Safe
        # pre-prep: the running executable holds its own mesh reference.
        mp = self.model_parallel
        group = {w: self.devices[i * mp:(i + 1) * mp]
                 for i, w in enumerate(self.worker_ids)}
        surplus = self.devices[len(self.worker_ids) * mp:]
        keep = [w for w in self.worker_ids if w not in victims]
        self.devices = ([d for w in keep for d in group[w]] +
                        [d for w in victims for d in group[w]] + surplus)
        return self._request("scale_in", target, block=block,
                             victims=victims, release=release,
                             dead=tuple(dead))

    def _request(self, op: str, target_p: int, *, block: bool,
                 victims=None, n_join: int | None = None,
                 release: bool = False, target_mp: int | None = None,
                 dead: tuple = ()):
        target_mp = (target_mp if target_mp is not None
                     else self.model_parallel)
        key = self._exec_key(target_p, target_mp)
        with self._exec_lock:
            cache_hit = key in self._exec_cache
        # with block=True the span also holds the steps up to the commit
        with span("edl.adjust.request", adj=self.controller.admitted, op=op,
                  cache_hit=cache_hit,
                  **{"from": f"{self.p}x{self.model_parallel}",
                     "to": f"{target_p}x{target_mp}"}):
            avail = len(self.devices) // target_mp
            if target_p > avail:
                raise ValueError(f"need {target_p} slices of {target_mp} "
                                 f"device(s), have {avail}")
            if self.global_batch % target_p:
                raise ValueError(f"global batch {self.global_batch} not "
                                 f"divisible by p={target_p}")
            if self.n_virtual and self.n_virtual % target_p:
                raise ValueError(
                    f"p={target_p} must divide virtual_workers="
                    f"{self.n_virtual} (virtual blocks stay contiguous and "
                    f"equal-sized at every shape)")
            plan = self.controller.admit(op, self.p, target_p)  # raises Busy
            adj = plan.record.adj
            plan.record.from_mp = self.model_parallel
            plan.record.to_mp = target_mp
            plan.exiting = tuple(victims or ())
            plan.dead_exiting = tuple(dead)
            plan.joining = ("new",) * (n_join or max(0, target_p - self.p))
            plan.release_devices = release
            steps_before = self.step_idx
            plan.record.exec_cache_key = key
            plan.record.compile_cache_hit = cache_hit

            def finish(handle):
                k = max(1, math.ceil(self.time_allowance_s /
                                     max(self.step_time_ema or 0.01, 1e-4)))
                plan.record.steps_during_prep = self.step_idx - steps_before
                self.controller.prepared(self.step_idx + k, handle)

            src = self.exec

            def build():
                handle = self._build_exec(target_p, target_mp, adj=adj)
                self._state_move(src, handle, adj=adj)
                return handle

            def prepare():
                finish(build())

            def prepare_in_background():
                try:
                    prepare()
                except Exception as e:      # raised by the next step()
                    self._prep_error = e

            if block:
                prepare()
                # commit at the next boundary manually
                while self.controller.phase is Phase.SCHEDULED:
                    if self.step() is None:
                        self._commit_switch()
                return self.controller.history[-1]
            if cache_hit:
                # warm shape (prefetched, or one this job already ran at):
                # prep IS the cache lookup — schedule inline, no thread or
                # ticket round trip, prep_s collapses to microseconds
                prepare()
                return None
            svc = self.compile_service
            if svc is not None:
                from repro.core.compile_service import DONE, PRIO_COMMITTED

                def on_ticket(t):
                    if t.state != DONE:     # raised by the next step()
                        self._prep_error = t.error or RuntimeError(
                            f"context prep ticket {key} ended {t.state}")
                        return
                    finish(t.value)

                # dedup/escalation: if a speculative prefetch of this shape
                # is already pending or running, this JOINS it as committed
                self._prep_ticket = svc.submit(
                    key, build, priority=PRIO_COMMITTED, owner=self.job_handle)
                self._prep_ticket.add_done_callback(on_ticket)
                return None
            self._prep_thread = threading.Thread(target=prepare_in_background,
                                                 daemon=True)
            self._prep_thread.start()
            return None

    def _maybe_stage_switch(self):
        """Stage the state move when a ready switch commits at the current
        step's boundary (and overlap is on)."""
        plan = self.controller.plan
        if (self.overlap_reshard and plan is not None and plan.ready
                and self.step_idx + 1 >= plan.switch_step):
            self._stage_switch(plan)

    def _stage_switch(self, plan):
        """Overlapped state move: issue the switch's ``StateMove``
        against the CURRENT state (whose producing step may still be in
        flight — async dispatch queues the transfers behind it) into
        fresh destination buffers on the new mesh. The staged arrays are
        the double buffer: the live state keeps its own buffers until the
        commit's pointer swap, so training output is untouched if the
        commit never consumes the staging (it falls back to the in-stop
        move)."""
        if plan.staged_state is not None:
            return
        rec = plan.record
        rec.t_stage_start = self.controller.clock()
        handle: ExecHandle = plan.exec_handle
        rplan = self._reshard_plan(handle)
        if rec.op == "reshape":
            rec.reshard_bytes_moved = rplan.bytes_moved
            rec.reshard_bytes_kept = rplan.bytes_kept
            rec.bytes_moved_overlapped = rplan.bytes_moved
        move = self._state_move(self.exec, handle)
        with span("edl.adjust.staged_reshard", adj=rec.adj, op=rec.op,
                  bytes=rplan.bytes_moved, host_bytes=move.host_bytes):
            plan.staged_state = self._move_state(rplan, move, handle)
        plan.staged_from = self.state
        rec.t_stage_end = self.controller.clock()

    def _reshard_plan(self, handle: ExecHandle):
        """The planner's move from the live layout to ``handle``'s (its
        ``bytes_moved`` prices a resize's move too)."""
        src = StateSpec.for_trainer(self)
        dst = StateSpec.from_shardings(handle.p, handle.mp,
                                       handle.state_shardings, self.state)
        return plan_reshard(src, dst)

    def _move_state(self, rplan, move: StateMove, handle: ExecHandle):
        """Start the state move onto ``handle``'s mesh along ``move``: every
        switch (resize, grant, release, migrate, reshape) is a reshard, a
        resize one that keeps ``mp``. Records the bytes that went through
        host memory."""
        state, host_bytes = apply_plan(rplan, self.state,
                                       handle.state_shardings, move)
        self.controller.plan.record.host_bytes = host_bytes
        return state

    def _commit_switch(self):
        """The brief stop: reshard state (model broadcast) + swap topology.
        It ends once the whole moved state is ready on the new mesh, so the
        record's stop time is the device's move, not its enqueue."""
        plan = self.controller.plan
        rec = plan.record
        staged = (plan.staged_state is not None
                  and plan.staged_from is self.state)
        with span("edl.adjust.stop_window", adj=rec.adj, op=rec.op,
                  staged=staged):
            rec, freed = self._switch(plan, staged)
        if freed and self.on_devices_released is not None:
            # let the hook know WHICH verb is freeing (a reshape's surplus
            # is not a data-parallel scale-in; event logs must not invent
            # a p-transition that never happened)
            self._releasing_op = rec.op
            try:
                self.on_devices_released(self, freed)
            finally:
                self._releasing_op = None
        return rec

    def _switch(self, plan, staged: bool):
        """The stop window itself; returns the completed record and the
        devices it frees."""
        self.controller.begin_switch()
        handle: ExecHandle = plan.exec_handle
        op = plan.record.op
        # graceful exit of victims (their data remainder returns to the
        # pool). A reshape that shrinks the data axis retires the surplus
        # data-parallel slices exactly like a scale-in.
        if op in ("scale_in", "migrate") or \
                (op == "reshape" and handle.p < len(self.worker_ids)):
            victims = list(plan.exiting) or self.worker_ids[handle.p:]
            leader_leaving = self.leader_id in victims
            for wid in victims:
                self._remove_worker(wid, dead=wid in plan.dead_exiting)
            if leader_leaving:
                self.election.resign()
                self.election = LeaderElection(self.store, self.job_handle,
                                               self.worker_ids[0])
                self.leader_id = self.election.elect().leader_id
        while len(self.worker_ids) < handle.p:
            self._add_worker()
        # model broadcast == reshard onto the new mesh. The overlapped
        # path consumed nothing but host time so far: if the draining
        # mini-batch staged the move (see _stage_switch) against exactly
        # this state, the transfers have been in flight since dispatch —
        # only the readiness wait + pointer swap remain in the stop.
        # A reshape's record carries the planner's move accounting.
        adj = plan.record.adj
        if staged:
            self.state = plan.staged_state
        else:
            rplan = self._reshard_plan(handle)
            if op == "reshape":
                plan.record.reshard_bytes_moved = rplan.bytes_moved
                plan.record.reshard_bytes_kept = rplan.bytes_kept
                plan.record.bytes_moved_overlapped = 0
            move = self._state_move(self.exec, handle)
            with span("edl.adjust.move", adj=adj, bytes=rplan.bytes_moved,
                      host_bytes=move.host_bytes):
                self.state = self._move_state(rplan, move, handle)
        with span("edl.adjust.ready", adj=adj):
            jax.block_until_ready(self.state)
        self.exec = handle
        self.p = handle.p
        self.model_parallel = handle.mp
        freed = []
        if plan.release_devices:
            # hand everything beyond the new topology back to the caller
            # (cluster executor reclaim): the job stops owning those devices
            in_use = handle.p * handle.mp
            freed, self.devices = self.devices[in_use:], self.devices[:in_use]
        return self.controller.complete(), freed

    # ------------------------------------------------ device pool hand-off
    def grant_devices(self, new_devices, *, block: bool = False
                      ) -> ScalingRecord | None:
        """Non-blocking hand-off path: a scheduler grants this job extra
        devices (e.g. transient resources loaned from an idle pool) and the
        job scales out onto them, stop-free. The devices join the job's pool
        immediately; the topology switch commits at a mini-batch boundary."""
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        n_new, rem = divmod(len(new_devices), self.model_parallel)
        if n_new < 1 or rem:
            # a partial group could never host a data-parallel slice of the
            # (data, model) mesh; refusing keeps grant arithmetic exact
            raise ValueError(
                f"grants move whole device groups: got {len(new_devices)} "
                f"device(s), group size is {self.model_parallel}")
        self.devices = self.devices + list(new_devices)
        try:
            return self._request("scale_out", self.p + n_new, block=block)
        except Exception:
            self.devices = self.devices[:len(self.devices)
                                        - len(new_devices)]
            raise

    def release_devices(self, n_slices: int = 1, *,
                        victims: list[str] | None = None,
                        block: bool = False) -> ScalingRecord | None:
        """Graceful scale-in that RETURNS the freed devices: once the switch
        commits, the devices leave ``self.devices`` and are handed to the
        ``on_devices_released`` hook (the reclaim side of a transient loan).
        Stop-free like any scale-in; raises Busy under a conflicting op."""
        return self.scale_in(n_slices, victims=victims, block=block,
                             release=True)

    def reshape(self, p: int, mp: int, *, new_devices=None,
                block: bool = False, release: bool = False
                ) -> ScalingRecord | None:
        """RESHAPE: trade data-parallel for model-parallel degree live —
        re-mesh the job from ``(self.p, self.model_parallel)`` to
        ``(p, mp)`` stop-free. The new executable compiles in the
        background while training continues at the old shape; at the
        scheduled mini-batch boundary the train state is resharded onto
        the new mesh along a ``repro.reshape.plan_reshard`` plan (the
        record carries its byte accounting) and surplus data-parallel
        slices exit gracefully, returning their data remainders.

        Device arithmetic: ``new_devices`` joins the job's pool first (a
        scheduler funding a footprint-growing reshape); with ``release=
        True`` any devices beyond ``p * mp`` are handed to
        ``on_devices_released`` when the switch commits (a footprint-
        shrinking reshape returns them to the scheduler's free pool).
        Raises ``Busy`` (the paper's RETRY) while another operation is in
        flight."""
        if self.controller.phase is not Phase.IDLE:
            raise Busy("scaling in flight; retry later")
        if mp < 1 or p < 1:
            raise ValueError(f"reshape target ({p}, {mp}) must be >= 1 "
                             f"on both axes")
        if p == self.p and mp == self.model_parallel:
            raise ValueError(f"already at shape ({p}, {mp})")
        if new_devices:
            self.devices = self.devices + list(new_devices)
        try:
            return self._request("reshape", p, block=block,
                                 release=release, target_mp=mp)
        except Exception:
            if new_devices:
                self.devices = self.devices[:len(self.devices)
                                            - len(new_devices)]
            raise

    # ------------------------------------------------------------- helpers
    def run(self, n_steps: int, *, on_step=None):
        done = 0
        while done < n_steps:
            m = self.step()
            if m is None:       # epoch rolled; pipeline restarts itself
                if self.pipeline.exhausted:
                    break
                continue
            done += 1
            if on_step:
                on_step(m)
        return done

    def join_prep(self, timeout: float | None = None) -> bool:
        """Wait (bounded) for the in-flight context prep, whichever engine
        carries it — the legacy private thread or a compile-service
        ticket. Returns True when no prep remains in flight. This is the
        executor's event-driven replacement for fixed-quantum sleeps: the
        wait returns the moment the handle lands."""
        ticket = self._prep_ticket
        if ticket is not None:
            done = ticket.wait(timeout)
            if done:
                self._prep_ticket = None
            return done
        t = self._prep_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            return not t.is_alive()
        return True

    def wait_for_scaling(self, max_steps: int = 10_000):
        """Keep training (stop-free!) until the in-flight scaling commits."""
        steps = 0
        while self.controller.phase is not Phase.IDLE and steps < max_steps:
            m = self.step()
            if m is None and self.controller.phase is Phase.SCHEDULED:
                self._commit_switch()
            steps += 1
        return self.controller.history[-1] if self.controller.history else None

    def throughput(self, last_n: int = 20) -> float:
        xs = self.throughput_log[-last_n:]
        return float(np.mean([t for _, _, t in xs])) if xs else 0.0


def jit_step(cfg, optimizer, mesh, *, seq_len: int, global_batch: int,
             n_virtual: int = 0, seed: int = 0):
    """The jitted train step of a job on ``mesh``: returns ``(step,
    abstract_args, state_shardings, batch_shardings)``, where
    ``step.lower(*abstract_args)`` needs no arrays, so ``mesh`` may hold
    described devices (a compile for a chip that is not attached). Virtual
    mode builds the deterministic shard_map step for THIS mesh shape; its
    math (per-vw slices, tree reduction, per-vw RNG) is a function of
    ``n_virtual`` alone, so every shape computes bitwise-identical
    updates."""
    from repro.configs.base import InputShape, input_specs
    st_sh = state_sharding(cfg, mesh, optimizer)
    specs = input_specs(cfg, InputShape("rt", seq_len, global_batch,
                                        "train"))
    specs.pop("cache", None)
    b_sh = batch_sharding(cfg, mesh, specs)
    fn = make_train_step(cfg, optimizer, n_virtual=n_virtual, mesh=mesh,
                         global_batch=global_batch, seed=seed)
    step = jax.jit(fn, in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, None))
    return step, (_abstract_state(cfg, optimizer), specs), st_sh, b_sh


def _abstract_state(cfg, optimizer):
    from repro.training.step import state_shape_structs
    s = state_shape_structs(cfg, optimizer)
    if optimizer.slots < 2:
        s["opt"].pop("nu", None)
    return s
