"""Chip smoke test: drive the elastic trainer and the cluster executor once on
a TPU, through the drivers' own entry points, and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one 2x2 host, four chips

One chip: both Pallas kernels, compiled for the chip, match their jnp
references; the ``edl-paper`` configuration at its full published width and
depth (12 x 768, vocabulary 32768, random weights from seed 0) trains 20
steps at global batch 8 x 512 as ``repro.launch.train`` builds it; it is
checkpoint-stopped and resumed into a fresh trainer, whose state must equal
the saved state bit for bit; then ``repro.launch.cluster`` time-shares the
chip between two tenants under Tiresias, with a preemption and a
re-admission.

Four chips: the same configuration with four virtual workers at 16 x 256
runs the elastic path p=2 -> scale_out to 4 -> reshape to (2, 2) ->
scale_in to (1, 2), and its per-step losses must equal a static p=2 run's;
then the cluster driver runs its default three tenants on the four chips.

Lines before the last are smoke output, prefixed ``smoke``: timings there
describe this run, not a benchmark. The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, and prints no such line, when JAX finds no TPU
or any check fails. Everything runs in this one process, which holds the
chip; checkpoints and libtpu logs go under ``.chip_smoke/``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")


class SmokeFailure(Exception):
    pass


def log(phase: str, **fields):
    print(f"smoke {phase} {json.dumps(fields)}", flush=True)


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def _mean(xs):
    return sum(xs) / len(xs)


def _peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def _flat(tree, prefix=""):
    """{path: numpy array} of a nested dict of arrays."""
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(_flat(tree[k], f"{prefix}/{k}"))
        return out
    return {prefix: tree}


# ------------------------------------------------------------- one chip
def trainer_phase(devices):
    """20 steps of the full edl-paper configuration on one chip."""
    from repro.launch import train as train_driver
    args = train_driver.parse_args([
        "--arch", "edl-paper", "--steps", "20", "--batch", "8",
        "--seq", "512", "--init-p", "1", "--devices", "1"])
    t0 = time.monotonic()
    trainer = train_driver.build_trainer(args, devices)
    build_s = time.monotonic() - t0
    summary = train_driver.train(trainer, args, log=lambda *a, **k: None)
    losses = summary["losses"]
    step_s = [m["step_time"] for m in trainer.metrics_log]
    log("trainer", arch=summary["arch"], batch=args.batch, seq=args.seq,
        build_and_compile_s=build_s, first_step_s=step_s[0],
        median_step_s=sorted(step_s[1:])[len(step_s[1:]) // 2],
        peak_bytes_in_use=_peak_bytes(devices), losses=losses,
        unique_sample_frac=summary["unique_sample_frac"])
    vocab = trainer.cfg.vocab
    check(len(losses) == args.steps, f"{len(losses)} losses for "
          f"{args.steps} steps")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    # labels are uniform over the vocabulary, and at init the logits have
    # unit variance (RMS-normed hidden state, head std 1/sqrt(d_model)), so
    # the first loss sits at ln(V) + 1/2, the mean excess of Gaussian logits
    excess = losses[0] - math.log(vocab)
    check(abs(excess - 0.5) < 0.25,
          f"first loss {losses[0]} is {excess} above ln({vocab}), not "
          f"within 0.25 of the 0.5 that random init gives")
    check(_mean(losses[-5:]) < _mean(losses[:5]),
          "the mean of the last 5 losses is not below the first 5's")
    check(summary["unique_sample_frac"] == 1.0,
          f"unique_sample_frac {summary['unique_sample_frac']} != 1.0")
    return trainer, args


def preemption_phase(trainer, args, devices):
    """Checkpoint-stop, then resume into a fresh trainer on the same chip:
    the state must come back bit for bit, and training goes on."""
    import jax
    from repro.core import checkpoint_stop, resume_from_checkpoint
    from repro.core.stop_resume import teardown_trainer
    from repro.launch import train as train_driver
    ckpt = os.path.join(OUT, "preempt")
    before = _flat(jax.device_get(trainer.state))
    step = trainer.step_idx
    t0 = time.monotonic()
    freed = checkpoint_stop(trainer, ckpt)
    stop_s = time.monotonic() - t0
    t0 = time.monotonic()
    fresh = train_driver.build_trainer(args, freed)
    resume_from_checkpoint(fresh, ckpt)
    resume_s = time.monotonic() - t0
    after = _flat(jax.device_get(fresh.state))
    check(sorted(before) == sorted(after), "restored state has other "
          "tensors than the saved one")
    differ = [k for k in before
              if before[k].dtype != after[k].dtype
              or before[k].shape != after[k].shape
              or before[k].tobytes() != after[k].tobytes()]
    check(not differ, f"restored tensors differ from the saved ones: "
          f"{differ[:5]}")
    check(fresh.step_idx == step, f"resumed at step {fresh.step_idx}, "
          f"stopped at {step}")
    more = [fresh.step() for _ in range(3)]
    check(all(m is not None and math.isfinite(m["loss"]) for m in more),
          "a step after the resume failed or gave a non-finite loss")
    check([m["step"] for m in more] == [step + 1, step + 2, step + 3],
          "step counter did not continue after the resume")
    log("preemption", tensors=len(before), bitwise_equal=True,
        checkpoint_stop_s=stop_s, rebuild_and_resume_s=resume_s,
        losses_after=[m["loss"] for m in more])
    teardown_trainer(fresh)
    shutil.rmtree(ckpt)


def cluster_phase(devices, argv, checks):
    """The cluster driver's run on ``devices``; ``checks(stats)``."""
    from repro.launch import cluster as cluster_driver
    args = cluster_driver.parse_args(argv)
    root = os.path.join(OUT, "cluster_ckpt")
    os.makedirs(root, exist_ok=True)
    # run() raises DeviceLeak the round device conservation breaks
    stats = cluster_driver.run(args, devices, checkpoint_root=root)
    ops = [e["op"] for e in stats["events"]]
    jobs = [{k: j[k] for k in ("name", "state", "steps_done", "final_loss",
                               "attained_gpu_s")} for j in stats["jobs"]]
    for j in jobs:      # device-seconds per step; at p=1, the step time
        j["device_s_per_step"] = j["attained_gpu_s"] / max(1, j["steps_done"])
    log("cluster", policy=stats["policy"], rounds=stats["rounds"],
        wall_s=stats["wall_s"], conserved=stats["conserved"],
        preemptions=stats["preemptions"],
        readmissions=stats["readmissions"],
        ops={op: ops.count(op) for op in sorted(set(ops))},
        events=[[e["round"], e["op"], e["job"], e["from_p"], e["to_p"]]
                for e in stats["events"]],
        jobs=jobs, peak_bytes_in_use=_peak_bytes(devices))
    unfinished = [j["name"] for j in stats["jobs"]
                  if j["state"] != "finished"]
    check(not unfinished, f"jobs did not finish: {unfinished}")
    check(all(math.isfinite(j["final_loss"]) for j in stats["jobs"]),
          "a tenant's final loss is not finite")
    checks(stats)


def kernels_phase(seq: int = 1024):
    """Both Pallas kernels, compiled for the chip, against the repo's jnp
    references (kernels/*/ref.py) at full precision: flash attention at
    edl-paper width (12 heads of 64, bf16) and wkv6 at rwkv6-1.6b width
    (32 heads of 64, fp32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.attention.ops import flash_attention
    from repro.kernels.attention.ref import attention_ref
    from repro.kernels.rwkv.ops import wkv6
    from repro.kernels.rwkv.ref import wkv6_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def compare(name, fn, ref, args, tol):
        hlo = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in hlo, f"{name} did not compile to a "
              f"Mosaic kernel (interpreted?)")
        out = jax.block_until_ready(jax.jit(fn)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(*args))
        errs = [float(np.max(np.abs(np.asarray(o, np.float32)
                                    - np.asarray(w, np.float32))))
                / float(np.max(np.abs(np.asarray(w, np.float32))))
                for o, w in zip(jax.tree.leaves(out), jax.tree.leaves(want))]
        log("kernel", name=name, max_err_over_max_ref=errs, limit=tol)
        check(all(e < tol for e in errs), f"{name} differs from its "
              f"reference by {errs} of the reference's largest value")

    # [B, Hkv, G, L, D] and [B, Hkv, L, D], the model's grouped layout
    q = jax.random.normal(ks[0], (2, 12, 1, seq, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 12, seq, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 12, seq, 64), jnp.bfloat16)
    compare("flash_attention", lambda q, k, v: flash_attention(q, k, v),
            lambda q, k, v: attention_ref(q[:, :, 0], k, v)[:, :, None],
            (q, k, v), 2e-2)
    # [B, L, H, hd]; decays as the model makes them (log w <= 0)
    shape = (1, seq, 32, 64)
    r, kk, vv = (jax.random.normal(ks[3 + i], shape) for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[6], shape) * 0.5)
    u = jax.random.normal(ks[7], (32, 64)) * 0.3
    s0 = jnp.zeros((1, 32, 64, 64))
    tr = lambda a: jnp.swapaxes(a, 1, 2)

    def wkv6_model_layout_ref(r, k, v, logw, u, s0):
        y, sT = wkv6_ref(tr(r), tr(k), tr(v), tr(logw), u, s0)
        return tr(y), sT
    compare("wkv6", wkv6, wkv6_model_layout_ref, (r, kk, vv, logw, u, s0),
            2e-2)


def one_chip(devices):
    kernels_phase()
    trainer, args = trainer_phase(devices)
    preemption_phase(trainer, args, devices)
    cluster_phase(
        devices,
        # a quantum of 1 ms of chip time: the first tenant has used it up
        # when the second arrives, so Tiresias preempts it
        ["--devices", "1", "--policy", "tiresias", "--quanta", "0.001,1000",
         "--jobs", "a=resnet50:1:20@0,b=vgg19:1:12@6"],
        lambda s: check(s["readmissions"] >= 1, "no tenant was preempted "
                        "and re-admitted"))


# ------------------------------------------------------------ four chips
def four_chips(devices):
    """Elastic p=2 -> 4 -> (2, 2) -> (1, 2) against a static p=2 run, both
    with four virtual workers; then the default three-tenant cluster."""
    from repro.core.compile_service import CompileService, PRIO_SPECULATIVE
    from repro.core.scaling import Phase
    from repro.core.stop_resume import teardown_trainer
    from repro.launch import train as train_driver
    # 16 x 256, not 16 x 512: at dp=1 each device runs all four virtual
    # workers, and (1, 2) at 16 x 512 needs 20.9 GB of a 15.75 GB chip
    args = train_driver.parse_args([
        "--arch", "edl-paper", "--batch", "16", "--seq", "256",
        "--init-p", "2", "--devices", "4", "--virtual-workers", "4"])
    t0 = time.monotonic()
    elastic = train_driver.build_trainer(args, devices)
    build_s = time.monotonic() - t0
    # the executor's speculative prefetch: the three target shapes compile
    # in the background while the static run is built (its p=2 executable
    # comes from the persistent cache) and trains, so each switch below
    # finds its executable warm
    svc = CompileService(workers=3)
    elastic.compile_service = svc
    t0 = time.monotonic()
    for p, mp in [(4, 1), (2, 2), (1, 2)]:
        svc.submit(elastic._exec_key(p, mp),
                   lambda p=p, mp=mp: elastic._build_exec(p, mp),
                   priority=PRIO_SPECULATIVE, owner="smoke")
    static = train_driver.build_trainer(args, devices)
    static_losses = [static.step()["loss"] for _ in range(24)]
    check(svc.drain(900), "target shapes did not compile in 900 s")
    prefetch_s = time.monotonic() - t0
    try:
        ops = [("scale_out", lambda: elastic.scale_out(2)),
               ("reshape", lambda: elastic.reshape(2, 2)),
               ("scale_in", lambda: elastic.scale_in(1))]
        losses, since_idle = [], 0
        while ops or elastic.controller.phase is not Phase.IDLE \
                or since_idle < 3:
            if elastic.controller.phase is Phase.IDLE:
                if ops and since_idle >= 3:
                    ops.pop(0)[1]()
                    since_idle = 0
            m = elastic.step()
            losses.append(m["loss"])
            since_idle = (since_idle + 1
                          if elastic.controller.phase is Phase.IDLE else 0)
        while len(static_losses) < len(losses):
            static_losses.append(static.step()["loss"])
    finally:
        svc.shutdown()
    records = [r.summary() for r in elastic.controller.history]
    diff = max(abs(a - b) for a, b in zip(losses, static_losses))
    log("elastic_vs_static", steps=len(losses),
        shapes=[(r["op"], r["from_p"], r["to_p"], r.get("to_mp", 1))
                for r in records],
        stop_s=[r["stop_s"] for r in records],
        cache_hit=[r["cache_hit"] for r in records],
        build_and_compile_s=build_s, prefetch_s=prefetch_s,
        max_abs_loss_diff=diff, elastic_losses=losses,
        static_losses=static_losses[:len(losses)],
        peak_bytes_in_use=_peak_bytes(devices))
    check([r["op"] for r in records] == ["scale_out", "reshape", "scale_in"],
          f"scaling records {records}")
    check((elastic.p, elastic.model_parallel) == (1, 2),
          f"elastic run ended at {(elastic.p, elastic.model_parallel)}")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    teardown_trainer(static)
    teardown_trainer(elastic)

    def grew_and_shrank(s):
        grow = [e for e in s["events"]
                if e["op"] == "scale_out" and e["from_p"] > 0]
        shrink = [e for e in s["events"] if e["op"] == "scale_in"]
        check(grow and shrink, "no live scale_out and scale_in")
    # the cluster runs even when the losses differ: one call shows both
    cluster_phase(devices, ["--devices", "4"], grew_and_shrank)
    check(losses == static_losses[:len(losses)],
          f"elastic losses differ from the static run's (largest "
          f"difference {diff})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (a 2x2 host)")
    args = ap.parse_args(argv)
    os.makedirs(OUT, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT, "tpu_logs"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.devices import describe, enable_compile_cache, \
        pick_devices
    n = 4 if args.four_chips else 1
    devices = pick_devices(n)
    cache = enable_compile_cache()
    log("setup", platform=dev.platform, kind=dev.device_kind,
        devices=len(jax.devices()), using=n, jax=jax.__version__,
        compile_cache=cache)
    try:
        (four_chips if args.four_chips else one_chip)(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": describe(jax.devices())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
