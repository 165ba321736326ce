import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
    # LICM hoists the bf16->f32 convert of the remat residual stack out of the
    # backward loop, materializing an fp32 copy of the whole [L,B,T,D] stack
    # (+24 GiB/device on phi3 train_4k). Disable for honest memory analysis;
    # see EXPERIMENTS.md §Dry-run.
    + " --xla_disable_hlo_passes=while-loop-invariant-code-motion,"
      "while-loop-expensive-invariant-code-motion")
"""Multi-pod dry-run + roofline-term extraction.

Two phases per (architecture x input-shape x mesh):

  A. FULL config, layer-scanned: jit(...).lower().compile() — proves the
     sharding is coherent, gives memory_analysis() (fits-per-device) and the
     collective schedule. This is the required dry-run deliverable.

  B. COST compiles (single-pod only): XLA's cost_analysis() counts a while
     loop's body ONCE, not x trip-count (verified in EXPERIMENTS.md §Dry-run),
     so HLO_FLOPs of a scanned module undercounts. We therefore compile the
     SAME program at 1x and 2x the layer period, Python-unrolled with inner
     chunk loops unrolled too (lax.scan unroll=n), and extrapolate linearly in
     depth — exact for depth-homogeneous stacks:
         total(k periods) = base + k * per_period
     Collective bytes are parsed from the post-SPMD HLO the same way.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k [--multi-pod] [--out results.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import dataclasses
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, input_specs
from repro.launch.mesh import make_production_mesh, peaks_for
from repro.models import model as M
from repro.models.blocks import scan_plan
from repro.optim import adamw
from repro.training.step import batch_sharding, cache_sharding, \
    make_train_step, params_sharding, state_shape_structs, state_sharding

TARGET_KIND = "TPU v5 lite"     # the chip of make_production_mesh's pod
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")
COLLECTIVE_RE = re.compile(
    r"=\s+(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)\[([0-9,]*)\]\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
TUPLE_COLLECTIVE_RE = re.compile(
    r"=\s+\(([^)]*)\)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)\[([0-9,]*)\]")
DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-chip bytes of every collective in the post-SPMD HLO (output-operand
    sizes, per-device shapes). Handles tuple-shaped variadic collectives."""
    out = {k: 0.0 for k in COLLECTIVE_KINDS}
    count = {k: 0 for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if m:
            out[m.group(3)] += _shape_bytes(m.group(1), m.group(2))
            count[m.group(3)] += 1
            continue
        m = TUPLE_COLLECTIVE_RE.search(line)
        if m:
            total = sum(_shape_bytes(d, s)
                        for d, s in SHAPE_RE.findall(m.group(1)))
            out[m.group(2)] += total
            count[m.group(2)] += 1
    return {"bytes": out, "ops": count,
            "total": float(sum(out.values()))}


def _lower(cfg, shape, mesh):
    """Build + lower the step function for one (cfg, shape, mesh)."""
    specs = input_specs(cfg, shape)
    if shape.mode == "train":
        optimizer = adamw(1e-4)
        fn = make_train_step(cfg, optimizer)
        st = state_shape_structs(cfg, optimizer)
        st_sh = state_sharding(cfg, mesh, optimizer)
        b_sh = batch_sharding(cfg, mesh, specs)
        with jax.set_mesh(mesh):
            return jax.jit(fn, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None)).lower(st, specs)
    p = M.param_shape_structs(cfg)
    p_sh = params_sharding(cfg, mesh)
    if shape.mode == "prefill":
        fn = lambda params, batch: M.prefill(cfg, params, batch)
        b_sh = batch_sharding(cfg, mesh, specs)
        with jax.set_mesh(mesh):
            return jax.jit(fn, in_shardings=(p_sh, b_sh)).lower(p, specs)
    fn = lambda params, batch, cache: M.serve_step(cfg, params, batch, cache)
    cache_specs_ = specs.pop("cache")
    c_sh = cache_sharding(cfg, mesh, shape.global_batch, shape.seq_len)
    b_sh = batch_sharding(cfg, mesh, specs)
    with jax.set_mesh(mesh):
        return jax.jit(fn, in_shardings=(p_sh, b_sh, c_sh),
                       out_shardings=(None, c_sh)).lower(
                           p, specs, cache_specs_)


def _cost_cfg(cfg, shape, k_periods: int):
    """Reduced-depth, fully-unrolled variant for exact cost accounting."""
    _, n_periods = scan_plan(cfg)
    period = cfg.n_layers // n_periods
    L = shape.seq_len
    kw = dict(
        n_layers=period * k_periods, scan_layers=False, full_unroll=True,
        attn_chunk=max(L // 8, min(1024, L)),
        loss_chunk=max(L // 4, min(1024, L)),
        mamba_chunk=max(L // 4, min(128, L)),
        chunked_wkv=True, wkv_chunk=max(L // 16, min(256, L)),
    )
    return dataclasses.replace(cfg, **kw), n_periods


def _extract_costs(compiled):
    ca = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll["total"], "coll_bytes": coll["bytes"],
            "coll_ops": coll["ops"]}


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               cost: bool = True, verbose: bool = True,
               save_hlo: str | None = None, swa_pruned: bool = True,
               mesh_override: tuple[int, int] | None = None) -> dict:
    cfg = dataclasses.replace(get_config(arch), swa_pruned=swa_pruned)
    shape = INPUT_SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return {"arch": arch, "shape": shape_name, "status": "SKIP",
                "mesh": "pod2x16x16" if multi_pod else "16x16",
                "reason": "pure full-attention arch; long-context decode "
                          "requires sub-quadratic attention (DESIGN.md §5)"}
    if mesh_override is not None:
        # §Perf lever: same 256 chips, different logical (data, model) split
        d_ax, m_ax = mesh_override
        assert d_ax * m_ax == 256
        mesh = jax.make_mesh((d_ax, m_ax), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size

    # ---- Phase A: full-config dry-run --------------------------------
    t0 = time.monotonic()
    lowered = _lower(cfg, shape, mesh)
    t_lower = time.monotonic() - t0
    t0 = time.monotonic()
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0
    mem = compiled.memory_analysis()
    full_coll = parse_collective_bytes(compiled.as_text())
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(compiled.as_text())

    result = {
        "arch": arch, "shape": shape_name, "status": "OK",
        "mesh": (f"{mesh_override[0]}x{mesh_override[1]}" if mesh_override
                 else ("pod2x16x16" if multi_pod else "16x16")),
        "chips": n_chips,
        "mode": shape.mode, "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "arg_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "per_device_bytes": (mem.argument_size_in_bytes +
                             mem.temp_size_in_bytes),
        "collective_ops_full": full_coll["ops"],
    }

    # ---- Phase B: exact cost via depth extrapolation ------------------
    if cost:
        cfg1, n_periods = _cost_cfg(cfg, shape, 1)
        cfg2, _ = _cost_cfg(cfg, shape, 2)
        c1 = _extract_costs(_lower(cfg1, shape, mesh).compile())
        c2 = _extract_costs(_lower(cfg2, shape, mesh).compile())
        per = {k: c2[k] - c1[k] for k in ("flops", "bytes", "coll")}
        tot = {k: c1[k] + (n_periods - 1) * per[k]
               for k in ("flops", "bytes", "coll")}
        coll_bytes = {k: c1["coll_bytes"][k] + (n_periods - 1) *
                      (c2["coll_bytes"][k] - c1["coll_bytes"][k])
                      for k in c1["coll_bytes"]}
        # per-device numbers against the production pod's chip, which this
        # CPU-hosted dry-run only describes
        peaks = peaks_for(TARGET_KIND)
        t_compute = tot["flops"] / peaks.flops_bf16
        t_memory = tot["bytes"] / peaks.hbm_bw
        t_coll = tot["coll"] / peaks.ici_bw
        n_params = cfg.param_count()
        n_active = cfg.active_param_count()
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.mode != "decode" else 1)
        mult = 6 if shape.mode == "train" else 2
        model_flops = mult * n_active * tokens
        dom = max(("compute", t_compute), ("memory", t_memory),
                  ("collective", t_coll), key=lambda kv: kv[1])[0]
        result.update({
            "hlo_flops_per_device": tot["flops"],
            "hlo_bytes_per_device": tot["bytes"],
            "collective_bytes_per_device": tot["coll"],
            "collective_breakdown": coll_bytes,
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dom,
            "params": n_params, "active_params": n_active,
            "model_flops": model_flops,
            "useful_flops_ratio": model_flops / max(tot["flops"] * n_chips,
                                                    1.0),
        })
    if verbose:
        print(json.dumps(result, indent=1))
        print(f"memory_analysis: {mem}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="phase A only (lower+compile proof)")
    ap.add_argument("--out", default=None, help="append JSONL results here")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--no-swa-pruned", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip pairs already recorded in --out")
    args = ap.parse_args(argv)

    if args.all:
        pairs = [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape
        pairs = [(args.arch, args.shape)]

    if args.resume and args.out and os.path.exists(args.out):
        done = set()
        with open(args.out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("status") in ("OK", "SKIP"):
                    done.add((r["arch"], r["shape"]))
        pairs = [p_ for p_ in pairs if p_ not in done]
        print(f"resume: {len(done)} done, {len(pairs)} remaining", flush=True)

    failures = 0
    for arch, shape in pairs:
        t0 = time.monotonic()
        try:
            r = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                           cost=not args.no_cost, verbose=not args.out,
                           save_hlo=args.save_hlo,
                           swa_pruned=not args.no_swa_pruned)
        except Exception as e:  # dry-run failure == sharding bug in our system
            r = {"arch": arch, "shape": shape, "status": "FAIL",
                 "mesh": "pod2x16x16" if args.multi_pod else "16x16",
                 "error": f"{type(e).__name__}: {e}"}
            failures += 1
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
            print(f"{arch} x {shape} [{r['mesh']}]: {r['status']} "
                  f"({time.monotonic() - t0:.0f}s)", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
