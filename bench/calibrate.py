"""Readings that the limits of ``bench/limits/<workload>.json`` are set from,
on the chip at the cell's own size: for each seed, the program's numbers
against the reference; for the control seeds also the control's (the
reference in float8 in the program's place) and the faults' (planted in the
reference in the program's place: half of the batch left out, the mean
over the rest; on several chips also the gradient exchange left out, each
chip's update from its own rows alone). A step that returns its state
unchanged reads 1 on change_gap by definition and needs no run.

    python bench/calibrate.py --workload <name> --seeds 11,12,... \
        --control-seeds 11,12,13 [--out calib.jsonl]

One JSON line per seed and kind; the benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import check, harness
    bench = harness.load_benchmark()
    cell, config, traffic = harness.cell_parts(bench, args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(f"calib {line}", flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in (int(s) for s in args.seeds.split(",")):
        got = []
        t0 = time.perf_counter()
        harness.run_cell(args.workload, seed, 0, False, t_start=t0,
                         bench=bench, on_checked=got.append)
        driver = got[0]
        run = dict(config=config, traffic=traffic, seed=seed,
                   rows=driver.rows, check_ids=driver.check_ids,
                   devices=driver.devices)
        ref = check.reference_run(**run)
        t_ref = time.perf_counter()
        emit({"seed": seed, "kind": "program",
              **check.gaps(driver.prog, ref),
              "program_losses": driver.prog["losses"],
              "reference_losses": ref["losses"]})
        if seed not in controls:
            continue
        ctrl = check.reference_run(**run, ein="fp8")
        emit({"seed": seed, "kind": "control_fp8", **check.gaps(ctrl, ref),
              "reference_s": t_ref - t0})
        b = traffic["global_batch"]
        half = check.reference_run(**run, loss_rows=b // 2)
        emit({"seed": seed, "kind": "fault_half_batch",
              **check.gaps(half, ref)})
        if cell["chips"] > 1:
            p0 = traffic["start"][0]
            alone = check.reference_run(**run, loss_rows=b // p0)
            emit({"seed": seed, "kind": "fault_no_exchange",
                  **check.gaps(alone, ref)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
