"""The run of one cell: what every cell shares, whatever its traffic.

A cell of ``BENCHMARK.json`` names a configuration (a file of sizes under
``bench/configs/``), a traffic mix (``bench/traffic/<name>.json``, data
only) and its chips. The mix's ``kind`` names its driver,
``bench/traffic/<kind>.py``, the general generator for every mix of that
kind, with one interface (``Driver`` there):

  Driver(cell, config, mix, seed, devices, run)
           set-up: validate the mix against the driver's ``KEYS``, build
           the program and every shape the mix will use, weights from
           ``--seed``;
  .warm()  drive the first steps that the check follows through the
           window's own path;
  .step() -> Step
           one step of the window, with whatever the mix requests before
           it (``Adjustment`` records go to ``run.adjustments``);
  .in_flight
           work begun that the window sees through before it closes;
  .annotate
           set while the profiler records: wrap work in ``bench.*`` spans;
  .free()  drop every device buffer and program the driver holds; with
           ``--trace 1`` first put the HLO text (``as_text()``) of each
           step program into ``run.hlo_texts``, where the named scopes
           are looked up;
  .check() -> {"correct": bool, "numbers": {name: {value, limit}}}
           after ``free``: the comparison with the plain reference.

The harness's own mix keys are ``kind`` and ``trace_steps`` (with
``--trace 1``, the profiler records the window's first ``trace_steps``
steps; 0 records all of it). A key that neither the harness nor the driver
knows is refused.

One run, in order: set-up (the driver's build and ``warm``), the window of
``--seconds`` (closing at the first step boundary with nothing in flight),
``free``, the reduction of the trace, and ``check``.

With ``--trace 1`` the reduction fills ``run.reduced`` (device ops and the
benchmark's spans), ``run.spans`` (the program's ``edl.*`` host spans with
their args) and ``run.scopes`` (device seconds of each named scope over
the traced steps, exclusive and averaged over the chips, with
``unscoped``, ``containers`` and ``busy``, and apart each scope that the
configuration names: ``bench.scopes.scope_times``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import re
import shutil
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
REQUIRED = object()         # a driver's KEYS default for a key a mix must set
HARNESS_KEYS = {"kind": REQUIRED, "trace_steps": 0}
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Step:
    t0: float           # host seconds (perf_counter) at the call
    t1: float           # after the step returned its loss to the host
    shape: tuple        # (p, mp) the step ran on
    tokens: int


@dataclasses.dataclass
class Adjustment:
    op: str
    t_request: float
    target: tuple
    t_commit: float | None = None   # end of the step that switched shapes
    t_done: float | None = None     # end of the first step on the new shape

    @property
    def seconds(self):
        return None if self.t_done is None else self.t_done - self.t_request


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    trace: bool
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)
    steps: list = dataclasses.field(default_factory=list)
    adjustments: list = dataclasses.field(default_factory=list)
    held: list = dataclasses.field(default_factory=list)  # (t, chip ids)
    flops_per_step: float = 0.0
    peak_flops: float = 0.0
    reduced: object = None          # bench.trace.Reduced (--trace 1)
    traced: tuple = (0.0, 0.0)      # host seconds covered by the trace
    hlo_texts: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)  # scopes.Span
    scopes: dict = dataclasses.field(default_factory=dict)  # name -> s


# ------------------------------------------------------------------ specs
def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_parts(bench: dict, workload: str, root: Path = ROOT):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


def driver_module(traffic: dict):
    """The driver of the mix's ``kind``: ``bench/traffic/<kind>.py``."""
    kind = traffic.get("kind")
    if not isinstance(kind, str) or not _NAME.match(kind) or \
            not (BENCH / "traffic" / f"{kind}.py").exists():
        raise ValueError(f"traffic kind {kind!r} has no driver "
                         f"bench/traffic/<kind>.py")
    return importlib.import_module(f"bench.traffic.{kind}")


def mix_with_defaults(traffic: dict, keys: dict) -> dict:
    """The mix with the driver's and the harness's defaults filled in. A key
    that neither knows is refused, as is a required key (default
    ``REQUIRED``) that the mix leaves out."""
    known = {**HARNESS_KEYS, **keys}
    unknown = sorted(set(traffic) - set(known))
    if unknown:
        raise ValueError(f"traffic kind {traffic.get('kind')!r} has no "
                         f"keys {unknown}")
    out = dict(known, **traffic)
    missing = sorted(k for k, v in out.items() if v is REQUIRED)
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    return out


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    the fixed ``.jax_cache/`` of this checkout. Its key holds the programs'
    metadata: an executable that another commit cached would otherwise come
    back with that commit's op names, and the named scopes with them."""
    import jax
    from repro.launch.devices import enable_compile_cache as enable
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return enable(str(ROOT / ".jax_cache"))


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits are loads, not
    compilations) from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.listening = True

        def on_duration(event, secs, **_):
            if self.listening and event == \
                    "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_):
            if self.listening and event == \
                    "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def backend_compiles(self) -> int:
        """Backend compiles that were not persistent-cache loads."""
        return self.compiles - self.cache_hits


class GcPauses:
    """Seconds that Python's garbage collector held the host, per run of
    it, while ``on``: a host stall in the window is then either the
    collector's or not."""

    def __init__(self):
        self.on = False
        self.pauses: list = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self):
        self.on = False
        gc.callbacks.remove(self._cb)


def log(what: str, **fields):
    print(f"bench {what} {json.dumps(fields)}", flush=True)


# ------------------------------------------------------------------- the run
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_tpu: bool = True,
             bench: dict | None = None, parts=None,
             on_checked=None) -> dict | None:
    """One run of one cell; returns the result object (the last line).
    ``require_tpu=False`` and ``parts`` (the cell, configuration and
    traffic as dicts) let the tests drive a run at a small size on the
    CPU. ``on_checked`` (calibration) skips the window: once the driver is
    warm and freed, it is called with the driver in place of the check, and
    the run returns None."""
    import jax
    from bench import peaks, scopes

    bench = bench if bench is not None else load_benchmark(root)
    cell, config, traffic = parts or cell_parts(bench, workload, root)
    scopes.extra_scopes(config)         # a scope that is no name fails here
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform})")
    if len(devices) < cell["chips"]:
        raise NoChip(f"{workload} needs {cell['chips']} chips, JAX has "
                     f"{len(devices)}")
    devices = devices[:cell["chips"]]
    peak = (peaks.peaks_for(devices[0].device_kind).flops_bf16
            if require_tpu else 0.0)
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    mod = driver_module(traffic)
    traffic = mix_with_defaults(traffic, mod.KEYS)
    run = Run(cell, config, traffic, seed, trace, peak_flops=peak)

    # ---- set-up
    t_build = time.perf_counter()
    driver = mod.Driver(cell, config, traffic, seed, devices, run)
    t_warm = time.perf_counter()
    driver.warm()
    t_ready = time.perf_counter()
    compiles_setup = counter.backend_compiles()
    log("setup", cache_dir=cache_dir, imports_s=t_build - t_start,
        build_s=t_warm - t_build, warm_s=t_ready - t_warm,
        compiles=compiles_setup)
    if on_checked is not None:
        counter.listening = False
        free(driver)
        on_checked(driver)
        return None

    # ---- window
    tracer = Tracer() if trace else None
    if tracer is not None:
        tracer.start()
        driver.annotate = True
    n_trace = traffic["trace_steps"]
    pauses = GcPauses()
    counter.compiles = counter.cache_hits = 0
    t0 = time.perf_counter()
    run.setup_s = t0 - t_start
    pauses.on = True
    steps = []
    while True:
        if time.perf_counter() - t0 >= seconds and not driver.in_flight:
            break
        st = driver.step()
        steps.append(st)
        if tracer is not None and tracer.on and n_trace and \
                len(steps) >= n_trace:
            run.traced = (t0, st.t1)
            tracer.stop()
            driver.annotate = False
    pauses.close()
    t1 = steps[-1].t1
    if tracer is not None and tracer.on:
        run.traced = (t0, t1)
        tracer.stop()
        driver.annotate = False
    run.window = (t0, t1)
    run.steps = steps
    compiles_window = counter.backend_compiles()
    counter.listening = False
    log("window", **window_log(run, pauses.pauses),
        compiles_in_setup=compiles_setup, compiles_in_window=compiles_window,
        cache_hits_in_window=counter.cache_hits)

    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log("memory", peak_bytes_in_use=[s.get("peak_bytes_in_use")
                                     for s in stats])

    # ---- free the program, reduce the trace, then the reference
    free(driver)
    if tracer is not None:
        reduce_trace(run, tracer)
    checks = driver.check()
    return assemble(run, bench, checks, peak_bytes, devices)


def reduce_trace(run: Run, tracer):
    """Fill ``run.reduced``, ``run.spans`` and ``run.scopes`` from the
    trace, then remove it."""
    from bench import scopes, trace
    traced = [st.t0 for st in run.steps
              if run.traced[0] <= st.t0 <= run.traced[1]]
    red = trace.align(tracer.reduce(), traced)
    tracer.cleanup()
    run.reduced = red
    run.spans = red.program.spans
    lo, hi = red.host_to_trace(run.traced)
    run.scopes = scopes.scope_times(red.program, run.hlo_texts, lo, hi,
                                    scopes.extra_scopes(run.config))
    log("scopes", steps=len(traced), hlo_texts=len(run.hlo_texts),
        **run.scopes)
    run.hlo_texts = []


def free(driver):
    """Drop the program's buffers and executables, so that the reference
    runs on chips the program no longer fills."""
    import jax
    driver.free()
    gc.collect()
    jax.clear_caches()


def window_log(run: Run, gc_pauses: list) -> dict:
    """What the window's log line says of the steps: the median, the three
    longest with their place in the window, and the host time spent
    outside any step call."""
    t0, t1 = run.window
    steps = run.steps
    ms = [(st.t1 - st.t0) * 1e3 for st in steps]
    order = sorted(range(len(ms)), key=ms.__getitem__)
    outside = (t1 - t0) - sum(st.t1 - st.t0 for st in steps)
    return {"seconds": t1 - t0, "steps": len(steps),
            "step_ms_median": ms[order[len(ms) // 2]],
            "step_ms_longest": [[i, steps[i].t0 - t0, ms[i]]
                                for i in order[-3:]],
            "outside_steps_s": outside,
            "gc_runs": len(gc_pauses), "gc_s": sum(gc_pauses),
            "adjustments": [(a.op, a.seconds) for a in run.adjustments]}


class Tracer:
    """The profiler over the window, into a temporary directory that is
    removed once the trace is reduced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.on = True

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        self.on = False

    def reduce(self):
        from bench import trace
        return trace.reduce_dir(self.dir)

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


def assemble(run: Run, bench: dict, checks, peak_bytes, devices) -> dict:
    cell = run.cell["name"]
    group = bench["per_layer"] if run.trace else bench["end_to_end"]
    metrics = {}
    for m in group:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = metric_reader(m["name"])(run)
        if value is None:
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": checks["correct"],
           "attempted": len(run.steps) + len(run.adjustments),
           "failed": sum(a.t_done is None for a in run.adjustments),
           "metrics": metrics, "device": device}
    if run.trace and run.reduced is not None:
        from bench import trace
        lo, hi = run.reduced.host_to_trace(run.traced)
        busy, window = trace.busy_and_window(run, lo, hi)
        device["busy_s"] = busy
        device["window_s"] = window
        out["breakdown"] = trace.breakdown(run, lo, hi)
    out["checks"] = checks["numbers"]
    return out
