"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: each chip's device-op intervals, and the benchmark's own host
spans (``bench.*`` annotations), on the trace's clock.

Device planes are ``/device:TPU:<n>``; their ops are the events of the
``XLA Ops`` line. Busy time is the union of those intervals, so ops that
overlap on one chip count once. Host spans come from
``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` in the
benchmark's step loop; ``align`` matches the traced ``bench.step`` spans with
the loop's own step records to carry host times onto the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    ops: dict           # chip id -> sorted [(start_ns, end_ns, name)]
    spans: list         # [(name, start_ns, end_ns)] of bench.* host events
    offset_ns: float = 0.0      # trace_ns = host_perf_counter_s*1e9 + offset

    def host_to_trace(self, interval) -> tuple:
        return tuple(t * 1e9 + self.offset_ns for t in interval)

    def step_spans(self) -> list:
        return [s for s in self.spans if s[0] == "bench.step"]


def op_name(text: str) -> str:
    """An XLA op's name without its HLO text ("%fusion.12 = bf16[...] ..."
    -> "fusion.12")."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict = {}
    spans = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)))
            ops[chip] = sorted(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s[1])
    return Reduced(ops, spans)


def reduce_dir(directory: str) -> Reduced:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(max(paths, key=os.path.getmtime))


def align(red: Reduced, step_starts: list) -> Reduced:
    """Set the host-to-trace offset from the traced ``bench.step`` spans,
    matched in order with the loop's step start times (perf_counter
    seconds)."""
    spans = red.step_spans()
    n = min(len(spans), len(step_starts))
    if n == 0:
        raise ValueError("the trace holds no bench.step span")
    red.offset_ns = statistics.median(
        spans[i][1] - step_starts[i] * 1e9 for i in range(n))
    return red


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in intervals:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """[(start, end)] of the stretches in [lo, hi) with no op running."""
    gaps, t = [], lo
    for s, e, *_ in intervals:
        if e <= t:
            continue
        if s >= hi:
            break
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def held_intervals(run, chip: int, lo: float, hi: float, red: Reduced):
    """[(start, end)] trace-ns stretches of [lo, hi) in which the tenant
    held ``chip`` (a chip that release_devices gave back is not held until
    grant_devices returns it)."""
    marks = [(red.host_to_trace((t,))[0], chip in ids) for t, ids in run.held]
    out = []
    state = marks[0][1] if marks else True
    cursor = lo
    for t, holds in marks:
        if t <= lo:
            state = holds
            continue
        if t >= hi:
            break
        if holds != state:
            if state:
                out.append((cursor, t))
            cursor, state = t, holds
    if state:
        out.append((cursor, hi))
    return out


def busy_and_window(run, lo: float, hi: float) -> tuple:
    """(busy_s, window_s) averaged over the cell's chips: busy is the union
    of device ops while the chip is held, window the time it is held."""
    red = run.reduced
    busy, window, n = 0.0, 0.0, 0
    for chip, ops in red.ops.items():
        for s, e in held_intervals(run, chip, lo, hi, red):
            busy += union(ops, s, e)
            window += e - s
        n += 1
    n = max(n, 1)
    return busy / n / 1e9, window / n / 1e9


def open_span(red: Reduced, t: float) -> str:
    """The innermost bench span open at trace time ``t``."""
    best = None
    for name, s, e in red.spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside any benchmark span"


def breakdown(run, lo: float, hi: float, top: int = 10) -> dict:
    red = run.reduced
    chips = sorted(red.ops)
    per_op: dict = {}
    for chip in chips:
        for s, e, name in red.ops[chip]:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
    n = max(len(chips), 1)
    ops = sorted(((k, v / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = []
    for chip in chips:
        for hs, he in held_intervals(run, chip, lo, hi, red):
            for s, e in idle_gaps(red.ops[chip], hs, he):
                gaps.append((f"chip {chip}: {open_span(red, (s + e) / 2)}",
                             (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [list(x) for x in ops],
            "idle_gaps": [list(x) for x in gaps[:top]]}


