"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: each chip's device-op intervals, the benchmark's own host
spans (``bench.*`` annotations), and the program's names (its ``edl.*``
host spans with their args, each op's full instruction text and the
``XLA Modules`` line, which ``bench.scopes`` reads), on the trace's clock.

Device planes are ``/device:TPU:<n>``; their ops are the events of the
``XLA Ops`` line. Busy time is the union of those intervals, so ops that
overlap on one chip count once. Host spans come from
``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` in the
benchmark's step loop and in the program; ``align`` matches the traced
``bench.step`` spans with the loop's own step records to carry host times
onto the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import statistics

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    ops: dict           # chip id -> sorted [(start_ns, end_ns, name)]
    spans: list         # [(name, start_ns, end_ns)] of bench.* host events
    offset_ns: float = 0.0      # trace_ns = host_perf_counter_s*1e9 + offset
    program: object = None      # bench.scopes.Profile of the same trace

    def host_to_trace(self, interval) -> tuple:
        return tuple(t * 1e9 + self.offset_ns for t in interval)

    def step_spans(self) -> list:
        return [s for s in self.spans if s[0] == "bench.step"]


def op_name(text: str) -> str:
    """An XLA op's name without its HLO text ("%fusion.12 = bf16[...] ..."
    -> "fusion.12")."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce_file(path: str, prefix: str = "edl.") -> Reduced:
    """One pass over the trace: the ops by name and the ``bench.*`` spans,
    and as ``program`` the ops' full texts, the module runs and the host
    spans whose names start with ``prefix``."""
    from jax.profiler import ProfileData
    from bench.scopes import Profile, Span
    texts: dict = {}            # one string object for each distinct text
    ops, full, modules = {}, {}, {}
    spans, program = [], []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            ops[chip] = []
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  texts.setdefault(e.name, e.name))
                                 for e in line.events)
                    (full if line.name == OPS_LINE else modules)[chip] = evs
            names: dict = {}
            ops[chip] = [(s, e, names.get(t) or names.setdefault(
                t, op_name(t))) for s, e, t in full.get(chip, [])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, end))
                    elif e.name.startswith(prefix):
                        program.append(Span(e.name, e.start_ns, end,
                                            dict(e.stats), line.name))
    spans.sort(key=lambda s: s[1])
    program.sort(key=lambda s: (s.start, -s.end))
    return Reduced(ops, spans, program=Profile(full, modules, program))


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


def exclusive(ops: list) -> list:
    """[(start, end, text, own_ns, is_leaf)]: each op's time not covered
    by the ops nested in it (a ``while`` contains its body's ops)."""
    out, stack = [], []         # stack: indices into out of open ops
    for s, e, text in sorted(ops, key=lambda o: (o[0], -(o[1] - o[0]))):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(e, parent[1]) - s
            parent[4] = False
        out.append([s, e, text, e - s, True])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def open_span(red: Reduced, t: float) -> str:
    """The innermost of the program's spans open at trace time ``t``, else
    the innermost benchmark span."""
    program = red.program.spans if red.program is not None else []
    for spans in ([(s.name, s.start, s.end) for s in program], red.spans):
        best = None
        for name, s, e in spans:
            if s <= t < e and (best is None or s >= best[1]):
                best = (name, s)
        if best:
            return best[0]
    return "outside any benchmark span"


def breakdown(run, lo: float, hi: float, top: int = 10) -> dict:
    """The ``top`` device ops by their exclusive time (a container's own
    time only), averaged over the chips, and the ``top`` longest idle gaps
    on held chips, each named by the span open in its middle."""
    red = run.reduced
    chips = sorted(red.ops)
    per_op: dict = {}
    for chip in chips:
        clipped = [(max(s, lo), min(e, hi), name)
                   for s, e, name in red.ops[chip] if min(e, hi) > max(s, lo)]
        for _, _, name, own, _ in exclusive(clipped):
            per_op[name] = per_op.get(name, 0.0) + own / 1e9
    n = max(len(chips), 1)
    ops = sorted(((k, v / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = []
    for chip in chips:
        for hs, he in held_intervals(run, chip, lo, hi, red):
            gaps += [(e - s, s, e, chip)
                     for s, e in idle_gaps(red.ops[chip], hs, he)]
    gaps = sorted(gaps, key=lambda g: -g[0])[:top]
    return {"device_ops": [list(x) for x in ops],
            "idle_gaps": [[f"chip {chip}: {open_span(red, (s + e) / 2)}",
                           length / 1e9] for length, s, e, chip in gaps]}
