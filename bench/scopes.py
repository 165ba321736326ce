"""The program's own names in a profiler trace (``.xplane.pb``): its host
spans (``edl.*`` annotations, with their args) and the named scope of each
device op, and the per-layer readings made from them.

Scope names are ``SCOPES`` (the model's ``attention``, ``mlp``,
``head_loss`` and the step's ``optimizer``), each op's innermost one; a
configuration file may name more under ``"scopes"`` (``extra_scopes``),
each read apart as the time of the ops whose path names it anywhere, so
that an extra name takes nothing from ``SCOPES`` or from another; a
metric's reader then reads ``run.scopes[<name>]``. Scopes come from the
compiled modules' HLO text
(``compiled.as_text()``), whose ``op_name`` metadata holds the scope path
of each instruction, e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/mul``.
The trace names an op by its instruction only (``%fusion.12 = ...``), and
several modules may share a name (one step program per mesh shape, each
``jit_train_step``). So each op is looked up in the module that the chip's
``XLA Modules`` line shows running at the op's start, and each module run
(``jit_train_step(<program id>)``) is matched to the HLO text that holds
its ops' instruction texts.

Device ops nest: a ``while`` event contains its body's ops. Time is
counted exclusively (``bench.trace.exclusive``): an op with no op inside
it (a leaf) gives its time to its scope, or to ``unscoped``; an op with
ops inside it (a container) gives only the time none of them covers, to
``containers``. Per chip these add up to the union of the ops' intervals,
the chip's busy time; an extra scope's leaf time lies inside it.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

from bench.trace import exclusive, union

SCOPES = ("attention", "mlp", "head_loss", "optimizer")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT )?(%\S+ = .*)$")
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")
_SCOPE_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


@dataclasses.dataclass
class Span:
    name: str
    start: float            # trace ns
    end: float
    args: dict
    thread: str


@dataclasses.dataclass
class Profile:
    ops: dict               # chip -> sorted [(start, end, instruction text)]
    modules: dict           # chip -> sorted [(start, end, module run name)]
    spans: list             # [Span] of the program's host annotations


def read(path: str, prefix: str = "edl.") -> Profile:
    """The device ops (full instruction text), module runs and the host
    spans whose names start with ``prefix`` of one ``.xplane.pb``."""
    from bench.trace import reduce_file
    return reduce_file(path, prefix).program


def extra_scopes(config: dict) -> tuple:
    """The scopes that a configuration file names under ``"scopes"``
    beyond ``SCOPES``, once each, in its order."""
    extra = config.get("scopes", [])
    if not isinstance(extra, list) or not all(
            isinstance(n, str) and _SCOPE_NAME.match(n) for n in extra):
        raise ValueError(f"configuration {config.get('name')!r} names "
                         f"scopes that are no names: {extra!r}")
    return tuple(n for n in dict.fromkeys(extra) if n not in SCOPES)


# ------------------------------------------------------------ scopes
def path_parts(path: str) -> list:
    """The parts of an ``op_name`` path, each unwrapped from the
    transformations around it (``jvp(head_loss)``,
    ``transpose(jvp(attention))``)."""
    out = []
    for part in path.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.append(part)
    return out


def scope_of(path: str) -> str | None:
    """The innermost of ``SCOPES`` in an ``op_name`` path."""
    found = None
    for part in path_parts(path):
        if part in SCOPES:
            found = part
    return found


def op_key(text: str) -> tuple:
    """(name, result type, opcode) of an instruction's text. The trace
    prints an op with its operands' types and without metadata, the HLO
    text the other way round; both agree up to the opcode."""
    name, _, rest = text.partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return name, rest[:i], rest[i + 1:].split("(", 1)[0]
    return name, rest, ""


def op_paths(hlo_text: str) -> dict:
    """``op_key`` -> ``op_name`` path (None without one), for every
    instruction of one module's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(m.group(1))
        out[op_key(m.group(1))] = meta.group(1) if meta else None
    return out


def instructions(hlo_text: str) -> dict:
    """``op_key`` -> scope among ``SCOPES``, for every instruction of one
    module's HLO text."""
    return {k: scope_of(p) if p else None
            for k, p in op_paths(hlo_text).items()}


class Module:
    """One compiled module's instructions, looked up by a trace op's
    text: ``table`` holds each one's scope among ``SCOPES``, ``extra``
    the names of ``extra`` in its path (where there are any)."""

    def __init__(self, hlo_text: str, extra: tuple = ()):
        paths = op_paths(hlo_text)
        self.table = {k: scope_of(p) if p else None
                      for k, p in paths.items()}
        self.extra = {}
        for k, p in paths.items():
            parts = set(path_parts(p)) if p and extra else ()
            named = tuple(n for n in extra if n in parts)
            if named:
                self.extra[k] = named

    def find(self, text: str):
        """(found, scope) of the op named ``text``."""
        key = op_key(text)
        return key in self.table, self.table.get(key)

    def extras(self, text: str) -> tuple:
        """The extra scopes in the path of the op named ``text``."""
        return self.extra.get(op_key(text), ())


def _run_at(runs: list, starts: list, t: float):
    """The module run name open at ``t`` on a chip, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return runs[i][2] if i >= 0 and t < runs[i][1] else None


def match_modules(prof: Profile, hlo_texts: list, extra: tuple = ()) -> dict:
    """Module run name -> the ``Module`` of the HLO text that holds the
    most of the ops run under that name (None where no text holds any)."""
    modules = [Module(t, extra) for t in hlo_texts]
    seen: dict = {}
    for chip, runs in prof.modules.items():
        starts = [r[0] for r in runs]
        for s, _, text in prof.ops.get(chip, []):
            name = _run_at(runs, starts, s)
            if name is not None:
                seen.setdefault(name, set()).add(text)
    out = {}
    for name, texts in seen.items():
        best, score = None, 0
        for mod in modules:
            n = sum(mod.find(t)[0] for t in texts)
            if n > score:
                best, score = mod, n
        out[name] = best
    return out


def scope_times(prof: Profile, hlo_texts: list, lo: float, hi: float,
                extra: tuple = ()) -> dict:
    """Device seconds of the ops within [lo, hi), averaged over the chips:
    the leaf ops of each of ``SCOPES``, ``unscoped`` leaf ops (and those
    of a module run that no HLO text matches), ``containers``' own time,
    and ``busy``, the union of the ops, which these add up to; and apart,
    for each name of ``extra``, the leaf ops whose path names it."""
    modules = match_modules(prof, hlo_texts, extra)
    total = dict.fromkeys(SCOPES + ("unscoped", "containers", "busy")
                          + extra, 0.0)
    for chip, ops in prof.ops.items():
        ops = [o for o in ops if o[0] >= lo and o[1] <= hi]
        runs = prof.modules.get(chip, [])
        starts = [r[0] for r in runs]
        for s, _, text, own, leaf in exclusive(ops):
            if not leaf:
                total["containers"] += own
                continue
            mod = modules.get(_run_at(runs, starts, s))
            scope = mod.find(text)[1] if mod is not None else None
            total[scope or "unscoped"] += own
            for name in mod.extras(text) if mod is not None else ():
                total[name] += own
        total["busy"] += union(ops, lo, hi)
    n = max(len(prof.ops), 1)
    return {k: v / n / 1e9 for k, v in total.items()}


# ------------------------------------------------------------ spans
def step_host_ms(spans: list, lo: float, hi: float) -> float | None:
    """Mean over the ``edl.step`` spans in [lo, hi) of their host time: the
    span less the time ``edl.step.wait`` and ``edl.adjust.*`` spans cover
    inside it."""
    steps = [s for s in spans if s.name == "edl.step"
             and s.start >= lo and s.end <= hi]
    if not steps:
        return None
    out = []
    for st in steps:
        inner = [(sp.start, sp.end) for sp in spans
                 if sp.thread == st.thread and sp.start >= st.start
                 and sp.end <= st.end
                 and (sp.name == "edl.step.wait"
                      or sp.name.startswith("edl.adjust."))]
        out.append((st.end - st.start
                    - union(sorted(inner), st.start, st.end)) / 1e6)
    return sum(out) / len(out)


def adjustments(spans: list) -> dict:
    """adj -> {span name: [Span]} of the ``edl.adjust.*`` spans."""
    out: dict = {}
    for sp in spans:
        if sp.name.startswith("edl.adjust.") and "adj" in sp.args:
            out.setdefault(sp.args["adj"], {}).setdefault(
                sp.name, []).append(sp)
    return out


def move_ms(by_name: dict, waits: list = ()) -> float | None:
    """One adjustment's move as far as it holds up training: from its
    start (that of ``staged_reshard``, else of ``move``), or from the end
    of the last ``edl.step.wait`` of ``waits`` before ``ready`` where that
    is later, to the end of ``ready``. A staged move is issued behind the
    draining step and queues behind it on the device; that step's wait
    ends when the device has finished it, so the step's time is left
    out."""
    start = by_name.get("edl.adjust.staged_reshard") or \
        by_name.get("edl.adjust.move")
    ready = by_name.get("edl.adjust.ready")
    if not start or not ready:
        return None
    t0 = start[0].start
    for w in waits:
        if w.thread == ready[-1].thread and t0 < w.end <= ready[-1].start:
            t0 = w.end
    return (ready[-1].end - t0) / 1e6


def adjust_move_ms(spans: list, lo: float, hi: float) -> float | None:
    """Mean ``move_ms`` over the adjustments whose spans all lie in
    [lo, hi)."""
    waits = [s for s in spans if s.name == "edl.step.wait"]
    got = []
    for by_name in adjustments(spans).values():
        ms = move_ms(by_name, waits)
        first = min(s.start for v in by_name.values() for s in v)
        if ms is not None and first >= lo and \
                max(s.end for v in by_name.values() for s in v) <= hi:
            got.append(ms)
    return sum(got) / len(got) if got else None
