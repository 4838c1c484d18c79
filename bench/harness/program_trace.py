"""The program's own names in a ``jax.profiler`` trace of a window: the
``repro:<track>/<name>`` spans that :mod:`repro.core.tracing` opens on the
profiler's clock, and the ``traversal.*`` phase scopes
(:func:`repro.core.loop.phase`) that the compiled module carries in each
instruction's ``op_name``.

- :func:`load_spans`: every ``repro:`` span of the trace, with its thread;
- :func:`phase_map`: the phase of each instruction of a compiled module,
  from its HLO text;
- :func:`phase_times`: device time of one module by phase;
- :func:`idle_split` and :func:`gaps`: the device's idle time split by the
  innermost program span open at each instant (a ``bench:`` span only where
  no program span is open), and its longest gaps labelled at their
  midpoint;
- :func:`dispatch_idle`: device-idle time inside each
  ``scheduler/dispatch`` span.

Device operations and ``bench:`` spans come from :func:`harness.trace.load`;
times are in nanoseconds on the trace's clock, results in seconds, and
per-device quantities are means over the devices.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from harness import trace as trace_mod

PREFIX = "repro:"
DISPATCH = "scheduler/dispatch"
UNSCOPED = "unscoped"
NO_SPAN = "no span"

_PHASE = re.compile(r"traversal\.(\w+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


@dataclasses.dataclass(frozen=True)
class ProgramSpan:
    name: str  # "<track>/<name>", without the prefix
    start: float  # ns
    end: float
    thread: str


def load_spans(path: Path) -> List[ProgramSpan]:
    """The ``repro:`` spans of an ``.xplane.pb``, one thread per line of
    each host plane."""
    from jax.profiler import ProfileData

    spans: List[ProgramSpan] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(ProgramSpan(
                        e.name[len(PREFIX):], e.start_ns,
                        e.start_ns + e.duration_ns,
                        f"{plane.name}#{i}:{line.name}"))
    return spans


def _phase_of(op_name: Optional[str]) -> Optional[str]:
    m = _PHASE.search(op_name or "")
    return m.group(0) if m else None


def phase_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: "traversal.<phase>"}`` for every instruction of
    a compiled module's HLO text whose phase can be told: its own
    ``op_name``; else that of the root of the computation it calls (a
    fusion's, say), or the phase most of that computation's instructions
    carry; else, for an instruction the compiler made without a name (the
    zeros a scatter starts from, say), the phase of the first instruction
    that uses it.  Instructions of no phase are left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    roots: Dict[str, Optional[str]] = {}
    members: Dict[str, List[Optional[str]]] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and " = " not in line:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        phase = _phase_of(op.group(1) if op else None)
        own[name] = phase
        members[comp].append(phase)
        if line.lstrip().startswith("ROOT"):
            roots[comp] = phase
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        rhs = line.split("=", 1)[1].split("metadata=", 1)[0]
        for operand in _OPERAND.findall(rhs):
            users.setdefault(operand, []).append(name)

    def direct(name):
        phase = own.get(name)
        if phase is None and name in calls:
            comp = calls[name]
            phase = roots.get(comp)
            if phase is None:
                found = [p for p in members.get(comp, ()) if p]
                phase = max(set(found), key=found.count) if found else None
        return phase

    out: Dict[str, str] = {}
    for name in own:
        phase = direct(name)
        if phase is None:
            phase = next((p for p in map(direct, users.get(name, ())) if p),
                         None)
        if phase is not None:
            out[name] = phase
    return out


def _by_device(ops, lo, hi, module=None):
    out: Dict[str, List[trace_mod.Op]] = {}
    for o in ops:
        if o.end > lo and o.start < hi and (module is None
                                            or o.module == module):
            out.setdefault(o.device, []).append(o)
    return out


def _innermost(items, lo, hi, rank):
    """Split ``[lo, hi)`` at every boundary of ``items`` (each with
    ``.start``/``.end``) and give each piece to the covering item of
    greatest ``rank``: ``[(start, end, item or None)]`` in order."""
    items = sorted(items, key=lambda it: it.start)
    cuts = sorted({lo, hi} | {t for it in items for t in (it.start, it.end)
                              if lo < t < hi})
    pieces, active, k = [], [], 0
    for s, e in zip(cuts, cuts[1:]):
        while k < len(items) and items[k].start <= s:
            active.append(items[k])
            k += 1
        active = [it for it in active if it.end > s]
        pieces.append((s, e, max(active, key=rank) if active else None))
    return pieces


def phase_times(ops: Sequence[trace_mod.Op], module: Optional[str],
                phases: Dict[str, str], lo: float, hi: float
                ) -> Dict[str, float]:
    """Device seconds of ``module`` (every module where None) inside
    ``[lo, hi)`` by phase, mean over devices.  Each instant of the module's
    busy time goes to the innermost operation running then (the latest to
    start; a ``while`` or other container only where nothing inside it
    runs), and to that operation's phase, or :data:`UNSCOPED`.  The phases
    sum to the module's busy time."""
    devices = _by_device(ops, lo, hi, module)
    totals: Dict[str, float] = {}

    def rank(o):
        return (not trace_mod._CONTAINER.match(o.name), o.start)

    for dev_ops in devices.values():
        for s, e, o in _innermost(dev_ops, lo, hi, rank):
            if o is not None:
                key = phases.get(o.name, UNSCOPED)
                totals[key] = totals.get(key, 0.0) + (e - s)
    n = max(len(devices), 1)
    return {k: v * 1e-9 / n for k, v in sorted(totals.items())}


def _idle(ops, lo, hi) -> Dict[str, List[Tuple[float, float]]]:
    """Per device, the idle intervals of ``[lo, hi)``."""
    out = {}
    for device, dev_ops in _by_device(ops, lo, hi).items():
        covered = trace_mod.union([(max(o.start, lo), min(o.end, hi))
                                   for o in dev_ops])
        edges = [lo] + [t for iv in covered for t in iv] + [hi]
        out[device] = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                       if e > s]
    return out


def _label_rank(span):
    # a program span over any bench span, then the latest to start
    return (isinstance(span, ProgramSpan), span.start)


def _name(span) -> str:
    """A program span's ``<track>/<name>``; a bench span keeps its
    ``bench:`` prefix, to tell the two apart."""
    return NO_SPAN if span is None else span.name


def _label_pieces(program_spans, bench_spans, lo, hi):
    spans = list(program_spans) + [s for s in bench_spans
                                   if s.name != trace_mod.WINDOW]
    return _innermost(spans, lo, hi, _label_rank)


def _overlaps(intervals, pieces):
    """``(start, end, item)`` for each overlap of sorted, disjoint
    ``intervals`` with sorted, disjoint ``pieces``."""
    i = j = 0
    while i < len(intervals) and j < len(pieces):
        s, e = intervals[i]
        a, b, item = pieces[j]
        lo, hi = max(s, a), min(e, b)
        if hi > lo:
            yield lo, hi, item
        if e <= b:
            i += 1
        else:
            j += 1


def idle_split(ops, program_spans, bench_spans, lo, hi) -> Dict[str, float]:
    """Device-idle seconds of ``[lo, hi)`` by the innermost span open at
    each instant: a ``repro:`` span (any thread) where one is, else a
    ``bench:`` span, else :data:`NO_SPAN`; mean over devices."""
    pieces = _label_pieces(program_spans, bench_spans, lo, hi)
    idle = _idle(ops, lo, hi)
    totals: Dict[str, float] = {}
    for intervals in idle.values():
        for a, b, sp in _overlaps(intervals, pieces):
            totals[_name(sp)] = totals.get(_name(sp), 0.0) + (b - a)
    n = max(len(idle), 1)
    return {k: v * 1e-9 / n for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])}


def gaps(ops, program_spans, bench_spans, lo, hi, top: int = 10
         ) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps, each labelled with the innermost span
    open at its midpoint, as :func:`idle_split` ranks them."""
    pieces = _label_pieces(program_spans, bench_spans, lo, hi)
    starts = [p[0] for p in pieces]
    found = []
    for intervals in _idle(ops, lo, hi).values():
        for s, e in intervals:
            k = bisect.bisect_right(starts, (s + e) / 2) - 1
            found.append((_name(pieces[k][2]) if k >= 0 else NO_SPAN,
                          (e - s) * 1e-9))
    return sorted(found, key=lambda g: -g[1])[:top]


def dispatch_idle(ops, program_spans, lo, hi) -> List[float]:
    """Device-idle seconds inside each ``scheduler/dispatch`` span that lies
    wholly inside ``[lo, hi)``, mean over devices, in span order."""
    idle = _idle(ops, lo, hi)
    out = []
    for sp in sorted(program_spans, key=lambda s: s.start):
        if sp.name != DISPATCH or sp.start < lo or sp.end > hi:
            continue
        total = sum(b - a for intervals in idle.values()
                    for a, b, _ in _overlaps(intervals,
                                             [(sp.start, sp.end, sp)]))
        out.append(total * 1e-9 / max(len(idle), 1))
    return out


def report(path: Path, module: Optional[str] = None,
           hlo_text: Optional[str] = None, top: int = 10) -> dict:
    """Everything above for one trace file, over its ``bench:window`` (or
    the span of its operations): the module's device time by phase (where
    ``hlo_text`` is given), the idle split and the share of idle time inside
    some program span, the longest gaps, and the dispatches' idle time."""
    ops, bench_spans = trace_mod.load(path)
    program_spans = load_spans(path)
    windows = [s for s in bench_spans if s.name == trace_mod.WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    summary = trace_mod.reduce(ops, bench_spans, module=module, top=top)
    split = idle_split(ops, program_spans, bench_spans, lo, hi)
    idle_s = sum(split.values())
    in_program = sum(v for k, v in split.items()
                     if k != NO_SPAN and not k.startswith(trace_mod.PREFIX))
    out = {
        "window_s": summary.window_s,
        "busy_s": summary.busy_s,
        "module_s": summary.module_s,
        "idle_s": idle_s,
        "idle_in_program_span": in_program / idle_s if idle_s else None,
        "idle_by_span": split,
        "idle_gaps": gaps(ops, program_spans, bench_spans, lo, hi, top),
        "device_ops": summary.device_ops,
        "spans": len(program_spans),
    }
    if hlo_text is not None:
        phases = phase_map(hlo_text)
        out["phase_s"] = phase_times(ops, module, phases, lo, hi)
        out["top_op_phase"] = {n: phases.get(n, UNSCOPED)
                               for n, _ in summary.device_ops}
    per_dispatch = dispatch_idle(ops, program_spans, lo, hi)
    if per_dispatch:
        out["dispatch_idle_s"] = per_dispatch
    return out
