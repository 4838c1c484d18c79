"""From one ``jax.profiler`` trace of the window to device metrics.

The run wraps its window in the host span ``bench:window`` and its own
host steps (dispatch, device wait, copy-back, assembly, client waits) in
``bench:<step>`` spans (:class:`Spans`).  :func:`load` reads the trace's
device operations and those spans; :func:`reduce` computes, per device and
averaged over the devices:

- busy time: the union of the intervals in which an operation ran;
- collective time: the same union over the collective operations
  (``collective-permute``, ``all-reduce``, ``all-gather``, ``all-to-all``,
  or the JAX names ``ppermute``, ``psum``, ...), classified by op name
  until the program names its phases;
- the busy time of one compiled program (by HLO module name);

and the ``breakdown``: the operations that took most time (control flow
such as ``while``, whose time is its body's, left out), and the longest
idle gaps, each labelled with the innermost ``bench:`` span the host was
in at the gap's midpoint.

Device operations come from the ``XLA Ops`` line of each ``/device:*``
plane (a TPU); where a trace has no device plane (the CPU backend), from
the host events that carry an ``hlo_op`` stat, one device per
``device_ordinal``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

PREFIX = "bench:"
WINDOW = PREFIX + "window"
# HLO opcodes, and the JAX primitive names XLA may give the instructions
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|ragged-all-to-all|collective-permute"
    r"|collective-broadcast|reduce-scatter|send|recv"
    r"|ppermute|psum|pmax|pmin|all_gather|all_to_all|reduce_scatter)")
_SUFFIX = re.compile(r"\(\d+\)$")
# control flow whose device time is that of the ops it runs: kept out of
# the ranking of ops, which would count that time twice
_CONTAINER = re.compile(r"^(while|conditional|call)\b")


@dataclasses.dataclass(frozen=True)
class Op:
    device: str
    name: str
    module: str
    start: float  # ns
    end: float


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Summary:
    devices: int
    window_s: float
    busy_s: float  # mean over devices
    collective_s: float
    module_s: float  # busy time of the named program, or of all when None
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0


class Spans:
    """``bench:<name>`` host spans in the profiler's trace; no-ops when
    the run is not traced."""

    def __init__(self, enabled: bool):
        self.enabled = enabled

    def __call__(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(PREFIX + name)


@contextlib.contextmanager
def recording(log_dir: Path):
    """Profile the device and the host's annotations (no Python tracer)
    into ``log_dir``."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(event) -> dict:
    with warnings.catch_warnings():
        # the binding's stats type lacks __module__, which Python 3.12 warns of
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in event.stats}


def module_name(name: str) -> str:
    """``jit_body(12)`` -> ``jit_body``: the HLO module a trace names."""
    return _SUFFIX.sub("", name)


def load(path: Path) -> Tuple[List[Op], List[HostSpan]]:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    ops: List[Op] = []
    host_lines = []
    for plane in prof.planes:
        if not plane.name.startswith("/device:"):
            host_lines.extend(plane.lines)
            continue
        lines = {line.name: line for line in plane.lines}
        modules = []
        if "XLA Modules" in lines:
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              module_name(e.name))
                             for e in lines["XLA Modules"].events)
        if "XLA Ops" not in lines:
            continue
        for e in lines["XLA Ops"].events:
            mod = _stats(e).get("hlo_module") or _containing(modules,
                                                             e.start_ns)
            # a TPU trace names an op by its HLO text: "%fusion.3 = ..."
            ops.append(Op(plane.name, e.name.lstrip("%").split(" = ", 1)[0],
                          module_name(str(mod or "")), e.start_ns,
                          e.start_ns + e.duration_ns))
    on_host = not ops  # the CPU backend: its ops are host events
    spans: List[HostSpan] = []
    for line in host_lines:
        for e in line.events:
            if e.name.startswith(PREFIX):
                spans.append(HostSpan(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
            elif on_host and e.duration_ns > 0:
                st = _stats(e)
                if "hlo_op" in st:
                    ops.append(Op(f"cpu:{st.get('device_ordinal', 0)}",
                                  str(st["hlo_op"]),
                                  module_name(str(st.get("hlo_module", ""))),
                                  e.start_ns, e.start_ns + e.duration_ns))
    return ops, spans


def _containing(modules, t) -> Optional[str]:
    for start, end, name in modules:
        if start <= t < end:
            return name
        if start > t:
            break
    return None


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covering exactly what the inputs cover."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(op: Op, lo: float, hi: float) -> Tuple[float, float]:
    return max(op.start, lo), min(op.end, hi)


def _label(spans: List[HostSpan], t: float) -> str:
    """The innermost (latest-starting) bench span the host was in at t."""
    inside = [s for s in spans
              if s.name != WINDOW and s.start <= t < s.end]
    if not inside:
        return "no bench span"
    return max(inside, key=lambda s: s.start).name[len(PREFIX):]


def reduce(ops: List[Op], spans: List[HostSpan], *,
           module: Optional[str] = None, top: int = 10) -> Summary:
    """Device metrics of the traced window (the ``bench:window`` span, or
    the span of the operations where the run did not mark it)."""
    windows = [s for s in spans if s.name == WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    elif ops:
        lo, hi = min(o.start for o in ops), max(o.end for o in ops)
    else:
        raise ValueError("the trace holds no device operation and no window")
    by_device: Dict[str, List[Op]] = {}
    for o in ops:
        if o.end > lo and o.start < hi:
            by_device.setdefault(o.device, []).append(o)
    if not by_device:
        raise ValueError("no device operation ran inside the traced window")
    has_module = module is not None and any(o.module for o in ops)
    busy = collective = in_module = 0.0
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []  # (length, midpoint)
    for device in sorted(by_device):
        dev_ops = by_device[device]
        covered = union([_clip(o, lo, hi) for o in dev_ops])
        busy += _length(covered)
        collective += _length(union([_clip(o, lo, hi) for o in dev_ops
                                     if _COLLECTIVE.match(o.name)]))
        in_module += _length(union([_clip(o, lo, hi) for o in dev_ops
                                    if not has_module or o.module == module]))
        for o in dev_ops:
            if not _CONTAINER.match(o.name):
                s, e = _clip(o, lo, hi)
                op_time[o.name] = op_time.get(o.name, 0.0) + (e - s)
        edges = [lo] + [t for iv in covered for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    n = len(by_device)
    gaps.sort(key=lambda g: -g[0])
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        devices=n,
        window_s=(hi - lo) * 1e-9,
        busy_s=busy / n * 1e-9,
        collective_s=collective / n * 1e-9,
        module_s=in_module / n * 1e-9,
        device_ops=[(name, t * 1e-9 / n) for name, t in ranked],
        idle_gaps=[(_label(spans, mid), t * 1e-9) for t, mid in gaps[:top]],
    )
