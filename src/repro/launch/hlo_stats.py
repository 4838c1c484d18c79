"""Roofline-term extraction from compiled XLA artifacts.

``cost_analysis()`` gives per-device HLO_FLOPs / bytes-accessed;
collective bytes are NOT in cost_analysis, so we parse the optimized HLO
(``compiled.as_text()``) and sum operand sizes of every collective op
(brief: ROOFLINE ANALYSIS).  We additionally estimate *wire* bytes per
device from the replica-group size (ring all-gather moves (P-1)/P of the
full buffer per device, etc.) — both are recorded.

Hardware constants: TPU v5e per chip — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (per the brief).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link (we assume one busy link per phase)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(%[\w.\-]+)\s*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_DOT_RE = re.compile(
    # operands may carry an inline `f32[..]{..}` type prefix (XLA version
    # dependent) — skip it and capture the operand names.
    r"=\s*[a-z0-9]+\[([0-9,]*)\][^ ]*\s+dot\("
    r"(?:[a-z0-9]+\[[0-9,]*\]\{[^}]*\}\s+)?(%[\w.\-]+), "
    r"(?:[a-z0-9]+\[[0-9,]*\]\{[^}]*\}\s+)?(%[\w.\-]+)\)"
)
_LHS_C_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def dot_flops(hlo_text: str) -> float:
    """MXU flops per device: 2 · |result| · |contraction| summed over every
    ``dot`` in the optimized HLO.

    Why not ``cost_analysis()['flops']``: on the CPU backend XLA inserts
    bf16→f32 converts (no native bf16 dot) that HloCostAnalysis counts as
    flops — for decode steps those cache-sized converts dominate the count
    by 60× (measured; DESIGN.md §10).  TPU has native bf16 MXU dots, so the
    dot-only number is the hardware-meaningful compute term.  Operand
    shapes come from a name→shape symbol table over the module text.
    """
    shapes = {}
    total = 0.0
    for line in hlo_text.splitlines():
        md = _DEF_RE.match(line)
        if md:
            dims = [int(x) for x in md.group(3).split(",") if x]
            shapes[md.group(1)] = dims
        m = _DOT_RE.search(line)
        if not m:
            continue
        result = [int(x) for x in m.group(1).split(",") if x]
        lhs = shapes.get(m.group(2))
        mc = _LHS_C_RE.search(line)
        if lhs is None or mc is None:
            continue
        cdims = [int(x) for x in mc.group(1).split(",") if x]
        contraction = _prod([lhs[i] for i in cdims if i < len(lhs)])
        total += 2.0 * _prod(result) * contraction
    return total


_PAIRS_RE = re.compile(r"source_target_pairs=\{\{([0-9]+),")


def _empty_stats() -> Dict[str, Dict[str, float]]:
    return {
        k: {"count": 0, "operand_bytes": 0.0, "wire_bytes": 0.0}
        for k in _COLLECTIVES
    }


def _accumulate_lines(lines) -> Dict[str, Dict[str, float]]:
    stats = _empty_stats()
    for line in lines:
        parsed = _collective_line_stats(line)
        if parsed is None:
            continue
        kind, obytes, wire = parsed
        stats[kind]["count"] += 1
        stats[kind]["operand_bytes"] += obytes
        stats[kind]["wire_bytes"] += wire
    return stats


def _collective_line_stats(line: str):
    """Parse one HLO line; returns ``(kind, operand_bytes, wire_bytes)`` for
    collective ops, else None.  Shared by the whole-module and
    per-computation accounting."""
    m = _OP_RE.search(line)
    if not m:
        return None
    kind = m.group(1)
    if f"{kind}-done(" in line:  # async pair: count only the -start
        return None
    rm = _SHAPE_RE.search(line)
    if not rm:
        return None
    rbytes = _shape_bytes(rm.group(1), rm.group(2))
    gm = _GROUPS_RE.search(line)
    gsize = len(gm.group(1).split(",")) if gm else 1
    gsize = max(gsize, 1)
    if kind == "all-gather":
        obytes = rbytes / gsize
        full = float(rbytes)
    elif kind == "reduce-scatter":
        obytes = rbytes * gsize
        full = float(obytes)
    else:
        obytes = float(rbytes)
        full = float(rbytes)
    if kind == "all-reduce":
        wire = 2.0 * full * (gsize - 1) / gsize
    elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
        wire = full * (gsize - 1) / gsize
    else:  # collective-permute: operand goes out once
        wire = float(obytes)
    return kind, float(obytes), wire


def collective_stats(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per collective-op-kind: count, operand bytes, wire-bytes estimate.

    Operands appear as bare names in optimized HLO, so operand sizes are
    derived from the RESULT shape + replica-group size using each op's
    semantics (all-gather result = operand × P, reduce-scatter result =
    operand / P, all-reduce / permute / all-to-all result = operand).
    Wire bytes use the standard ring/bidirectional estimates per device:
    all-reduce 2·N·(P-1)/P, all-gather & reduce-scatter N·(P-1)/P of the
    FULL buffer, all-to-all N·(P-1)/P, permute N.
    """
    return _accumulate_lines(hlo_text.splitlines())


# ---------------------------------------------------------------------------
# Branch-attributed collective accounting (for adaptive/conditional programs)
# ---------------------------------------------------------------------------

_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")
_CALLS_RE = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _segment_computations(hlo_text: str):
    """Split HLO module text into named computations.

    Returns ``(lines_by_comp, callees_by_comp)`` where callees include
    computations referenced via ``calls=`` / ``body=`` / ``condition=`` /
    ``to_apply=`` / ``branch_computations=`` (for transitive aggregation).
    """
    lines: Dict[str, list] = {}
    callees: Dict[str, list] = {}
    current = None
    for line in hlo_text.splitlines():
        hm = _COMP_HEADER_RE.match(line)
        if hm:
            current = hm.group(1)
            lines.setdefault(current, [])
            callees.setdefault(current, [])
            continue
        if current is None:
            continue
        if line.startswith("}"):
            current = None
            continue
        lines[current].append(line)
        callees[current].extend(_CALLS_RE.findall(line))
        bm = _BRANCHES_RE.search(line)
        if bm:
            callees[current].extend(
                n.strip().lstrip("%") for n in bm.group(1).split(",")
            )
    return lines, callees


def computation_collective_stats(
    hlo_text: str, *, transitive: bool = True
) -> Dict[str, Dict[str, Dict[str, float]]]:
    """Per-HLO-computation collective stats (same shape as
    :func:`collective_stats`).  With ``transitive=True`` each computation
    also absorbs the stats of everything it calls — so a ``lax.cond``
    branch's total includes collectives hidden in fusions/loops it invokes.
    """
    lines, callees = _segment_computations(hlo_text)
    direct = {name: _accumulate_lines(ls) for name, ls in lines.items()}
    if not transitive:
        return direct

    memo: Dict[str, Dict] = {}

    def total(name: str, seen) -> Dict:
        if name in memo:
            return memo[name]
        if name in seen or name not in direct:
            return _empty_stats()
        seen = seen | {name}
        agg = {k: dict(v) for k, v in direct[name].items()}
        for callee in callees.get(name, []):
            sub = total(callee, seen)
            for k in _COLLECTIVES:
                for field in ("count", "operand_bytes", "wire_bytes"):
                    agg[k][field] += sub[k][field]
        memo[name] = agg
        return agg

    return {name: total(name, frozenset()) for name in direct}


def level_body_collective_stats(hlo_text: str):
    """Collective stats of each innermost ``while`` body of the compiled
    program, with everything it calls: ``[(computation_name, stats)]``.

    A level loop whose steps come in variants (``loop.traced_while`` with
    ``pick``) compiles one inner loop per variant, each with its own copy
    of the level's collectives; a level runs exactly one of them, so one
    level's bytes are one innermost body's, not the module's total."""
    comp_stats = computation_collective_stats(hlo_text)
    _, callees = _segment_computations(hlo_text)
    bodies = set(re.findall(r" while\(.*?body=%([\w.\-]+)", hlo_text))

    def reaches(name, seen=frozenset()):
        for callee in callees.get(name, []):
            if callee in bodies or (callee not in seen
                                    and reaches(callee, seen | {name})):
                return True
        return False

    return [(b, comp_stats[b]) for b in sorted(bodies) if not reaches(b)]


def conditional_branch_stats(hlo_text: str):
    """Collective stats per ``lax.cond`` branch of the compiled program.

    ``collective_stats`` sums BOTH branches of a conditional — static HLO
    has no notion of which branch runs — which misreports adaptive
    collectives.  This walks every ``conditional(...)`` op and returns, in
    program order, a list of per-branch stats lists: one entry per
    conditional, each a list (branch order preserved: branch 0 = the
    ``lax.cond`` False path) of ``(computation_name, stats)`` tuples.
    """
    comp_stats = computation_collective_stats(hlo_text)
    out = []
    for line in hlo_text.splitlines():
        if " conditional(" not in line and "conditional-start" not in line:
            continue
        bm = _BRANCHES_RE.search(line)
        if not bm:
            continue
        names = [n.strip().lstrip("%") for n in bm.group(1).split(",")]
        for n in names:
            if n not in comp_stats:
                raise ValueError(
                    f"conditional references computation {n!r} that the "
                    "parser did not segment — HLO header format change?"
                )
        out.append([(n, comp_stats[n]) for n in names])
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_operand_bytes: float
    collective_wire_bytes: float
    t_compute: float
    t_memory: float
    t_collective: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step-time estimate: max of the three terms (they can
        overlap on TPU: MXU vs HBM DMA vs ICI)."""
        return max(self.t_compute, self.t_memory, self.t_collective)


def roofline_from(compiled, hlo_text: Optional[str] = None) -> Roofline:
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jaxlib: one dict per device
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    cs = collective_stats(text)
    op_b = sum(v["operand_bytes"] for v in cs.values())
    wire_b = sum(v["wire_bytes"] for v in cs.values())
    return Roofline(
        flops_per_device=flops,
        bytes_per_device=byt,
        collective_operand_bytes=op_b,
        collective_wire_bytes=wire_b,
        t_compute=flops / PEAK_FLOPS,
        t_memory=byt / HBM_BW,
        t_collective=wire_b / ICI_BW,
    )


def memory_stats(compiled) -> Dict[str, float]:
    ms = compiled.memory_analysis()
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        out[k] = float(getattr(ms, k, 0) or 0)
    out["peak_bytes_per_device"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"]
        - out["alias_size_in_bytes"]
    )
    return out
