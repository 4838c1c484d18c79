"""How ``correct`` is decided: the answers of the timed path against the
plain reference, each number beside its limit.

- ``distance_errors``: vertices, summed over every compared ``bfs``
  answer, whose hop distance differs from the reference's.  Exact: limit 0.
- ``closeness_rel_err``: the largest relative gap between a served
  closeness and the reference's, both from exact distances in float64.
  The limit sits between what sound runs read and what the control (a
  traversal cut one level short) reads; PERF.md gives both readings.
- ``answers_missing``: requests whose answer never came, a minute past
  the window's close.  Limit 0.

A run that compared no answer at all is not correct.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from harness import reference

LIMITS = {
    "distance_errors": 0,
    "closeness_rel_err": 1e-9,
    "answers_missing": 0,
}


def _as_request(item):
    """``(algo, root, answer)`` of a kernel-2 ``(root, dist)`` answer or a
    served ``(Request, answer)`` one."""
    key, answer = item
    if isinstance(key, (int, np.integer)):
        return "bfs", int(key), answer
    return key.algo, key.root, answer


def compare(answers: List[Tuple[object, object]], missing: int, adj,
            n_out: int, n_real: int) -> Tuple[Dict[str, dict], int]:
    """``({name: {"value", "limit"}}, answers compared)``; ``adj`` is the
    reference's graph (:func:`reference.adjacency`)."""
    items = [_as_request(a) for a in answers]
    want = reference.distances(adj, {root for _, root, _ in items}, n_out)
    errors = 0
    gap = None
    for algo, root, got in items:
        ref = want[root]
        if algo == "bfs":
            errors += int(np.count_nonzero(
                np.asarray(got, dtype=np.int64) != ref.astype(np.int64)))
        elif algo == "closeness":
            expect = reference.closeness(ref[:n_real], n_real)
            rel = abs(float(got) - expect) / max(abs(expect), 1e-300)
            gap = rel if gap is None else max(gap, rel)
        else:
            raise ValueError(f"no reference for algo {algo!r}")
    values = {"distance_errors": errors, "answers_missing": missing}
    if gap is not None:
        values["closeness_rel_err"] = gap
    return ({k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()},
            len(items))


def correct(checks: Dict[str, dict], compared: int) -> bool:
    return compared > 0 and all(c["value"] <= c["limit"]
                                for c in checks.values())
