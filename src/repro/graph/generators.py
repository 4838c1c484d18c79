"""Graph generators for the paper's input families (Sec. 4, Table 1).

* ``kronecker``  — Graph500 RMAT generator (the paper's scale-29/EF-8 claim
  uses this family; GAP_kron is the same generator at scale 27).
* ``uniform_random`` — Erdos–Renyi-ish (GAP_urand analogue).
* ``torus_2d`` / ``path_graph`` — large-diameter graphs reproducing the
  Webbase-2001 "no parallelism, synchronization dominates" regime.
* ``star_graph`` — worst-case hub for load-balance tests.

Every family accepts ``max_weight`` (0 = unweighted, the default): weights
are uniform ``uint32`` in ``[1, max_weight]`` drawn from a splitmix64 hash
of the CANONICAL endpoint pair, so ``w(u, v) == w(v, u)`` by construction
and the assignment is stable under the ETL's symmetrize/dedup (GAP
benchmark convention for weighted SSSP inputs; DESIGN.md §14).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from repro.graph import csr

# Graph500 RMAT probabilities.
_A, _B, _C = 0.57, 0.19, 0.19


def edge_weights(
    src: np.ndarray, dst: np.ndarray, max_weight: int, seed: int = 0
) -> np.ndarray:
    """Symmetric per-edge weights in ``[1, max_weight]`` (uint32).

    splitmix64 over the canonical (min, max) endpoint pair mixed with the
    seed — deterministic, order-independent, and identical for both
    directions of an undirected edge.
    """
    if max_weight < 1:
        raise ValueError(f"max_weight must be >= 1, got {max_weight}")
    a = np.minimum(src, dst).astype(np.uint64)
    b = np.maximum(src, dst).astype(np.uint64)
    x = (a << np.uint64(32)) | b
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15) * np.uint64(seed + 1)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(max_weight) + np.uint64(1)).astype(np.uint32)


def _maybe_weights(src, dst, max_weight: int, seed: int):
    if not max_weight:
        return None
    return edge_weights(np.asarray(src), np.asarray(dst), max_weight, seed)


def kronecker(
    scale: int,
    edge_factor: int = 8,
    seed: int = 0,
    *,
    symmetrize: bool = True,
    max_weight: int = 0,
    timings: Optional[Dict[str, float]] = None,
) -> csr.Graph:
    """RMAT/Kronecker generator, vectorized over all edges at once.

    ``timings``, when given, receives the host seconds of each ETL step:
    ``generate`` (edge draw), then :func:`csr.from_edges`' ``from_edges``
    (symmetrize/dedup/CSR) and ``validate``."""
    t0 = time.perf_counter()
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    # the draws of one rng.random(m) per bit, into reused buffers and
    # uint32 accumulators (vertex ids are int32 downstream anyway): no
    # per-bit int64 temporaries, the host ETL's largest step at scale 21+
    src = np.zeros(m, dtype=np.uint32)
    dst = np.zeros(m, dtype=np.uint32)
    r = np.empty(m)
    bits = np.empty(m, dtype=np.uint32)
    for bit in range(scale):
        rng.random(out=r)
        w = np.uint32(1 << bit)
        np.multiply(r >= (_A + _B), w, out=bits)
        src |= bits
        np.multiply(((r >= _A) & (r < _A + _B)) | (r >= (_A + _B + _C)), w,
                    out=bits)
        dst |= bits
    # Graph500 permutes vertex labels to break degree-locality correlation.
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    weights = _maybe_weights(src, dst, max_weight, seed)
    if timings is not None:
        timings["generate"] = time.perf_counter() - t0
    return csr.from_edges(src, dst, n, symmetrize=symmetrize,
                          weights=weights, timings=timings)


def uniform_random(
    n: int, m: int, seed: int = 0, *, max_weight: int = 0
) -> csr.Graph:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    return csr.from_edges(
        src, dst, n, weights=_maybe_weights(src, dst, max_weight, seed)
    )


def torus_2d(side: int, *, max_weight: int = 0, seed: int = 0) -> csr.Graph:
    """side x side wrap-around grid: diameter ~ side (high-diameter regime)."""
    ids = np.arange(side * side, dtype=np.int64).reshape(side, side)
    right = np.roll(ids, -1, axis=1)
    down = np.roll(ids, -1, axis=0)
    src = np.concatenate([ids.ravel(), ids.ravel()])
    dst = np.concatenate([right.ravel(), down.ravel()])
    return csr.from_edges(
        src, dst, side * side,
        weights=_maybe_weights(src, dst, max_weight, seed),
    )


def path_graph(n: int, *, max_weight: int = 0, seed: int = 0) -> csr.Graph:
    """Path: the paper's Webbase 'hundred-vertex tail' pathology, distilled."""
    src = np.arange(n - 1, dtype=np.int64)
    return csr.from_edges(
        src, src + 1, n,
        weights=_maybe_weights(src, src + 1, max_weight, seed),
    )


def star_graph(n: int, *, max_weight: int = 0, seed: int = 0) -> csr.Graph:
    """One hub connected to n-1 leaves (extreme degree skew)."""
    dst = np.arange(1, n, dtype=np.int64)
    src = np.zeros(n - 1, dtype=np.int64)
    return csr.from_edges(
        src, dst, n, weights=_maybe_weights(src, dst, max_weight, seed)
    )
