"""Graph500 kernel-2 input: the Kronecker edge list, drawn from the seed.

The draw is the one ``repro.graph.generators.kronecker`` makes (uint32
accumulators, one ``rng.random`` per bit, then Graph500's vertex-label
permutation), kept here so that the reference builds its graph from the
same raw edges as the program and from nothing the program made.  The
program's ETL (``csr.from_edges``: symmetrize, drop self-loops, dedup) is
part of the system under test and runs on these edges.
"""

from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, seed: int, a: float,
                    b: float, c: float):
    """``(src, dst, n)``: ``edge_factor * 2**scale`` directed edges of the
    Kronecker generator with initiator probabilities ``a``, ``b``, ``c``
    (Graph500: 0.57, 0.19, 0.19), labels permuted."""
    n = 1 << scale
    m = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(m, dtype=np.uint32)
    dst = np.zeros(m, dtype=np.uint32)
    r = np.empty(m)
    bits = np.empty(m, dtype=np.uint32)
    for bit in range(scale):
        rng.random(out=r)
        w = np.uint32(1 << bit)
        np.multiply(r >= (a + b), w, out=bits)
        src |= bits
        np.multiply(((r >= a) & (r < a + b)) | (r >= (a + b + c)), w,
                    out=bits)
        dst |= bits
    perm = rng.permutation(n)
    return perm[src], perm[dst], n
