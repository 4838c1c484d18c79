"""Butterfly collectives lowered to ``jax.lax.ppermute`` chains.

These are the JAX realizations of :mod:`repro.core.butterfly` schedules and
must be called *inside* ``jax.shard_map`` (they use named mesh axes).

Three families:

* ``butterfly_merge`` / ``butterfly_or`` / ``butterfly_allreduce`` — the
  paper-faithful pattern: every round ships the FULL buffer to ``digit-1``
  partners and merges (paper Alg. 2 phase 2, generalized merge op).
  Bytes/node = ``sum(d_i - 1) * |buf|``; depth = ``len(digits)`` rounds;
  peak live buffers = ``O(fanout * |buf|)`` (paper Contribution 4).

* ``butterfly_allreduce_rabenseifner`` — beyond-paper: recursive halving
  (reduce-scatter) + recursive doubling (all-gather) on the *same* butterfly
  wiring.  Bytes/node = ``2 * (P-1)/P * |buf|`` — asymptotically ``log(P)``×
  fewer bytes than the full-buffer pattern, at the same depth ``2 log(P)``.

* ``all_to_all_merge`` — the naive baseline the paper replaces: every node
  ships its buffer to all ``P-1`` peers (implemented as ``P-1`` ring shifts).

All support *hierarchical* mesh axes: pass ``axes=("model", "data", "pod")``
to run intra-chip-group digits first so the slowest interconnect carries only
the final round(s) (DESIGN.md Sec. 11).
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import butterfly
from repro.core import frontier as fr
from repro.core import monoid as mono
from repro.core.monoid import Monoid

Axes = Union[str, Sequence[str]]

_MERGE_OPS = {
    "add": lax.add,
    "or": jnp.bitwise_or,
    "and": jnp.bitwise_and,
    "max": lax.max,
    "min": lax.min,
}


def _as_axes(axes: Axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _resolve_op(op: Union[str, Callable]) -> Callable:
    return _MERGE_OPS[op] if isinstance(op, str) else op


# ---------------------------------------------------------------------------
# Paper-faithful full-buffer butterfly (Alg. 2, phase 2)
# ---------------------------------------------------------------------------


def butterfly_merge(
    x: jax.Array,
    axes: Axes,
    *,
    fanout: int = 2,
    op: Union[str, Callable] = "add",
) -> jax.Array:
    """Merge ``x`` across ``axes`` with the paper's butterfly pattern.

    Every participating rank ends with ``op``-reduction of all ranks' inputs
    (op must be associative + commutative).  One ``lax.ppermute`` per partner
    per round; ``sum(d_i - 1)`` messages sent per rank in ``len(digits)``
    rounds per axis.
    """
    merge = _resolve_op(op)
    for axis in _as_axes(axes):
        p = lax.axis_size(axis)
        if p == 1:
            continue
        sched = butterfly.build_schedule(p, fanout)
        for rnd in sched.rounds:
            # All sends of a round ship the same pre-round accumulator
            # (paper: the node's current merged frontier).
            received = [
                lax.ppermute(x, axis, list(enumerate(perm))) for perm in rnd.perms
            ]
            for r in received:
                x = merge(x, r)
    return x


def butterfly_reduce(
    x: jax.Array, axes: Axes, monoid: Monoid, *, fanout: int = 2
) -> jax.Array:
    """All-reduce ``x`` over an explicit :class:`~repro.core.monoid.Monoid`
    with the paper's full-buffer butterfly (DESIGN.md §14).

    Subsumes :func:`butterfly_or` (OR monoid over frontier bitmaps) — the
    same ``ppermute`` wiring carries min-distance relaxation (SSSP) and
    path-count accumulation (betweenness centrality)."""
    return butterfly_merge(x, axes, fanout=fanout, op=monoid.combine)


def butterfly_or(x: jax.Array, axes: Axes, *, fanout: int = 2) -> jax.Array:
    """Bitmap frontier synchronization (BFS phase 2): bitwise-OR merge."""
    return butterfly_reduce(x, axes, mono.OR_U32, fanout=fanout)


def butterfly_allreduce(
    x: jax.Array, axes: Axes, *, fanout: int = 2
) -> jax.Array:
    """Sum all-reduce with the paper-faithful full-buffer butterfly."""
    return butterfly_merge(x, axes, fanout=fanout, op="add")


# ---------------------------------------------------------------------------
# Density-adaptive sparse frontier exchange (DESIGN.md §12)
# ---------------------------------------------------------------------------


def butterfly_reduce_sparse(
    x: jax.Array,
    axes: Axes,
    monoid: Monoid,
    *,
    fanout: int = 2,
    capacity: int = 256,
    ref: jax.Array | None = None,
    fallback: bool = True,
) -> jax.Array:
    """Monoid all-reduce shipping COMPACT ``(word_index, word)`` pairs.

    Same :class:`butterfly.Schedule` wiring as :func:`butterfly_reduce`, but
    each round ppermutes a fixed-capacity compaction of the words CHANGED
    since the last sync (``x != ref``; ``ref`` defaults to the all-identity
    buffer, which for the OR monoid makes "changed" == "nonzero") instead of
    the full buffer, padded with the monoid identity so pads are no-ops on
    the receive side.

    The idempotence/delta dichotomy (DESIGN.md §19, enforced by
    ``monoid.check_sparse_ref``) governs what the wire carries:

    * **Idempotent monoid (remerge mode)** — any replicated-consistent
      ``ref``.  Contract (monotonicity): every rank's input must satisfy
      ``x == combine(x, ref)`` — each change is a combine-IMPROVEMENT over
      the shared reference (BFS frontiers only gain bits over the zero
      reference; SSSP relaxation only lowers distances below the post-last-
      sync buffer).  Unchanged words are not shipped, so a rank holding the
      reference value must already be correct for them — which is exactly
      what monotonicity guarantees.  Re-delivery of a word across rounds
      re-combines harmlessly because ``combine(x, x) == x``.
    * **Non-idempotent monoid (delta mode)** — ``ref`` MUST be ``None``
      (the identity): each rank's input is its own CONTRIBUTION relative to
      the identity (PageRank: this rank's scatter-added rank mass), never a
      buffer containing another rank's values.  Each butterfly round ships
      the pre-round accumulator — a disjoint subcube partial that reaches
      every destination exactly once — so combining is exact without
      idempotence, bit-identical to the dense :func:`butterfly_reduce`
      (identity pads combine as exact no-ops).  A non-identity ``ref``
      would be double-counted on every receive and is rejected with
      :class:`~repro.core.monoid.MonoidContractError`.

    The per-round send capacity multiplies by the round's digit (clamped at
    the dense size): after merging a round the accumulator differs from
    ``ref`` in at most the union of ``prod(digits so far)`` initial changed
    sets, so the INITIAL changed count is the only overflow condition.
    ``fallback=True`` guards exactly that condition with a scalar ``pmax``
    and a ``lax.cond`` to the dense :func:`butterfly_reduce` — truncation
    can never corrupt the result.  ``fallback=False`` skips the guard
    (callers that pre-checked the count, e.g. the adaptive dispatcher, and
    the HLO byte-accounting benchmarks that need a conditional-free
    lowering).

    Wire bytes per message: ``8 * cap_r`` (int32 index + 4-byte word) vs
    the dense ``4 * n_words`` — the paper Sec. 3 byte model's decisive
    lever at low change density: a BFS frontier of a handful of vertices,
    or an SSSP relaxation wave touching a handful of distances.
    """
    monoid.check_sparse_ref(ref)
    axes = _as_axes(axes)
    n_words = x.shape[0]
    if ref is None:
        ref = monoid.full(x.shape, x.dtype)

    def sparse(words):
        cap = capacity
        for axis in axes:
            p = lax.axis_size(axis)
            if p == 1:
                continue
            sched = butterfly.build_schedule(p, fanout)
            for rnd in sched.rounds:
                c = min(cap, n_words)
                idx, vals, _, _ = fr.compact_changed(words, ref, c, monoid)
                for perm in rnd.perms:
                    pairs = list(enumerate(perm))
                    ridx = lax.ppermute(idx, axis, pairs)
                    rvals = lax.ppermute(vals, axis, pairs)
                    words = fr.scatter_combine(words, ridx, rvals, monoid)
                cap *= rnd.digit
        return words

    if not fallback:
        return sparse(x)

    count = fr.changed_count(x, ref)
    for a in axes:
        count = lax.pmax(count, a)
    return lax.cond(
        count <= min(capacity, n_words),
        sparse,
        lambda w: butterfly_reduce(w, axes, monoid, fanout=fanout),
        x,
    )


def butterfly_reduce_adaptive(
    x: jax.Array,
    axes: Axes,
    monoid: Monoid,
    *,
    fanout: int = 2,
    capacity: int = 256,
    density_threshold: float = 0.02,
    ref: jax.Array | None = None,
) -> jax.Array:
    """Per-call dense/sparse dispatch keyed on the CHANGED-WORD density.

    The monoid generalization of :func:`butterfly_or_adaptive` (which keeps
    its bitmap-specific popcount policy): sparse when the busiest rank's
    changed-since-``ref`` word count stays under ``density_threshold`` of
    ``n_words`` AND fits ``capacity`` (the sparse path's no-overflow
    precondition — so the sparse branch needs no inner fallback), dense
    otherwise.  One scalar ``pmax`` rides the wire; both branches live in
    the compiled HLO and ``lax.cond`` picks one per call at run time.

    The §19 idempotence/delta dichotomy applies exactly as in
    :func:`butterfly_reduce_sparse`: non-idempotent monoids require
    ``ref=None`` (delta contributions) and are rejected otherwise.
    """
    monoid.check_sparse_ref(ref)
    axes = _as_axes(axes)
    n_words = x.shape[0]
    cap = min(capacity, n_words)
    # keep the caller's ref (None == delta mode) for the sparse delegate —
    # materializing the identity here would defeat the dichotomy check
    ref_arr = monoid.full(x.shape, x.dtype) if ref is None else ref

    changed = fr.changed_count(x, ref_arr)
    for a in axes:
        changed = lax.pmax(changed, a)
    words_limit = jnp.int32(density_threshold * n_words)
    go_sparse = (changed <= words_limit) & (changed <= cap)
    return lax.cond(
        go_sparse,
        lambda w: butterfly_reduce_sparse(
            w, axes, monoid, fanout=fanout, capacity=cap, ref=ref,
            fallback=False,
        ),
        lambda w: butterfly_reduce(w, axes, monoid, fanout=fanout),
        x,
    )


def butterfly_or_sparse(
    x: jax.Array,
    axes: Axes,
    *,
    fanout: int = 2,
    capacity: int = 256,
    fallback: bool = True,
) -> jax.Array:
    """Bitmap OR-merge shipping compact pairs: the OR-monoid instance of
    :func:`butterfly_reduce_sparse` (reference = all-zeros, so "changed"
    degenerates to "nonzero" and identity padding to zero padding)."""
    return butterfly_reduce_sparse(
        x, axes, mono.OR_U32, fanout=fanout, capacity=capacity,
        fallback=fallback,
    )


def butterfly_or_adaptive(
    x: jax.Array,
    axes: Axes,
    *,
    fanout: int = 2,
    capacity: int = 256,
    density_threshold: float = 0.02,
) -> jax.Array:
    """Per-call dense/sparse dispatch keyed on the frontier's density.

    Inside the jitted BFS level loop this decides EVERY level: sparse when
    the densest rank's popcount stays under ``density_threshold`` of the
    bitmap bits AND its active-word count fits ``capacity`` (the sparse
    path's no-overflow precondition — so the sparse branch needs no inner
    fallback), dense otherwise.  The two scalar ``pmax`` reductions ride the
    wire as a handful of bytes; both branches live in the compiled HLO and
    ``lax.cond`` picks one per level at run time.

    Each branch stages under its own ``jax.named_scope`` (``sparse``,
    ``dense``), so a compiled op's ``op_name`` and a device trace tell
    which branch ran; a scope changes no staged op.
    """
    axes = _as_axes(axes)
    n_words = x.shape[0]
    cap = min(capacity, n_words)

    pops = fr.popcount(x)
    nz = jnp.count_nonzero(x).astype(jnp.int32)
    for a in axes:
        pops = lax.pmax(pops, a)
        nz = lax.pmax(nz, a)
    bits_limit = jnp.int32(density_threshold * n_words * fr.WORD_BITS)
    go_sparse = (pops <= bits_limit) & (nz <= cap)

    def sparse(w):
        with jax.named_scope("sparse"):
            return butterfly_or_sparse(w, axes, fanout=fanout, capacity=cap,
                                       fallback=False)

    def dense(w):
        with jax.named_scope("dense"):
            return butterfly_or(w, axes, fanout=fanout)

    return lax.cond(go_sparse, sparse, dense, x)


# ---------------------------------------------------------------------------
# Beyond-paper: Rabenseifner on the butterfly wiring
# ---------------------------------------------------------------------------


def _global_stages(axes: Tuple[str, ...], fanout: int):
    """Stages (axis, digit, within-axis stride, perms) MSB-first over the
    combined mixed radix where ``axes[0]`` is the least-significant axis."""
    stages = []
    for axis in axes:  # LSB axis first...
        p = lax.axis_size(axis)
        if p == 1:
            continue
        sched = butterfly.build_schedule(p, fanout)  # rounds LSB digit first
        for rnd in sched.rounds:
            stages.append((axis, rnd))
    return stages[::-1]  # ...then reverse the flat list => global MSB first


def butterfly_reduce_scatter(
    x: jax.Array, axes: Axes, *, fanout: int = 2,
    op: Union[str, Callable] = "add",
) -> Tuple[jax.Array, jax.Array]:
    """Recursive-halving reduce-scatter over the butterfly wiring.

    ``x`` is flattened and zero-padded to a multiple of ``P`` (the pad is
    the identity of ``add``/``or``/``max``-on-unsigned).  Returns
    ``(chunk, chunk_index)`` where ``chunk`` is this rank's ``1/P`` slice of
    the reduced buffer and ``chunk_index`` its (traced) position.
    """
    merge = _resolve_op(op)
    axes = _as_axes(axes)
    p_total = 1
    for a in axes:
        p_total *= lax.axis_size(a)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % p_total
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunk_elems = flat.shape[0] // p_total

    stages = _global_stages(axes, fanout)
    lo = jnp.zeros((), jnp.int32)  # chunk-range start (in chunks), traced
    size = p_total  # chunk-range length (in chunks), static
    for axis, rnd in stages:
        d, stride = rnd.digit, rnd.stride
        newsize = size // d
        dig = (lax.axis_index(axis) // stride) % d
        mylo = lo + dig * newsize
        acc = lax.dynamic_slice(flat, (mylo * chunk_elems,), (newsize * chunk_elems,))
        for j, perm in enumerate(rnd.perms, start=1):
            send_lo = lo + ((dig + j) % d) * newsize
            chunk = lax.dynamic_slice(
                flat, (send_lo * chunk_elems,), (newsize * chunk_elems,)
            )
            recv = lax.ppermute(chunk, axis, list(enumerate(perm)))
            acc = merge(acc, recv)
        flat = lax.dynamic_update_slice(flat, acc, (mylo * chunk_elems,))
        lo, size = mylo, newsize
    chunk = lax.dynamic_slice(flat, (lo * chunk_elems,), (chunk_elems,))
    return chunk, lo


def butterfly_allgather_chunks(
    chunk: jax.Array,
    lo: jax.Array,
    total_elems: int,
    axes: Axes,
    *,
    fanout: int = 2,
) -> jax.Array:
    """Recursive-doubling all-gather: inverse of the reduce-scatter above."""
    axes = _as_axes(axes)
    p_total = 1
    for a in axes:
        p_total *= lax.axis_size(a)
    chunk_elems = chunk.shape[0]
    flat = jnp.zeros((p_total * chunk_elems,), chunk.dtype)
    flat = lax.dynamic_update_slice(flat, chunk, (lo * chunk_elems,))

    stages = _global_stages(axes, fanout)[::-1]  # LSB first
    size = 1
    for axis, rnd in stages:
        d, stride = rnd.digit, rnd.stride
        dig = (lax.axis_index(axis) // stride) % d
        base = lo - dig * size
        mine = lax.dynamic_slice(flat, (lo * chunk_elems,), (size * chunk_elems,))
        for j, perm in enumerate(rnd.perms, start=1):
            recv = lax.ppermute(mine, axis, list(enumerate(perm)))
            pdig = (dig - j) % d  # sender's digit
            flat = lax.dynamic_update_slice(
                flat, recv, ((base + pdig * size) * chunk_elems,)
            )
        lo, size = base, size * d
    return flat[:total_elems]


def butterfly_allreduce_rabenseifner(
    x: jax.Array, axes: Axes, *, fanout: int = 2,
    op: Union[str, Callable] = "add",
) -> jax.Array:
    """All-reduce = reduce-scatter + all-gather (bandwidth-optimal):
    ``2·(P-1)/P`` of the buffer per node vs the full-buffer butterfly's
    ``log_f(P)`` — the beyond-paper frontier-sync schedule (§Perf).
    ``op='or'`` gives the BFS bitmap merge."""
    shape, dtype = x.shape, x.dtype
    n = x.size
    chunk, lo = butterfly_reduce_scatter(x, axes, fanout=fanout, op=op)
    p_total = 1
    for a in _as_axes(axes):
        p_total *= lax.axis_size(a)
    padded = n + ((-n) % p_total)
    flat = butterfly_allgather_chunks(chunk, lo, padded, axes, fanout=fanout)
    return flat[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Naive baseline the paper replaces (Sec. 3 "two widely used approaches")
# ---------------------------------------------------------------------------


def all_to_all_merge(
    x: jax.Array,
    axes: Axes,
    *,
    op: Union[str, Callable] = "add",
) -> jax.Array:
    """All-to-all broadcast-merge: ``P-1`` ring shifts per axis, each rank
    ships its ORIGINAL buffer to every peer.  O(P^2) total messages —
    the pattern the butterfly replaces."""
    merge = _resolve_op(op)
    for axis in _as_axes(axes):
        p = lax.axis_size(axis)
        if p == 1:
            continue
        shifted = x
        for _ in range(p - 1):
            perm = [(i, (i + 1) % p) for i in range(p)]
            shifted = lax.ppermute(shifted, axis, perm)
            x = merge(x, shifted)
    return x


def xla_allreduce(x: jax.Array, axes: Axes, *, op: str = "add") -> jax.Array:
    """XLA-native collective (psum / custom) — the compiler-scheduled
    reference point for roofline comparisons."""
    axes = _as_axes(axes)
    if op == "add":
        return lax.psum(x, axes)
    if op == "max":
        return lax.pmax(x, axes)
    if op == "or":
        # XLA has no native bitwise-OR all-reduce: all-gather the words and
        # OR-reduce the gathered axis, one axis at a time.
        out = x
        for a in axes:
            g = lax.all_gather(out, a, axis=0, tiled=False)
            out = jnp.bitwise_or.reduce(g, axis=0)
        return out
    raise ValueError(op)


# ---------------------------------------------------------------------------
# Pytree wrappers (gradient synchronization entry point; DESIGN.md Sec. 7)
# ---------------------------------------------------------------------------


def tree_sync(
    tree,
    axes: Axes,
    *,
    method: str = "xla_psum",
    fanout: int = 2,
    mean: bool = True,
):
    """Synchronize a gradient pytree across data-parallel ``axes``.

    method: ``xla_psum`` | ``butterfly`` (paper) | ``rabenseifner``
    (beyond-paper) | ``all_to_all`` (paper's baseline).
    """
    axes = _as_axes(axes)
    p_total = 1
    for a in axes:
        p_total *= lax.axis_size(a)

    def sync_leaf(g):
        if method == "xla_psum":
            out = lax.psum(g, axes)
        elif method == "butterfly":
            out = butterfly_allreduce(g, axes, fanout=fanout)
        elif method == "rabenseifner":
            out = butterfly_allreduce_rabenseifner(g, axes, fanout=fanout)
        elif method == "all_to_all":
            out = all_to_all_merge(g, axes, op="add")
        else:
            raise ValueError(f"unknown grad-sync method {method!r}")
        return out / p_total if mean else out

    return jax.tree.map(sync_leaf, tree)


def butterfly_allreduce_int8(x: jax.Array, axes: Axes, *, fanout: int = 2) -> jax.Array:
    """Butterfly sum all-reduce with **int8 on the wire every round**.

    Each round the local fp32 accumulator is quantized (per-message scalar
    scale, shipped alongside); receivers dequantize and add.  Wire bytes per
    round ≈ |buf|/4 of the fp32 butterfly.  Quantization error compounds
    over the ``log_f(P)`` rounds — bounded to ``depth × max|g|/127`` per
    element; accuracy is property-tested against the fp32 path.
    """
    acc = x.astype(jnp.float32)
    for axis in _as_axes(axes):
        p = lax.axis_size(axis)
        if p == 1:
            continue
        sched = butterfly.build_schedule(p, fanout)
        for rnd in sched.rounds:
            scale = jnp.maximum(jnp.max(jnp.abs(acc)) / 127.0, 1e-30)
            q = jnp.clip(jnp.round(acc / scale), -127, 127).astype(jnp.int8)
            for perm in rnd.perms:
                pairs = list(enumerate(perm))
                rq = lax.ppermute(q, axis, pairs)
                rs = lax.ppermute(scale, axis, pairs)
                acc = acc + rq.astype(jnp.float32) * rs
    return acc


def tree_sync_int8(
    tree,
    axes: Axes,
    *,
    method: str = "butterfly",
    fanout: int = 2,
    mean: bool = True,
):
    """Gradient sync with int8 wire compression (DESIGN.md §7)."""
    axes = _as_axes(axes)
    p_total = 1
    for a in axes:
        p_total *= lax.axis_size(a)

    def sync_leaf(g):
        out = butterfly_allreduce_int8(g, axes, fanout=fanout)
        return ((out / p_total) if mean else out).astype(g.dtype)

    return jax.tree.map(sync_leaf, tree)
