"""Packed-bitmap frontier representation (DESIGN.md Sec. 3).

The paper's per-node vertex queues become packed uint32 bitmaps: the global
queue is ``uint32[n_words]`` covering every vertex; merge == bitwise OR
(idempotent — replaces the paper's atomic enqueue-if-new); the wire format
of the butterfly exchange is the bitmap itself.

Two packings share these primitives (DESIGN.md §3/§13):

* **vertex-packed** (single-source BFS): bit ``v & 31`` of word ``v >> 5``
  is vertex ``v`` — one bitmap covers all vertices.
* **lane-packed** (multi-source BFS): row ``v`` of ``uint32[n, B/32]`` is
  vertex ``v``; bit ``b & 31`` of lane-word ``b >> 5`` is search lane ``b``
  — one row holds the lane mask of every concurrent search at ``v``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

WORD_BITS = 32
_U32 = jnp.uint32


def lane_pack(bits: jax.Array) -> jax.Array:
    """bool[..., k*32] -> uint32[..., k]: pack the LAST axis, bit ``b & 31``
    of word ``b >> 5`` <- position ``b`` (the lane-mask wire layout)."""
    nb = bits.shape[-1]
    assert nb % WORD_BITS == 0, nb
    lanes = bits.reshape(*bits.shape[:-1], nb // WORD_BITS, WORD_BITS)
    weights = (jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=_U32)).astype(_U32)
    return (lanes.astype(_U32) * weights).sum(axis=-1, dtype=_U32)


def lane_unpack(words: jax.Array) -> jax.Array:
    """uint32[..., k] -> bool[..., k*32]: inverse of :func:`lane_pack`."""
    shifts = jnp.arange(WORD_BITS, dtype=_U32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * WORD_BITS).astype(
        jnp.bool_
    )


def pack(bits: jax.Array) -> jax.Array:
    """bool[n] -> uint32[n/32] (n must be a multiple of 32)."""
    assert bits.ndim == 1
    return lane_pack(bits)


def unpack(words: jax.Array) -> jax.Array:
    """uint32[w] -> bool[w*32]."""
    assert words.ndim == 1
    return lane_unpack(words)


def get_bits(words: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather single bits at vertex ids ``idx`` -> bool[...]."""
    idx = idx.astype(jnp.uint32)
    w = words[(idx >> 5).astype(jnp.int32)]
    return ((w >> (idx & jnp.uint32(31))) & jnp.uint32(1)).astype(jnp.bool_)


def set_bit(words: jax.Array, idx) -> jax.Array:
    """Set a single bit (used for root seeding)."""
    idx = jnp.asarray(idx, jnp.uint32)
    word = (idx >> 5).astype(jnp.int32)
    mask = (jnp.uint32(1) << (idx & jnp.uint32(31))).astype(_U32)
    return words.at[word].set(words[word] | mask)


def popcount(words: jax.Array) -> jax.Array:
    """Total set bits (int32)."""
    return lax.population_count(words).astype(jnp.int32).sum()


def popcount_lanes(words: jax.Array) -> jax.Array:
    """Per-lane set bits of a lane-packed buffer.

    ``uint32[..., k] -> int32[k*32]``: entry ``b`` counts, over every leading
    position (vertex row), how often lane bit ``b`` is set — i.e. per-search
    frontier/visited sizes of a multi-source wave.
    """
    bits = lane_unpack(words)
    return bits.reshape(-1, bits.shape[-1]).sum(axis=0, dtype=jnp.int32)


def compact_words(words: jax.Array, capacity: int):
    """Fixed-capacity sparse view of a bitmap: the first ``capacity`` active
    ``(word_index, word)`` pairs (size-bounded nonzero), in ascending index
    order — the wire format of the sparse butterfly exchange.

    Returns ``(idx int32[capacity], vals uint32[capacity], count int32,
    overflow bool)``.  Padding slots are ``(0, 0)``; a scatter-OR of a zero
    word is a no-op, so neither ``count`` nor ``overflow`` needs to travel
    on the wire — they exist for the density-adaptive dispatch and the
    overflow→dense fallback.  When ``count > capacity`` the tail words are
    silently truncated; callers MUST consult ``overflow`` (or pre-check the
    count) before trusting the pairs.

    The OR-monoid special case of :func:`compact_changed` (reference =
    all-zeros, identity padding = 0).
    """
    count = jnp.count_nonzero(words).astype(jnp.int32)
    (idx,) = jnp.nonzero(words, size=capacity, fill_value=0)
    idx = idx.astype(jnp.int32)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    vals = jnp.where(slot < count, words[idx], jnp.uint32(0))
    return idx, vals, count, count > capacity


def changed_count(words: jax.Array, ref: jax.Array) -> jax.Array:
    """Words differing from the reference buffer (int32 scalar) — the sparse
    exchange's overflow / density statistic, generalized from popcount-of-
    nonzero to changed-since-last-sync (DESIGN.md §14)."""
    return jnp.count_nonzero(words != ref).astype(jnp.int32)


def compact_changed(words: jax.Array, ref: jax.Array, capacity: int, monoid):
    """Monoid generalization of :func:`compact_words`: the first
    ``capacity`` words DIFFERING from ``ref`` (the post-last-sync buffer,
    replicated-consistent across ranks), padded with the monoid identity.

    Padding slots are ``(0, identity)`` — combining the identity into any
    word is a no-op, so the pairs travel without a count, exactly like the
    OR path's ``(0, 0)`` pads.  Returns ``(idx, vals, count, overflow)``
    with the same truncation contract as :func:`compact_words`.
    """
    diff = words != ref
    count = jnp.count_nonzero(diff).astype(jnp.int32)
    (idx,) = jnp.nonzero(diff, size=capacity, fill_value=0)
    idx = idx.astype(jnp.int32)
    slot = jnp.arange(capacity, dtype=jnp.int32)
    vals = jnp.where(slot < count, words[idx], monoid.identity_like(words))
    return idx, vals, count, count > capacity


def scatter_combine(words: jax.Array, idx: jax.Array, vals: jax.Array, monoid):
    """Monoid generalization of :func:`scatter_or_words` (the receive side
    of the sparse exchange): combine the compact ``(idx, vals)`` pairs into
    ``words``.  Duplicate indices combine through the monoid's scatter op;
    identity pads are no-ops."""
    expanded = monoid.scatter_into(
        monoid.full(words.shape, words.dtype), idx, vals
    )
    return monoid.combine(words, expanded)


def expand_words(n_words: int, idx: jax.Array, vals: jax.Array) -> jax.Array:
    """Inverse of :func:`compact_words`: scatter the pairs into an empty
    bitmap.  Scatter-max == scatter-OR here because real indices are unique
    within one compaction and padding values are 0."""
    return jnp.zeros((n_words,), _U32).at[idx].max(vals.astype(_U32))


def scatter_or_words(words: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    """OR compact ``(idx, vals)`` pairs into an existing bitmap (the receive
    side of the sparse exchange)."""
    return words | expand_words(words.shape[0], idx, vals)


def scatter_or_lanes(n_rows: int, idx: jax.Array, masks: jax.Array) -> jax.Array:
    """Build a lane-packed buffer ``uint32[n_rows, k]`` by OR-ing lane mask
    ``masks[i]`` into row ``idx[i]`` (duplicates OR together; out-of-range
    rows are dropped).  The multi-source analogue of :func:`scatter_or`:
    scatter-max over unpacked lane bits == scatter-OR on the packed words.
    """
    dense = jnp.zeros((n_rows, masks.shape[-1] * WORD_BITS), jnp.bool_)
    dense = dense.at[idx].max(lane_unpack(masks), mode="drop")
    return lane_pack(dense)


#: entries of one int32 scatter target: the TPU compiler sorts the indices
#: of a scatter into a larger target (and into any sub-word type) first,
#: which would put a sort in the level loop
SCATTER_CHUNK = 1 << 21


def scatter_int32(size: int, idx: jax.Array, vals, op: str) -> jax.Array:
    """``int32[size]`` zeros with ``vals`` combined in at ``idx`` by ``op``
    (``"add"`` or ``"max"``); indices outside ``[0, size)`` are dropped.
    One scatter per :data:`SCATTER_CHUNK` entries of the target, so no
    sort is compiled whatever ``size``."""
    parts = []
    for lo in range(0, size, SCATTER_CHUNK):
        width = min(SCATTER_CHUNK, size - lo)
        at = jnp.where((idx >= lo) & (idx < lo + width), idx - lo, width)
        ref = jnp.zeros((width,), jnp.int32).at[at]
        parts.append(getattr(ref, op)(vals, mode="drop"))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def scatter_or(n_words: int, idx: jax.Array, active: jax.Array) -> jax.Array:
    """Build a bitmap with bits ``idx[i]`` set where ``active[i]``.

    XLA path: scatter-max into a dense byte vector, then pack.  The Pallas
    kernel (kernels/frontier_scatter) replaces this on TPU.
    """
    dense = jnp.zeros((n_words * WORD_BITS,), jnp.bool_)
    dense = dense.at[idx].max(active, mode="drop")
    return pack(dense)


_SCAN_WIDTH = 128  # entries per row of the prefix count: one MXU tile


def prefix_count(bits: jax.Array) -> jax.Array:
    """``bool[n] -> int32[n]``: inclusive prefix count of the set entries.

    Each row of 128 entries is scanned by a matmul with an upper-triangular
    matrix of ones, and each row is offset by the prefix sum of the row
    totals before it, recursively.  The matmuls take bfloat16 operands of
    at most 8 bits (larger totals go byte by byte) and accumulate in
    float32, so every partial sum is an integer below 2**24 and exact.
    XLA's cumsum lowers on TPU to a reduce-window scan: with it the
    scale-21 single-source program took 58 s to compile for a TPU v5e,
    with this form 6 s.
    """
    return _prefix_sum(bits.astype(jnp.int32), 1)


def _prefix_sum(values: jax.Array, bound: int) -> jax.Array:
    """Inclusive prefix sum of ``int32[n]`` values in ``[0, bound]``, for
    sums below 2**31 (so the row totals' bound is capped there)."""
    n = values.shape[0]
    rows = -(-n // _SCAN_WIDTH)
    x = jnp.pad(values, (0, rows * _SCAN_WIDTH - n)).reshape(rows, _SCAN_WIDTH)
    ones = jnp.triu(jnp.ones((_SCAN_WIDTH, _SCAN_WIDTH), jnp.bfloat16))
    within = jnp.zeros(x.shape, jnp.int32)
    for shift in range(0, bound.bit_length(), 8):
        digit = ((x >> shift) & 255).astype(jnp.bfloat16)
        part = jnp.dot(digit, ones, preferred_element_type=jnp.float32)
        within += part.astype(jnp.int32) << shift
    if rows > 1:
        totals = within[:, -1]
        before = _prefix_sum(
            totals, min(bound * min(n, _SCAN_WIDTH), 2**31 - 1)) - totals
        within += before[:, None]
    return within.reshape(-1)[:n]


def segment_or(n_words: int, offsets: jax.Array, active: jax.Array,
               word_start) -> jax.Array:
    """Build a bitmap with bit ``32 * word_start + v`` set where any of
    ``active[offsets[v]:offsets[v + 1]]`` is set.

    The scatter-free form of :func:`scatter_or` for indices sorted into
    runs, one per vertex of an aligned window (``offsets`` is
    ``int32[k * 32 + 1]``, non-decreasing): one prefix count over
    ``active``, one gather at the sorted run ends, and the window's packed
    words written in place.  Entries past ``offsets[-1]`` are never read.
    """
    count = prefix_count(active)
    count = jnp.concatenate([jnp.zeros((1,), jnp.int32), count])
    at = count[offsets]
    window = pack(at[1:] > at[:-1])
    return lax.dynamic_update_slice(
        jnp.zeros((n_words,), _U32), window, (word_start,))
