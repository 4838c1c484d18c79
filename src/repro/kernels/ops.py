"""jit'd wrappers over the Pallas kernels + the BFS-facing expansion ops.

``interpret`` is an explicit argument of every wrapper: ``True`` runs the
kernel body in the Pallas interpreter (same semantics, same BlockSpec
tiling, any backend).  Nothing picks it from the backend.  The TPU
compiler refuses the gather and scatter kernels as written
(``PALLAS_REFUSED``), so only tests run them, interpreted.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.kernels import bitmap_merge as _bm
from repro.kernels import frontier_gather as _fg
from repro.kernels import frontier_scatter as _fs


# What the TPU compiler says about the Pallas frontier kernels (compiled for
# a described v5e).  Until a rewrite lands they run under the interpreter.
PALLAS_REFUSED = (
    "frontier_gather's ww-word window block is not a multiple of the "
    "128-word tile; frontier_gather_full and frontier_scatter use (1, eb) "
    "edge blocks (the last two block dims must divide by (8, 128) or equal "
    "the array's); with (8, eb) blocks the gathers' vector-indexed load "
    "words_ref[s >> 5] fails ('Cannot do int indexing on TPU')"
)


def bitmap_or_reduce(stack: jax.Array, *, block: int = 1024,
                     interpret: bool) -> jax.Array:
    w = stack.shape[-1]
    block = min(block, w)
    while w % block:
        block //= 2
    return _bm.bitmap_or_reduce(stack, block=max(block, 1), interpret=interpret)


def frontier_gather(words, block_ws, src_local, *, ww, interpret: bool):
    return _fg.frontier_gather(words, block_ws, src_local, ww=ww, interpret=interpret)


def frontier_gather_full(words, src, *, interpret: bool):
    return _fg.frontier_gather_full(words, src, interpret=interpret)


def frontier_scatter(active, block_win, block_first, dst_local, *, n_windows,
                     ww, interpret: bool):
    return _fs.frontier_scatter(
        active,
        block_win,
        block_first,
        dst_local,
        n_windows=n_windows,
        ww=ww,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# BFS-facing expansion ops (consume the blocks.py layouts)
# ---------------------------------------------------------------------------


def _pad_words(words: jax.Array, words_pad: int) -> jax.Array:
    w = words.shape[0]
    if w == words_pad:
        return words
    if w > words_pad:
        return words[:words_pad]
    return jnp.concatenate([words, jnp.zeros((words_pad - w,), words.dtype)])


def expand_push_pallas(
    frontier_words: jax.Array, arrays: Dict, meta: Dict, n_words: int, *,
    interpret: bool,
) -> jax.Array:
    """Top-down expansion via gather + scatter kernels."""
    if meta["gather_full"]:
        active = frontier_gather_full(
            _pad_words(frontier_words, meta["gather_words_pad"]),
            arrays["tdg_src"], interpret=interpret,
        )
    else:
        active = frontier_gather(
            _pad_words(frontier_words, meta["gather_words_pad"]),
            arrays["tdg_ws"],
            arrays["tdg_src"],
            ww=meta["gather_ww"],
            interpret=interpret,
        )
    act_blocked = active.reshape(-1)[arrays["tds_perm"]]
    out = frontier_scatter(
        act_blocked,
        arrays["tds_win"],
        arrays["tds_first"],
        arrays["tds_dst"],
        n_windows=meta["scatter_windows"],
        ww=meta["scatter_ww"],
        interpret=interpret,
    )
    return out[:n_words]


def expand_pull_pallas(
    frontier_words: jax.Array,
    visited_words: jax.Array,
    arrays: Dict,
    meta: Dict,
    n_words: int,
    *,
    interpret: bool,
) -> jax.Array:
    """Bottom-up expansion: parent probe (full gather on unsorted in_src) +
    unvisited mask (windowed gather on sorted in_dst) + windowed scatter."""
    parent = frontier_gather_full(
        _pad_words(frontier_words, meta["gather_words_pad"]),
        arrays["in_src_blocks"], interpret=interpret,
    )
    if meta["pull_gather_full"]:
        vis = frontier_gather_full(
            _pad_words(visited_words, meta["pull_gather_words_pad"]),
            arrays["pug_dst"], interpret=interpret,
        )
    else:
        vis = frontier_gather(
            _pad_words(visited_words, meta["pull_gather_words_pad"]),
            arrays["pug_ws"],
            arrays["pug_dst"],
            ww=meta["pull_gather_ww"],
            interpret=interpret,
        )
    # both are in-edge flat order; lengths may differ by block padding, and
    # every real edge index < count <= min length.
    m = min(parent.size, vis.size)
    found = parent.reshape(-1)[:m] & (~vis.reshape(-1)[:m])
    act_blocked = found[arrays["pus_perm"]]
    out = frontier_scatter(
        act_blocked,
        arrays["pus_win"],
        arrays["pus_first"],
        arrays["pus_dst"],
        n_windows=meta["scatter_windows"],
        ww=meta["scatter_ww"],
        interpret=interpret,
    )
    return out[:n_words]
