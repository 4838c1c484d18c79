"""Pallas kernels: frontier bit-gather (top-down phase-1 'is src active?').

Two variants, both grid-parallel over fixed-size edge blocks (the TPU
adaptation of the paper's LRB load balancing — every launch does identical
work; DESIGN.md Sec. 3):

* ``frontier_gather``  — *windowed*: edges are sorted by source, so each
  block's sources span a small contiguous window of the frontier bitmap.
  A scalar-prefetched per-block window index drives the BlockSpec, so only
  ``ww`` words of the bitmap are DMA'd into VMEM per block.
* ``frontier_gather_full`` — the whole bitmap resides in VMEM (valid when
  ``W*4 <= VMEM``); used by the bottom-up pull whose in-edge sources are
  unsorted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _windowed_kernel(bws_ref, words_ref, src_ref, out_ref):
    s = src_ref[0]
    w = words_ref[s >> 5]
    bit = (w >> (s.astype(jnp.uint32) & jnp.uint32(31))) & jnp.uint32(1)
    out_ref[0] = bit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("ww", "interpret"))
def frontier_gather(
    words: jax.Array,
    block_ws: jax.Array,
    src_local: jax.Array,
    *,
    ww: int,
    interpret: bool,
) -> jax.Array:
    """Gather frontier bits for edges blocked by source window.

    words:     uint32[W]        (W % ww == 0)
    block_ws:  int32[NB]        per-block window index (units of ``ww`` words)
    src_local: int32[NB, EB]    bit offset of each edge's src inside its window
    returns    bool[NB, EB]
    """
    w = words.shape[0]
    nb, eb = src_local.shape
    assert w % ww == 0, (w, ww)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((ww,), lambda i, bws: (bws[i],)),
            pl.BlockSpec((1, eb), lambda i, bws: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, eb), lambda i, bws: (i, 0)),
    )
    out = pl.pallas_call(
        _windowed_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, eb), jnp.int32),
        interpret=interpret,
    )(block_ws, words, src_local)
    return out.astype(jnp.bool_)


def _full_kernel(words_ref, src_ref, out_ref):
    s = src_ref[0]
    w = words_ref[s >> 5]
    bit = (w >> (s.astype(jnp.uint32) & jnp.uint32(31))) & jnp.uint32(1)
    out_ref[0] = bit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def frontier_gather_full(
    words: jax.Array, src: jax.Array, *, interpret: bool
) -> jax.Array:
    """Gather bits at arbitrary vertex ids; whole bitmap pinned in VMEM.

    words: uint32[W]; src: int32[NB, EB] -> bool[NB, EB]."""
    w = words.shape[0]
    nb, eb = src.shape
    out = pl.pallas_call(
        _full_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((w,), lambda i: (0,)),
            pl.BlockSpec((1, eb), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, eb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, eb), jnp.int32),
        interpret=interpret,
    )(words, src)
    return out.astype(jnp.bool_)
