"""Pallas kernel: frontier scatter-OR (phase-1 'mark dst in global queue').

TPU adaptation of the CUDA atomic-enqueue (DESIGN.md Sec. 3): edges are
pre-sorted by destination and cut into fixed-size blocks that each target ONE
``ww``-word output window.  Within a block the scatter becomes a dense
one-hot contraction on the MXU — the BLAS formulation of BFS the paper cites
(Buluc & Madduri) — followed by an in-VMEM bit-pack:

    counts[j] = sum_e active[e] * (dst_local[e] == j)      (MXU, f32)
    bits[j]   = counts[j] > 0                              (VPU)
    out[w]    = OR_e bits  packed 32/word                  (VPU)

Hot windows (hubs) span several *consecutive* blocks mapping to the same
output window; Pallas keeps the window tile resident in VMEM across them and
we OR-accumulate, initializing on the scalar-prefetched ``block_first`` flag.
This is how the paper's LRB 'uniform work per launch' idea survives on a
static grid: every block is exactly ``EB`` edges regardless of degree skew.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB_BITS = 512  # one-hot sub-tile width (lanes)


def _make_kernel(ww: int, eb: int):
    bits = ww * 32
    n_sub = max(1, bits // SUB_BITS)
    sub = bits // n_sub

    def kernel(bw_ref, bf_ref, active_ref, dst_ref, out_ref):
        i = pl.program_id(0)
        act = active_ref[0].astype(jnp.float32)  # [EB]
        dst = dst_ref[0]  # [EB], == bits for invalid slots
        packed = []
        for t in range(n_sub):
            iota = jax.lax.broadcasted_iota(jnp.int32, (1, sub), 1) + t * sub
            onehot = (dst[:, None] == iota).astype(jnp.float32)  # [EB, sub]
            counts = jnp.dot(
                act[None, :], onehot, preferred_element_type=jnp.float32
            )  # [1, sub]  (MXU)
            b = (counts[0] > 0).reshape(sub // 32, 32).astype(jnp.uint32)
            weights = (jnp.uint32(1) << jax.lax.broadcasted_iota(jnp.uint32, (1, 32), 1))
            packed.append((b * weights).sum(axis=1, dtype=jnp.uint32))
        words = jnp.concatenate(packed) if n_sub > 1 else packed[0]  # [ww]

        @pl.when(bf_ref[i] == 1)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] = out_ref[...] | words

    return kernel


@functools.partial(jax.jit, static_argnames=("n_windows", "ww", "interpret"))
def frontier_scatter(
    active: jax.Array,
    block_win: jax.Array,
    block_first: jax.Array,
    dst_local: jax.Array,
    *,
    n_windows: int,
    ww: int,
    interpret: bool,
) -> jax.Array:
    """Scatter-OR active bits into a packed bitmap.

    active:      bool/int[NB, EB]  per-edge activity (dst-sorted block order)
    block_win:   int32[NB]         output window index per block (sorted!)
    block_first: int32[NB]         1 on the first block of each window
    dst_local:   int32[NB, EB]     bit offset in window; ``ww*32`` = invalid
    returns      uint32[n_windows * ww]
    """
    nb, eb = dst_local.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, eb), lambda i, bw, bf: (i, 0)),
            pl.BlockSpec((1, eb), lambda i, bw, bf: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ww,), lambda i, bw, bf: (bw[i],)),
    )
    return pl.pallas_call(
        _make_kernel(ww, eb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_windows * ww,), jnp.uint32),
        interpret=interpret,
    )(block_win, block_first, active.astype(jnp.int32), dst_local)
