"""Pallas kernel: multi-way OR-reduce of packed frontier bitmaps.

Used by the butterfly merge: the ``fanout - 1`` buffers received in one
round plus the local accumulator are OR-merged in ONE pass over VMEM tiles
instead of ``fanout - 1`` separate elementwise passes (saves HBM traffic
proportional to the fanout; see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_WORDS = 1024  # 4 KiB of uint32 per tile per input


def _kernel(stack_ref, out_ref):
    acc = stack_ref[0]
    for k in range(1, stack_ref.shape[0]):
        acc = acc | stack_ref[k]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bitmap_or_reduce(
    stack: jax.Array, *, block: int = BLOCK_WORDS, interpret: bool
) -> jax.Array:
    """OR-reduce ``uint32[K, W]`` -> ``uint32[W]``; W must divide by block."""
    k, w = stack.shape
    assert w % block == 0, (w, block)
    return pl.pallas_call(
        _kernel,
        grid=(w // block,),
        in_specs=[pl.BlockSpec((k, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((w,), jnp.uint32),
        interpret=interpret,
    )(stack)
