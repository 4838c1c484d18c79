"""THE while-loop builder for level-synchronous traversals (DESIGN.md §19).

Every traversal in this repo — BFS, MS-BFS, SSSP, betweenness centrality,
and the §19 vertex programs — compiles to the same shape: ONE
``jit(shard_map(lax.while_loop))`` program whose carry optionally threads
the §18 flight-recorder buffer.  Before §19 that scaffolding was
copy-pasted per algorithm; this module is the single implementation every
builder delegates to.

Two pieces:

* :func:`traced_while` — the level loop.  The per-algorithm ``step``
  returns ``(next_state, (index, row))`` where ``row`` is the §18 trace
  row (or ``None`` untraced); this helper owns the trace-buffer carry
  slot, the ``record`` write, and the Python-level gating that keeps
  ``trace=False`` staging the EXACT uninstrumented jaxpr (the §18 cost
  contract — guarded by the HLO fingerprint test in
  ``tests/test_programs.py``).
* :func:`jit_shard` — the ``jit(shard_map(...))`` wrapper with the
  standard graph-pytree ``in_specs`` every builder uses: a dict of
  ``[P, ...]`` graph planes sharded over the mesh axes plus replicated
  scalar/root operands, and ``n_out`` sharded outputs (+1 for the trace
  buffer).

The helpers are pure code motion from the pre-§19 builders: a delegating
builder stages a byte-identical StableHLO program (asserted against
recorded fingerprints), so the refactor is invisible to the compiler.

BFS and MS-BFS share more than the loop: one level step,
:func:`repro.core.bfs.level_step` (expand, exchange, update, Beamer's
switch), written once over two frontier formats —
:class:`~repro.core.bfs.VertexFormat` (one search, vertex-packed) and
:class:`~repro.core.bfs.LaneFormat` (a 32-lane wave, lane-packed) — which
:func:`repro.core.bfs.run_levels` runs on :func:`traced_while`.  The
host-segmented step of :mod:`repro.core.flightrec` is one call of it.

:func:`phase` names the phases of a level step with ``jax.named_scope``
(``traversal.expand``, ``.exchange``, ``.update``, ``.direction``,
``.cond``).  A scope changes only the ``op_name`` metadata of the ops
staged inside it: the compiled module names each operation's phase, so a
device trace can be split by phase, and the StableHLO text the
fingerprints hash is unchanged.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

#: the phases of a level step, as :func:`phase` names them
PHASES = ("expand", "exchange", "update", "direction", "cond")


def phase(name: str):
    """``jax.named_scope("traversal.<name>")`` around the ops of one phase:
    ``expand`` (phase 1, push or pull, and the level's edge count),
    ``exchange`` (the phase-2 frontier sync), ``update`` (enqueue-if-new
    and the distance write), ``direction`` (Beamer's switch), ``cond``
    (the level loop's condition)."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; expected one of {PHASES}")
    return jax.named_scope("traversal." + name)


def traced_while(
    cond: Callable,
    step,
    init: Tuple,
    *,
    trace: bool = False,
    trace_levels: Optional[int] = None,
    pick: Optional[Callable] = None,
):
    """Run ``lax.while_loop(cond, step, init)`` with optional §18 tracing.

    ``step(state) -> (next_state, rec)`` where ``rec`` is ``(index, row)``
    when ``trace=True`` (``row`` an ``int32[TRACE_COLS]`` from
    ``flightrec.trace_row``; ``index`` the level it records) and ignored —
    conventionally ``None`` — otherwise.  The trace buffer rides as the
    LAST carry entry, so ``cond``/``step`` address their own state by
    prefix (``state[:k]``) exactly as before the refactor.

    With ``pick``, ``step`` is a sequence of steps and ``pick(state)`` the
    index of the one the next iteration runs: an outer loop runs, for each
    step in turn, an inner loop of that step while ``cond`` holds and
    ``pick`` names it.  Every step is then straight-line code in a loop
    body, which XLA compiles as it does a plain loop, where a ``lax.cond``
    over the steps would put each in a conditional's branch.

    Returns the final full state tuple; traced runs carry the filled
    ``int32[trace_levels, TRACE_COLS]`` buffer in the last slot.
    """
    init = tuple(init)
    if trace:
        from repro.core import flightrec

        if trace_levels is None:
            raise ValueError("trace=True requires trace_levels")
        init = init + (flightrec.zeros(trace_levels),)
    if pick is None:
        return lax.while_loop(cond, _body(step, trace), init)
    bodies = [_body(s, trace) for s in step]

    def outer(state):
        for i, body in enumerate(bodies):
            state = lax.while_loop(
                lambda s, i=i: cond(s) & (pick(s) == i), body, state)
        return state

    return lax.while_loop(cond, outer, init)


def _body(step: Callable, trace: bool) -> Callable:
    """A ``lax.while_loop`` body from a ``step``: with ``trace`` it records
    the step's row into the trace buffer in the last carry slot."""
    if trace:
        from repro.core import flightrec

        def body(state):
            out, rec = step(state)
            index, row = rec
            return tuple(out) + (flightrec.record(state[-1], index, row),)

        return body

    def body(state):
        out, _ = step(state)
        return tuple(out)

    return body


def jit_shard(
    body: Callable,
    mesh: jax.sharding.Mesh,
    array_keys: Sequence[str],
    spec: P,
    *,
    n_in: int = 1,
    n_out: int = 3,
    trace: bool = False,
):
    """``jit(shard_map(body))`` with the standard traversal signature:
    ``body(arrays, *operands)`` where ``arrays`` is the placed graph
    pytree (every key sharded by ``spec``) and the ``n_in`` trailing
    operands are replicated; ``n_out`` sharded outputs plus the sharded
    trace buffer when ``trace=True``."""
    shard_fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=({k: spec for k in array_keys},) + (P(),) * n_in,
        out_specs=(spec,) * n_out + ((spec,) if trace else ()),
        check_vma=False,
    )
    return jax.jit(shard_fn)
