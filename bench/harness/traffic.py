"""The one traffic generator: request streams drawn from the seed and the
parameters of a mix file (``bench/traffic/<mix>.json``).

A mix names its ``driver`` (:mod:`harness.drivers`: ``single_source``,
one closed-loop client driving the compiled traversal;
``service_open``, open-loop arrivals into ``GraphQueryService`` at a fixed
rate; ``service_closed``, clients that each wait for their answer before
they ask again) and these parameters:

- ``clients``: request streams (one for ``single_source`` and
  ``service_open``);
- ``rate_per_s`` (``service_open``): arrivals per second, evenly spaced;
- ``algos``: ``{algo: probability}`` of each request's algorithm;
- ``hot_roots``, ``hot_share``: roots are drawn uniformly over the largest
  component, distinct, in an order from the seed; the first ``hot_roots``
  are hot, and that share of requests goes to one of them (uniformly);
  the rest are cold, dealt to the streams in turn;
- ``check``: ``all`` answers are compared with the reference, or a
  ``sample`` of ``check_count`` requests drawn from the seed.

The k-th request of stream i depends only on the seed, the candidates
and the mix, never on timing.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
from typing import Iterator, List, Tuple

import numpy as np

# stream ids that keep the generator's draws apart from each other and
# from the graph generator's (which uses the bare seed)
_ROOTS, _CLIENT, _CHECK = 1, 2, 3


@dataclasses.dataclass(frozen=True)
class Request:
    algo: str
    root: int
    hot: bool


@dataclasses.dataclass
class Plan:
    """What a run will ask: hot roots, the cold roots in draw order, and
    one request stream per client."""

    hot: np.ndarray
    cold: np.ndarray
    clients: List["ClientStream"]


class ClientStream:
    """Client ``index``'s requests, in order: its own generator decides
    algo and hot or cold; cold roots come from its deal of the draw."""

    def __init__(self, index: int, mix: dict, seed: int, hot: np.ndarray,
                 cold: Iterator[int]):
        self.index = index
        self._rng = np.random.default_rng([seed, _CLIENT, index])
        self._algos = list(mix["algos"])
        self._p = np.asarray([mix["algos"][a] for a in self._algos], float)
        self._p = self._p / self._p.sum()
        self._hot = hot
        self._hot_share = float(mix.get("hot_share", 0.0)) if hot.size else 0.0
        self._cold = cold

    def __iter__(self):
        return self

    def __next__(self) -> Request:
        algo = self._algos[int(self._rng.choice(len(self._algos), p=self._p))]
        if self._rng.random() < self._hot_share:
            return Request(algo, int(self._hot[self._rng.integers(
                self._hot.size)]), True)
        return Request(algo, int(next(self._cold)), False)


def plan(mix: dict, seed: int, candidates: np.ndarray) -> Plan:
    """The run's requests, from the seed.  ``candidates`` are the vertices
    roots may be drawn from (the largest component, ascending)."""
    rng = np.random.default_rng([seed, _ROOTS])
    roots = rng.permutation(candidates).astype(np.int64)
    n_hot = int(mix.get("hot_roots", 0))
    hot, cold = roots[:n_hot], roots[n_hot:]
    n = int(mix["clients"])
    clients = [ClientStream(i, mix, seed, hot,
                            itertools.cycle(cold[i::n].tolist()))
               for i in range(n)]
    return Plan(hot=hot, cold=cold, clients=clients)


class Sample:
    """The answers compared with the reference: every one where the mix
    says ``all``; else the ``check_count`` completed requests of least
    priority, where request k of client i has a priority drawn from the
    seed, whatever the timing.  That is a uniform sample of the window's
    completed requests, and never holds more than ``check_count``."""

    def __init__(self, mix: dict, seed: int):
        self.all = mix["check"] == "all"
        self._k = 0 if self.all else int(mix["check_count"])
        self._seed = seed
        self._prio = {}  # client -> (its generator, priorities drawn)
        self._heap: List[Tuple[float, int, int, object, object]] = []
        self._kept: List[Tuple[object, object]] = []

    def priority(self, client: int, k: int) -> float:
        if client not in self._prio:
            self._prio[client] = (
                np.random.default_rng([self._seed, _CHECK, client]), [])
        rng, drawn = self._prio[client]
        while k >= len(drawn):
            drawn.extend(rng.random(64).tolist())
        return drawn[k]

    def offer(self, client: int, k: int, key, answer) -> None:
        """Request ``k`` of ``client`` (``key``: its root or request)
        completed with ``answer``."""
        if self.all:
            self._kept.append((key, answer))
            return
        item = (-self.priority(client, k), client, k, key, answer)
        if len(self._heap) < self._k:
            heapq.heappush(self._heap, item)
        elif item[0] > self._heap[0][0]:
            heapq.heapreplace(self._heap, item)

    def answers(self) -> List[Tuple[object, object]]:
        if self.all:
            return list(self._kept)
        return [(key, answer) for _, _, _, key, answer in sorted(
            self._heap, key=lambda it: (it[1], it[2]))]
