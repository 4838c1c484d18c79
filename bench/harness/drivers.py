"""The drivers that push a traffic plan through the system for the window,
on the host clock, from one thread (the closed loop's clients resubmit
from the thread that resolved their answer).

- :func:`single_source`: one closed-loop client; each traversal is
  dispatched, waited for, copied back and assembled before the next.  The
  window runs from the first dispatch to the completion of the last
  traversal started before the deadline.
- :func:`service_open`: open-loop arrivals at a rate fixed in the traffic
  mix, evenly spaced, into ``GraphQueryService``.  Each request is timed
  from when it was due to when its answer resolved.  The window closes
  when the last answer is back.
- :func:`service_closed`: a fixed number of clients, each of which
  submits its next request as soon as its answer is back (from the
  answer's callback), until the deadline.  Each request is timed from its submit to its answer.  The
  window closes when the last request submitted before the deadline is
  answered.

A served answer that has not come a minute past the deadline is missing.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from concurrent.futures import wait as futures_wait
from typing import List

import numpy as np

from harness.traffic import Plan, Sample

GRACE_S = 60.0  # how long past the deadline an answer may still come


@dataclasses.dataclass
class Window:
    seconds: float = 0.0
    attempted: int = 0
    completed: int = 0
    failed: int = 0
    missing: int = 0
    latencies: List[float] = dataclasses.field(default_factory=list)  # s
    roots: List[int] = dataclasses.field(default_factory=list)  # completed
    answers: list = dataclasses.field(default_factory=list)  # to compare


def single_source(sut, plan: Plan, seconds: float, spans, log,
                  sample: Sample) -> Window:
    """Traversals from the plan's single client, back to back."""
    client = plan.clients[0]
    w = Window()
    t_start = time.perf_counter()
    deadline = t_start + seconds
    with spans("window"):
        while time.perf_counter() < deadline:
            root = next(client).root
            w.attempted += 1
            t = time.perf_counter()
            try:
                with spans("dispatch"):
                    out = sut(root)
                with spans("device-wait"):
                    out[0].block_until_ready()
                with spans("copy-back"):
                    d_owned = np.asarray(out[0])
                with spans("host-assembly"):
                    dist = sut.assemble(d_owned)
            except Exception as exc:  # the run goes on to report it
                w.failed += 1
                log(f"window: traversal from {root} failed: {exc!r}")
                break
            w.latencies.append(time.perf_counter() - t)
            sample.offer(0, w.completed, root, dist)
            w.completed += 1
            w.roots.append(root)
        w.seconds = time.perf_counter() - t_start
    w.answers = sample.answers()
    log("window: traversal seconds " + " ".join(
        f"{s:.3f}" for s in w.latencies))
    return w


def service_open(svc, plan: Plan, seconds: float, spans, log,
                 sample: Sample, *, rate_per_s: float) -> Window:
    """Open-loop arrivals against ``svc``: request k of the plan's stream is
    due at ``k / rate_per_s`` seconds into the window, whether or not
    earlier answers are back, for as many as fall inside ``seconds``.  Each
    is timed from when it was due to when its future resolved (a callback
    stamps the time in the resolving thread)."""
    w = Window()
    stream = plan.clients[0]
    n = int(rate_per_s * seconds)
    done_at = {}  # request index -> resolve time
    pending = {}  # request index -> (request, future, due time)
    late = 0.0
    t_start = time.perf_counter()

    def stamp(k):
        return lambda fut: done_at.__setitem__(k, time.perf_counter())

    with spans("window"):
        for k in range(n):
            req = next(stream)
            due = t_start + k / rate_per_s
            wait_s = due - time.perf_counter()
            if wait_s > 0:
                with spans("client-wait"):
                    time.sleep(wait_s)
            late = max(late, time.perf_counter() - due)
            w.attempted += 1
            try:
                with spans("submit"):
                    fut = svc.submit(req.algo, req.root)
            except Exception as exc:  # refused: counts as failed
                w.failed += 1
                w.latencies.append(math.inf)
                log(f"window: {req.algo} from {req.root} refused: {exc!r}")
                continue
            fut.add_done_callback(stamp(k))
            pending[k] = (req, fut, due)
        with spans("client-wait"):
            futures_wait([f for _, f, _ in pending.values()],
                         timeout=max(t_start + seconds + GRACE_S
                                     - time.perf_counter(), 0))
            # a future's callbacks run just after its waiters wake
            for _ in range(1000):
                if all(k in done_at for k, (_, f, _) in pending.items()
                       if f.done()):
                    break
                time.sleep(0.001)
        last = t_start
        for k, (req, fut, due) in sorted(pending.items()):
            if not fut.done():
                w.missing += 1
                w.latencies.append(math.inf)
                continue
            try:
                value = fut.result()
            except Exception as exc:
                w.failed += 1
                w.latencies.append(math.inf)
                log(f"window: {req.algo} from {req.root} failed: {exc!r}")
                continue
            t_done = done_at.get(k, time.perf_counter())
            last = max(last, t_done)
            w.completed += 1
            w.latencies.append(t_done - due)
            sample.offer(0, k, req, value)
        w.failed += w.missing
        w.seconds = (t_start + seconds + GRACE_S if w.missing else last) \
            - t_start
    w.answers = sample.answers()
    log(f"window: {n} requests due at {rate_per_s} /s; the generator ran "
        f"at most {late * 1e3:.1f} ms late")
    return w


def service_closed(svc, plan: Plan, seconds: float, spans, log,
                   sample: Sample) -> Window:
    """Each of the plan's clients keeps one request in flight.  An answer's
    callback, in whichever thread resolved it, sends that client's next
    request while the deadline has not passed, so every client is back in
    the queue before the scheduler forms its next dispatch, whatever this
    thread is doing; the answers come back to this thread to be counted.
    A failed answer counts as failed and the client goes on; a refused
    submit counts as failed and ends that client."""
    w = Window()
    answered = queue.SimpleQueue()  # (client, k, request, t0, future, t1)
    lock = threading.Lock()
    sent = [0] * len(plan.clients)
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def reserve(i: int):
        with lock:
            k = sent[i]
            sent[i] += 1
            w.attempted += 1
            return k, next(plan.clients[i])

    def send(i: int, k: int, req) -> None:
        while True:
            t0 = time.perf_counter()
            try:
                with spans("submit"):
                    fut = svc.submit(req.algo, req.root)
            except Exception as exc:
                answered.put((i, k, req, t0, exc, time.perf_counter()))
                return
            if not fut.done():
                fut.add_done_callback(lambda f: finish(i, k, req, t0, f))
                return
            # answered at once (a cache hit): go on here, not by recursion
            nxt = hand_over(i, k, req, t0, fut)
            if nxt is None:
                return
            k, req = nxt

    def hand_over(i: int, k: int, req, t0: float, fut):
        t1 = time.perf_counter()
        # the next request is counted before this answer is handed over,
        # so this thread never sees every answer in while one is due
        nxt = reserve(i) if t1 < deadline else None
        answered.put((i, k, req, t0, fut, t1))
        return nxt

    def finish(i: int, k: int, req, t0: float, fut) -> None:
        nxt = hand_over(i, k, req, t0, fut)
        if nxt is not None:
            send(i, *nxt)

    received = 0
    last = t_start
    with spans("window"):
        for i in range(len(plan.clients)):
            send(i, *reserve(i))
        while True:
            with lock:
                if received == w.attempted:
                    break
            try:
                with spans("client-wait"):
                    i, k, req, t0, fut, t1 = answered.get(timeout=max(
                        deadline + GRACE_S - time.perf_counter(), 0))
            except queue.Empty:
                break
            received += 1
            if isinstance(fut, Exception):
                w.failed += 1
                w.latencies.append(math.inf)
                log(f"window: {req.algo} from {req.root} refused: {fut!r}")
                continue
            try:
                value = fut.result()
            except Exception as exc:
                w.failed += 1
                w.latencies.append(math.inf)
                log(f"window: {req.algo} from {req.root} failed: {exc!r}")
                continue
            w.completed += 1
            w.latencies.append(t1 - t0)
            last = max(last, t1)
            sample.offer(i, k, req, value)
        with lock:
            w.missing = w.attempted - received
        w.failed += w.missing
        w.latencies.extend([math.inf] * w.missing)
        w.seconds = (deadline + GRACE_S if w.missing else last) - t_start
    w.answers = sample.answers()
    log(f"window: {len(plan.clients)} closed-loop clients sent "
        f"{w.attempted} requests")
    return w
