"""The closed-loop driver against a stand-in service: each client keeps
one request in flight, the window closes on the last answer of a request
sent before the deadline, and failures are counted."""

import threading
import time
from concurrent.futures import Future

import bench_tiny  # noqa: F401  (paths)
import numpy as np

from harness import drivers, spec, traffic, trace


class FakeService:
    """Answers each request after ``delay_s`` on a timer thread; a hot
    root answers at once, as the result cache does.  Fails requests whose
    root is in ``fail``."""

    def __init__(self, delay_s=0.01, fail=()):
        self.delay_s = delay_s
        self.fail = set(fail)
        self.in_flight = 0
        self.most_in_flight = 0
        self.lock = threading.Lock()
        self.hot = set()

    def submit(self, algo, root):
        fut = Future()
        if root in self.hot:
            fut.set_result(("cached", algo, root))
            return fut
        with self.lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)

        def answer():
            with self.lock:
                self.in_flight -= 1
            if root in self.fail:
                fut.set_exception(RuntimeError("wave failed"))
            else:
                fut.set_result((algo, root))

        threading.Timer(self.delay_s, answer).start()
        return fut


def _run(svc, seconds=0.3, seed=4):
    mix = spec.load_cell("g500-s18.serve", bench_tiny.CHECKOUT).traffic
    plan = traffic.plan(mix, seed, np.arange(100_000))
    svc.hot = set(plan.hot.tolist())
    t = time.perf_counter()
    w = drivers.service_closed(svc, plan, seconds, trace.Spans(False),
                               lambda msg: None, traffic.Sample(mix, seed))
    return w, time.perf_counter() - t, mix


def test_each_client_keeps_one_request_in_flight():
    svc = FakeService(delay_s=0.05)
    w, wall, mix = _run(svc)
    assert svc.most_in_flight <= mix["clients"]
    assert svc.most_in_flight >= 3 * mix["clients"] // 4  # they start at once
    assert w.failed == 0 and w.missing == 0
    assert w.completed == w.attempted > 4 * mix["clients"]
    # the window ends with the last answer, after the deadline
    assert 0.3 <= w.seconds <= wall
    assert len(w.answers) == mix["check_count"]
    assert all(answer[-1] == req.root for req, answer in w.answers)


def test_failed_answers_count_and_the_client_goes_on():
    mix = spec.load_cell("g500-s18.serve", bench_tiny.CHECKOUT).traffic
    plan = traffic.plan(mix, 4, np.arange(100_000))
    fail = plan.cold[:10].tolist()
    w, _, _ = _run(FakeService(fail=fail))
    assert w.failed == 10
    assert w.completed == w.attempted - 10
    assert sum(np.isinf(w.latencies)) == 10


class BatchService:
    """Resolves requests in dispatches, as the wave scheduler does: one
    thread takes every pending request, works, and resolves them in turn;
    it records how many each dispatch took."""

    def __init__(self, work_s=0.01):
        self.work_s = work_s
        self.pending = []
        self.cv = threading.Condition()
        self.sizes = []
        self.stop = False
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def submit(self, algo, root):
        fut = Future()
        with self.cv:
            self.pending.append((fut, algo, root))
            self.cv.notify()
        return fut

    def _run(self):
        while True:
            with self.cv:
                self.cv.wait_for(lambda: self.pending or self.stop)
                if self.stop:
                    return
                batch, self.pending = self.pending, []
            self.sizes.append(len(batch))
            time.sleep(self.work_s)
            for fut, algo, root in batch:
                fut.set_result((algo, root))
                time.sleep(0.0002)  # the host work of each answer


def test_clients_are_back_before_the_next_dispatch():
    """The packing of waves does not race the driver's thread: every
    dispatch after the first takes all the clients, however slowly the
    answers of the last one were handed out."""
    svc = BatchService()
    w, _, mix = _run(svc, seconds=0.5)
    svc.stop = True
    with svc.cv:
        svc.cv.notify()
    assert w.failed == 0 and w.missing == 0
    # the hot share answers no faster here: every request is queued
    full = svc.sizes[1:-1]
    assert len(full) > 5
    assert all(n == mix["clients"] for n in full), svc.sizes
