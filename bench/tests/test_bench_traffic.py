"""The traffic generator: the same seed gives the same requests, the mixes
ask for what their files say, and the compared sample is drawn from the
seed."""

import bench_tiny  # noqa: F401  (paths)
import numpy as np
import pytest

from harness import spec, traffic

BIG_SEED = 2**31 + 12345  # the driver's seeds exceed 32 signed bits
SERVE = ["g500-s18.serve", "g500-s18.serve_open"]


def _mix(name):
    return spec.load_cell(name, bench_tiny.CHECKOUT).traffic


def _take(plan, k):
    return [[next(c) for _ in range(k)] for c in plan.clients]


@pytest.mark.parametrize("cell", SERVE + ["g500-s21.kernel2"])
def test_same_seed_same_requests(cell):
    mix = _mix(cell)
    cand = np.arange(10, 50_000, 3)
    a = traffic.plan(mix, BIG_SEED, cand)
    b = traffic.plan(mix, BIG_SEED, cand)
    c = traffic.plan(mix, BIG_SEED + 1, cand)
    assert np.array_equal(a.hot, b.hot) and np.array_equal(a.cold, b.cold)
    assert _take(a, 20) == _take(b, 20)
    assert _take(traffic.plan(mix, BIG_SEED, cand), 20) != _take(c, 20)


@pytest.mark.parametrize("cell", SERVE)
def test_serve_mix_shares(cell):
    mix = _mix(cell)
    plan = traffic.plan(mix, 7, np.arange(100_000))
    assert len(plan.clients) == mix["clients"] and plan.hot.size == 8
    reqs = [r for stream in _take(plan, 1800 // mix["clients"])
            for r in stream]
    hot = np.mean([r.hot for r in reqs])
    assert abs(hot - 1 / 3) < 0.03
    assert abs(np.mean([r.algo == "bfs" for r in reqs]) - 0.5) < 0.03
    assert all(r.root in set(plan.hot.tolist()) for r in reqs if r.hot)
    cold = [r.root for r in reqs if not r.hot]
    assert len(set(cold)) == len(cold), "cold roots repeat"
    assert not set(cold) & set(plan.hot.tolist())


def test_roots_are_uniform_over_the_candidates():
    """No root is left out: the draw is a permutation of the component,
    so its depth, degree or id decides nothing."""
    cand = np.arange(5, 20_005)
    plan = traffic.plan(_mix("g500-s21.kernel2"), 3, cand)
    assert plan.hot.size == 0
    assert np.array_equal(np.sort(plan.cold), cand)
    first = plan.cold[:2000]
    # the first draws spread over the whole range, not a part of it
    counts = np.histogram(first, bins=10, range=(5, 20_005))[0]
    assert counts.min() > 150
    roots = [next(plan.clients[0]).root for _ in range(50)]
    assert roots == plan.cold[:50].tolist()


def test_closed_loop_clients_draw_apart():
    mix = _mix("g500-s18.serve")
    plan = traffic.plan(mix, 11, np.arange(50_000))
    cold = [r.root for stream in _take(plan, 30) for r in stream
            if not r.hot]
    assert len(set(cold)) == len(cold)


def _offer_all(sample, n_clients, per_client, order):
    for i, k in order(n_clients, per_client):
        sample.offer(i, k, (i, k), f"answer {i} {k}")
    return sample.answers()


def _in_order(n, m):
    return [(i, k) for k in range(m) for i in range(n)]


def _reversed(n, m):
    return list(reversed(_in_order(n, m)))


def test_sample_is_seeded_bounded_and_independent_of_timing():
    mix = _mix("g500-s18.serve")
    a = _offer_all(traffic.Sample(mix, BIG_SEED), 64, 15, _in_order)
    b = _offer_all(traffic.Sample(mix, BIG_SEED), 64, 15, _reversed)
    c = _offer_all(traffic.Sample(mix, BIG_SEED + 1), 64, 15, _in_order)
    assert len(a) == mix["check_count"]
    assert a == b  # the order answers came in decides nothing
    assert a != c
    assert all(answer == f"answer {i} {k}" for (i, k), answer in a)
    # spread over the clients and over the window, not its start
    assert len({i for (i, _), _ in a}) > 20
    assert max(k for (_, k), _ in a) > 10


def test_sample_all_keeps_every_answer_in_order():
    sample = traffic.Sample(_mix("g500-s21.kernel2"), 1)
    got = _offer_all(sample, 1, 7, _in_order)
    assert [key for key, _ in got] == [(0, k) for k in range(7)]


def test_sample_under_the_count_keeps_all():
    sample = traffic.Sample(_mix("g500-s18.serve_open"), 5)
    got = _offer_all(sample, 1, 10, _in_order)
    assert sorted(key for key, _ in got) == [(0, k) for k in range(10)]
