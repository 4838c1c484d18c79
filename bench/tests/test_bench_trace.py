"""The trace reduction: on hand-made events, and on a trace recorded on
the CPU backend at a tiny size."""

import bench_tiny  # noqa: F401  (paths, host devices)
import pytest

from harness import trace
from harness.trace import HostSpan, Op


def _op(dev, name, s, e, module="jit_body"):
    return Op(dev, name, module, float(s), float(e))


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]


def test_busy_idle_collectives_and_labels():
    ns = 1e9
    ops = [
        # device 0: busy 0-2 s and 3-6 s (overlapping ops), collective 5-6
        _op("d0", "fusion.1", 0, 2 * ns),
        _op("d0", "sort.3", 3 * ns, 5.5 * ns),
        _op("d0", "collective-permute-done.2", 5 * ns, 6 * ns),
        # device 1: busy 0-4 s, collective 2-3 s; one op of another program
        _op("d1", "fusion.1", 0, 4 * ns),
        _op("d1", "all-reduce.7", 2 * ns, 3 * ns),
        _op("d1", "copy.1", 9 * ns, 9.5 * ns, module="jit_other"),
        # outside the window: ignored
        _op("d0", "fusion.1", 11 * ns, 12 * ns),
    ]
    spans = [
        HostSpan("bench:window", 0, 10 * ns),
        HostSpan("bench:copy-back", 2 * ns, 3 * ns),
        HostSpan("bench:host-assembly", 6 * ns, 10 * ns),
        HostSpan("bench:client-wait", 6.2 * ns, 8.2 * ns),
    ]
    s = trace.reduce(ops, spans, module="jit_body")
    assert s.devices == 2
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx((5.0 + 4.5) / 2)
    assert s.idle_share == pytest.approx(1 - 4.75 / 10)
    assert s.collective_s == pytest.approx(1.0)
    assert s.module_s == pytest.approx((5.0 + 4.0) / 2)
    assert s.device_ops[0][0] == "fusion.1"
    assert s.device_ops[0][1] == pytest.approx((2 + 4) / 2)
    # longest gap: d1 4-9 s, midpoint 6.5 s, inside client-wait (innermost)
    assert s.idle_gaps[0] == ("client-wait", pytest.approx(5.0))
    labels = dict((round(t, 6), n) for n, t in s.idle_gaps)
    assert labels[4.0] == "client-wait"  # d0 6-10 s, midpoint 8 s
    assert labels[1.0] == "copy-back"  # d0 2-3 s
    assert len(s.idle_gaps) <= 10 and len(s.device_ops) <= 10


def test_collective_names():
    for name in ("all-reduce.1", "collective-permute-start.3", "ppermute.12",
                 "psum.7", "all-gather", "all-to-all.2"):
        assert trace._COLLECTIVE.match(name), name
    for name in ("fusion.3", "sort.1", "scatter.2", "while.1"):
        assert not trace._COLLECTIVE.match(name), name


def test_no_device_op_in_the_window_raises():
    with pytest.raises(ValueError):
        trace.reduce([_op("d0", "f", 20, 30)],
                     [HostSpan("bench:window", 0, 10)])


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2.0).sum())
    x = jnp.arange(200_000.0)
    f(x).block_until_ready()
    spans = trace.Spans(True)
    with trace.recording(tmp_path):
        with spans("window"):
            for _ in range(3):
                with spans("dispatch"):
                    y = f(x)
                with spans("device-wait"):
                    y.block_until_ready()
                with spans("host-assembly"):
                    sum(range(200_000))
    ops, host = trace.load(trace.xplane_file(tmp_path))
    assert {s.name for s in host} >= {"bench:window", "bench:dispatch",
                                      "bench:host-assembly"}
    assert ops and all(o.end >= o.start for o in ops)
    s = trace.reduce(ops, host)
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_share < 1
    assert any("sort" in name for name, _ in s.device_ops)
    assert s.idle_gaps and s.idle_gaps[0][1] > 0
    assert any(label == "host-assembly" for label, _ in s.idle_gaps)
