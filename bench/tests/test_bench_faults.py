"""``correct`` comes out false when the timed path is broken underneath,
for each fault a cell can have, and for the control (the program's own
``max_levels`` cutting every traversal one level short).  No cell runs on
more than one chip, so none can leave out the exchange between chips."""

import bench_tiny
import numpy as np
import pytest

import jax

from repro.analytics import engine, msbfs
from repro.core import bfs, loop


CELLS = ["g500-s21.kernel2", "g500-s18.serve", "g500-s18.serve_open"]
SERVE = CELLS[1:]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    result = bench_tiny.run_tiny(name, control=True)
    assert result["correct"] is False
    assert result["checks"]["distance_errors"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_state_returned_unchanged(name, monkeypatch):
    monkeypatch.setattr(loop, "traced_while",
                        lambda cond, step, init, **kw: init)
    assert bench_tiny.run_tiny(name)["correct"] is False


@pytest.mark.parametrize("name", SERVE)
def test_half_of_each_wave_left_out(name, monkeypatch):
    run_wave = engine.BFSQueryEngine._run_wave

    def half(self, roots):
        keep = max(1, roots.size // 2)
        out = np.full((roots.size, self.pg.n), np.iinfo(np.int32).max,
                      dtype=np.int64)
        out[:keep] = run_wave(self, roots[:keep])
        return out

    monkeypatch.setattr(engine.BFSQueryEngine, "_run_wave", half)
    result = bench_tiny.run_tiny(name)
    assert result["correct"] is False


def _altered(build, index):
    def build_altered(*args, **kw):
        fn = build(*args, **kw)

        def run(arrays, roots):
            out = fn(arrays, roots)
            return (out[0].at[index].add(1),) + tuple(out[1:])

        return jax.jit(run)

    return build_altered


def test_answer_altered_where_produced_kernel2(monkeypatch):
    monkeypatch.setattr(bfs, "build_bfs_fn",
                        _altered(bfs.build_bfs_fn, (0, 0)))
    assert bench_tiny.run_tiny("g500-s21.kernel2")["correct"] is False


@pytest.mark.parametrize("name", SERVE)
def test_answer_altered_where_produced_serve(name, monkeypatch):
    monkeypatch.setattr(msbfs, "build_msbfs_fn",
                        _altered(msbfs.build_msbfs_fn, (0, 0)))
    assert bench_tiny.run_tiny(name)["correct"] is False
