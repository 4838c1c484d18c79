"""Whole runs of each cell on the CPU backend at a tiny scale: the result
line's keys, seeded inputs, and the refusals of ``bench/run.py``."""

import json
import os
import shutil
import subprocess
import sys

import bench_tiny
import pytest

KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELLS = ["g500-s21.kernel2", "g500-s18.serve", "g500-s18.serve_open"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_result_line(name, traced):
    result = bench_tiny.run_tiny(name, traced=traced, seed=2**31 + 99)
    keys = list(result)
    want = KEYS + (["breakdown"] if traced else []) + ["checks"]
    assert keys == want, keys  # the checks come last
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    cell = bench_tiny.tiny_cell(name)
    assert set(result["metrics"]) <= {m.name for m in cell.metrics(traced)}
    if not traced:
        assert set(result["metrics"]) == {m.name for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] == cell.chips
    assert "memory_peak_bytes" in dev
    if traced:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    for check in result["checks"].values():
        assert set(check) == {"value", "limit"}
    json.dumps(result)  # one JSON line


def test_same_seed_same_answers():
    logs = [[], []]
    for log in logs:
        bench_tiny.run_tiny("g500-s21.kernel2", seed=11,
                            log=log.append)
    draws = [[m for m in log if m.startswith(("setup: graph",
                                                "setup: traffic"))]
             for log in logs]
    assert draws[0] and [d.split(" s:")[-1] for d in draws[0]] == \
        [d.split(" s:")[-1] for d in draws[1]]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s18.serve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    proc = _run_py(bench_tiny.CHECKOUT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no result" in proc.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(bench_tiny.CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench_tiny.CHECKOUT / "BENCHMARK.json", tmp_path)
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
