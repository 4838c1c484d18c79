"""BENCHMARK.json against the benchmark's contract, and the lookup of
configurations, traffic mixes and metrics by name alone."""

import json
import re
import shutil

import bench_tiny
import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(bench_tiny.CHECKOUT)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_entries_have_exactly_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES


def test_names_units_and_uniqueness(bench):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in bench["end_to_end"])


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench_tiny.CHECKOUT, bench)
        e2e = {m.name for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, (w["name"], e2e)
        assert cell.per_layer, w["name"]
        moved = {m["moves"] for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])}
        assert moved <= e2e, (w["name"], moved, e2e)


def test_per_layer_moves_an_end_to_end_metric_of_each_of_its_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_four_chip_cells_at_most_half_rounded_down_or_one(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_config_files_hold_what_reduced_names(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        with open(bench_tiny.CHECKOUT / c["file"]) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert set(c["reduced"]) == set(config["reduced"])
        assert config["graph"]["edge_factor"] == 16
        assert (config["graph"]["A"], config["graph"]["B"],
                config["graph"]["C"]) == (0.57, 0.19, 0.19)


def test_unknown_workload_raises():
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", bench_tiny.CHECKOUT)


def test_files_dropped_in_are_found_by_name_alone(tmp_path):
    """A new configuration, traffic mix and metric: three new files and
    new entries in BENCHMARK.json, and a run reads them with no other
    edit."""
    root = tmp_path / "checkout"
    shutil.copytree(bench_tiny.CHECKOUT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "src").symlink_to(bench_tiny.CHECKOUT / "src")
    bench = spec.load_benchmark(bench_tiny.CHECKOUT)

    config = json.loads((root / "bench/configs/g500-s18.json").read_text())
    config["name"] = "g500-s9"
    (root / "bench/configs/g500-s9.json").write_text(json.dumps(config))
    mix = json.loads((root / "bench/traffic/serve_open.json").read_text())
    mix.update(rate_per_s=200.0, hot_share=1.0)
    (root / "bench/traffic/all_hot.json").write_text(json.dumps(mix))
    (root / "bench/metrics/answers_completed.py").write_text(
        "def read(run):\n    return run.window.completed\n")

    bench["configs"].append({"name": "g500-s9", "source": "test",
                             "file": "bench/configs/g500-s9.json",
                             "reduced": ["scale"], "why": "test"})
    bench["workloads"].append({"name": "g500-s9.all_hot",
                               "config": "g500-s9", "traffic": "all_hot",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("qps", "latency_p95_ms"):
            m["workloads"].append("g500-s9.all_hot")
    bench["per_layer"].append({"name": "answers_completed", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "service", "moves": "qps",
                               "workloads": ["g500-s9.all_hot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("g500-s9.all_hot", root)
    assert cell.config["name"] == "g500-s9"
    assert cell.traffic["hot_share"] == 1.0
    assert [m.name for m in cell.per_layer] == ["answers_completed"]
    plain = bench_tiny.run_tiny("g500-s9.all_hot", root=root, seconds=0.5,
                                scale=8)
    assert plain["correct"]
    assert set(plain["metrics"]) == {"qps", "latency_p95_ms", "setup_s"}
    traced = bench_tiny.run_tiny("g500-s9.all_hot", root=root, seconds=0.5,
                                 scale=8, traced=True)
    assert traced["correct"]
    assert set(traced["metrics"]) == {"answers_completed"}
    assert traced["metrics"]["answers_completed"]["value"] > 0
