"""The benchmark's description: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: a deployment (the ``file`` of its
  ``configs`` entry);
- ``bench/traffic/<traffic>.json``: the parameters of a traffic mix, read by
  the one generator in :mod:`harness.traffic`;
- ``bench/metrics/<metric>.py``: a reader with ``read(run)`` that returns
  the metric's value, or ``None`` where the run has nothing to read.

Adding any of them takes a new file and a new entry, never an edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

# <checkout>/bench/harness/spec.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable  # read(run) -> Optional[float]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json``, with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]

    def metrics(self, trace: bool) -> List[Metric]:
        """``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
        per-layer ones."""
        return self.per_layer if trace else self.end_to_end


def load_benchmark(root: Path = CHECKOUT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_reader(name: str, root: Path = CHECKOUT) -> Callable:
    """``read`` of ``bench/metrics/<name>.py`` (names may hold dots, so the
    file is loaded by path, not imported by module name)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"metric {name!r}: no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = CHECKOUT,
              bench: Optional[dict] = None) -> Cell:
    """The workload ``name`` with its configuration, traffic mix and the
    readers of the metrics it reports.  Raises ``KeyError`` for a name
    ``BENCHMARK.json`` does not list."""
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(root / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def metrics(kind: str) -> List[Metric]:
        return [Metric(m["name"], m["unit"], load_reader(m["name"], root))
                for m in bench[kind] if _applies(m, name)]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=metrics("end_to_end"),
                per_layer=metrics("per_layer"))


def read_metrics(metrics: List[Metric], run) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read; a reader that returns ``None`` leaves its metric
    out of the line."""
    out = {}
    for m in metrics:
        value = m.read(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
