"""The four-chip kernel-2 cell on four host CPU devices at a tiny scale:
its result line, its layout, ``correct`` false for the control and for a
program that leaves out the exchange between chips; and the
``exchange_pct`` reader on hand-made traces."""

import bench_tiny
import pytest

from harness import cell as cell_mod, drivers, program_trace, spec, system
from harness import trace
from repro.core import bfs

CELL = "g500-s21-p4.kernel2"


@pytest.mark.parametrize("traced", [False, True])
def test_four_chip_cell_runs_and_is_correct(traced):
    result = bench_tiny.run_tiny(CELL, traced=traced, seed=2**31 + 15)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["count"] == 4
    metrics = result["metrics"]
    if traced:
        assert 0 < metrics["exchange_pct"]["value"] < 100
        assert metrics["exchange_pct"]["unit"] == "%"
    else:
        assert set(metrics) == {"gteps", "setup_s"}


def test_four_chip_cell_lays_out_one_program_for_every_seed():
    """The configuration pads the owned-vertex width, so that at the tiny
    scale too every seed gets one layout."""
    config = bench_tiny.tiny_cell(CELL).config
    assert config["partition"]["vertex_pad"] % 32 == 0
    layouts = set()
    for seed in (1, 2, 2**31 + 3):
        pg = system.build_graph(config, seed, 4, lambda m: None).pg
        layouts.add((pg.vmax, pg.emax, pg.n_words))
    assert len(layouts) == 1, layouts


def test_four_chip_control_is_not_correct():
    result = bench_tiny.run_tiny(CELL, control=True)
    assert result["correct"] is False
    assert result["checks"]["distance_errors"]["value"] > 0


def test_four_chip_exchange_left_out_is_not_correct(monkeypatch):
    """Each chip then knows only its own window of the next frontier."""
    monkeypatch.setattr(bfs, "_sync_frontier", lambda words, cfg: words)
    result = bench_tiny.run_tiny(CELL)
    assert result["correct"] is False
    assert result["checks"]["distance_errors"]["value"] > 0


def test_both_exchange_branches_are_filed_under_exchange():
    """The adaptive exchange's ``sparse`` and ``dense`` scopes sit inside
    ``traversal.exchange``, so the phase map files both branches there."""
    import jax
    import jax.numpy as jnp

    from repro.graph import generators, partition

    pg = partition.partition_1d(generators.kronecker(9, 8, seed=1), 4)
    mesh = system.mesh_of(jax.devices()[:4])
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive")
    text = bfs.build_bfs_fn(pg, mesh, cfg).lower(
        bfs.place_arrays(pg, mesh, cfg.axes), jnp.int32(0)).compile().as_text()
    phases = program_trace.phase_map(text)
    scoped = {}
    for line in text.splitlines():
        name = program_trace._INSTR.match(line)
        op = program_trace._OP_NAME.search(line)
        for branch in ("sparse", "dense"):
            if name and op and f"/{branch}/" in op.group(1):
                scoped.setdefault(branch, []).append(name.group(1))
    assert set(scoped) == {"sparse", "dense"}
    for names in scoped.values():
        assert {phases[n] for n in names} == {"traversal.exchange"}


def _record(chips, collective_s=0.5, module_s=10.0, driver="single_source"):
    summary = trace.Summary(devices=chips, window_s=12.0, busy_s=11.0,
                            collective_s=collective_s, module_s=module_s,
                            device_ops=[], idle_gaps=[])
    return cell_mod.RunRecord(driver=driver, chips=chips, setup_s=1.0,
                              window=drivers.Window(seconds=12.0),
                              trace=summary)


@pytest.mark.parametrize("chips,collective_s,module_s,driver,want", [
    (4, 0.5, 10.0, "single_source", 5.0),
    (4, 2.5, 10.0, "single_source", 25.0),
    (1, 0.5, 10.0, "single_source", None),  # one chip: no exchange
    (4, 0.0, 10.0, "single_source", None),  # no collective in the trace
    (4, 0.5, 0.0, "single_source", None),  # no traversal module time
    (4, 0.5, 10.0, "service_closed", None),
])
def test_exchange_pct_reader(chips, collective_s, module_s, driver, want):
    read = spec.load_reader("exchange_pct", bench_tiny.CHECKOUT)
    got = read(_record(chips, collective_s, module_s, driver))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_exchange_pct_reads_nothing_untraced():
    read = spec.load_reader("exchange_pct", bench_tiny.CHECKOUT)
    record = _record(4)
    record.trace = None
    assert read(record) is None
