"""The main traversal programs compile for a described TPU v5e and fit its HBM.

Nothing runs here: the TPU compiler, which ships with the installed JAX,
compiles for a ``v5e:2x2`` topology that is described, not attached.  It
refuses what the chip would refuse (tiling, lowering, memory), and its
``memory_analysis()`` gives the bytes each program needs per chip.  The
shapes are those of ``chip_smoke.py``'s Graph500 graphs: scale 21 on one
chip, scale 22 over four.

The topology is described inside a fixture: loading the TPU library at
import time would make pytest-xdist workers collect different tests.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.analytics import msbfs
from repro.core import bfs
from repro.graph import partition

HBM_BYTES = int(15.75 * 2**30)  # what the v5e compiler allows one program
EDGE_FACTOR = 16
LANES = 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def graph500_shapes(scale, p):
    """Upper bounds of ``partition_1d``'s shapes for a Graph500 Kronecker
    graph: every generated edge kept in both directions (dedup only
    removes some), and on P > 1 the edge and vertex imbalance allowed
    above what partitions at scales 16-18 show (1.003 and 1.06)."""
    n = 1 << scale
    m_directed = 2 * EDGE_FACTOR * n
    if p == 1:
        return partition.synthetic_shapes(n, m_directed, 1, slack=1.0,
                                          vskew=1.0)
    return partition.synthetic_shapes(n, m_directed, p, slack=1.02,
                                      vskew=1.25)


def _compile(topo, build, scale, p, root_shape):
    shapes = graph500_shapes(scale, p)
    mesh = jax.sharding.Mesh(list(topo.devices[:p]), ("data",))
    sharded = NamedSharding(mesh, P("data"))
    arrays = {k: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharded)
              for k, s in shapes.array_shapes().items()}
    roots = jax.ShapeDtypeStruct(root_shape, jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    cfg = bfs.BFSConfig(axes=("data",), sync="adaptive", fanout=2)
    return build(shapes, mesh, cfg).lower(arrays, roots).compile()


def _fits(compiled):
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes)
    assert need <= HBM_BYTES, (
        f"{need / 2**30:.2f} GiB per chip > {HBM_BYTES / 2**30} GiB "
        f"(args {m.argument_size_in_bytes / 2**30:.2f}, temp "
        f"{m.temp_size_in_bytes / 2**30:.2f})")
    return m


_CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=(%[\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_DEFINED = re.compile(r"\s+(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]")


def _loop_ops(text, opcode):
    """The ``opcode`` instructions that the level loop runs: every
    instruction of each ``while`` body and of the computations it calls,
    fusions included, as ``(name, op_name, operand sizes)``, the sizes in
    elements, from each operand's definition in the same computation."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)
    todo = [m.group(1) for lines in comps.values() for line in lines
            if " while(" in line
            for m in [re.search(r"body=(%[\w.\-]+)", line)]]
    seen, found = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        sizes = {}
        for line in comps[comp]:
            d = _DEFINED.match(line)
            if d:
                sizes[d.group(1)] = math.prod(
                    int(x) for x in d.group(2).split(",") if x)
        for line in comps[comp]:
            todo += _CALLED.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [c.strip() for c in group.split(",")]
            op = re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = .*? " + opcode
                          + r"\(([^)]*)\)", line)
            if op:
                where = re.search(r'op_name="([^"]*)"', line)
                operands = [sizes.get(o.strip())
                            for o in op.group(2).split(",")]
                found.append((op.group(1), where.group(1) if where else "",
                              operands))
    assert seen, "no while loop in the compiled program"
    return found


def _assert_no_scatter_expansion(compiled, shapes):
    """Single-source top-down: the level loop holds no sort at all; its
    dense expansion (the dst-sorted in-edge scan) holds no scatter; the
    only scatters are the adaptive exchange's sparse receive on P > 1 and
    the sparse push of the frontier's own out-edges, each of at most
    ``max(vmax, K)`` updates (the root's ``set_bit`` runs before the
    loop)."""
    text = compiled.as_text()
    assert _loop_ops(text, "sort") == []
    limit = max(shapes.vmax, bfs.push_capacity(shapes.emax))
    scatters = _loop_ops(text, "scatter")
    pushed = [s for s in scatters if "traversal.expand/" in s[1]]
    assert pushed, "the sparse push is missing"
    for name, where, (_, _, updates) in scatters:
        assert "traversal.exchange/" in where or (
            "traversal.expand/" in where and "/sparse_push/" in where), (
            name, where)
        assert updates is not None and updates <= limit, (name, updates)
    dense = [w for _, w, _ in _loop_ops(text, "gather")
             if "traversal.expand/" in w and "/dense_scan/" in w]
    assert dense, "the dense branch's frontier-bit gather is missing"


def test_single_source_bfs_compiles_one_chip_scale21(topo):
    compiled = _compile(topo, bfs.build_bfs_fn, 21, 1, ())
    _fits(compiled)
    _assert_no_scatter_expansion(compiled, graph500_shapes(21, 1))


def test_single_source_bfs_compiles_four_chip_mesh_scale22(topo):
    compiled = _compile(topo, bfs.build_bfs_fn, 22, 4, ())
    _fits(compiled)
    # the butterfly exchange is compiled in: rounds of ppermute
    assert "collective-permute" in compiled.as_text()
    _assert_no_scatter_expansion(compiled, graph500_shapes(22, 4))


def test_served_32_lane_wave_compiles_one_chip_scale21(topo):
    _fits(_compile(
        topo, lambda pg, mesh, cfg: msbfs.build_msbfs_fn(pg, mesh, cfg, LANES),
        21, 1, (LANES,)))


def test_served_32_lane_wave_compiles_four_chip_mesh_scale22(topo):
    compiled = _compile(
        topo, lambda pg, mesh, cfg: msbfs.build_msbfs_fn(pg, mesh, cfg, LANES),
        22, 4, (LANES,))
    _fits(compiled)
    assert "collective-permute" in compiled.as_text()


def test_pallas_path_refused_for_described_chip(topo):
    """Compiling ahead of time for a TPU mesh from a CPU process: the
    Pallas frontier path is refused at build time, by the mesh's platform."""
    shapes = graph500_shapes(10, 1)
    mesh = jax.sharding.Mesh(list(topo.devices[:1]), ("data",))
    cfg = bfs.BFSConfig(axes=("data",), use_pallas=True)
    with pytest.raises(NotImplementedError, match="does not compile for TPU"):
        bfs.build_bfs_fn(shapes, mesh, cfg)
