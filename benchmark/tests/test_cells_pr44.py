"""PR 44's per-layer metrics ``mesh_region_join_s`` and
``mesh_join_prepared_probes``: their entries in ``BENCHMARK.json``, each
reader on facts with and without its program / counters, and the mesh
cell at CPU scale (additions only: no cell, bound or harness file
moves)."""
import pytest

from benchmark.harness import run
from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _facts
from benchmark.tests.test_new_cell import _result_ok, _small_config

LAYER = "mesh regions: exec/mesh_region.py, exec/mesh_exec.py, parallel/"
CELL = "tpcds-sf1-mesh4.q6"
METRICS = [
    {"name": "mesh_region_join_s", "unit": "s", "better": "lower",
     "source": "device_trace", "layer": LAYER, "moves": "query_s",
     "workloads": [CELL]},
    {"name": "mesh_join_prepared_probes", "unit": "count",
     "better": "higher", "source": "program_counter", "layer": LAYER,
     "moves": "query_s", "workloads": [CELL]},
]


def test_metrics_are_declared_as_the_issue_names_them(bench_copy):
    root, bench, _ = bench_copy
    names = {m["name"] for m in METRICS}
    # found by name: a later PR appends its own entries after these
    assert [m for m in bench["per_layer"] if m["name"] in names] == METRICS
    for other in bench["workloads"]:
        cell = load_cell(other["name"], root)
        reported = names & {m["name"] for m in cell.per_layer}
        assert reported == (names if other["name"] == CELL else set())
    assert "query_s" in {m["name"] for m in load_cell(CELL, root).end_to_end}


def test_region_join_seconds_with_and_without_the_program():
    read = load_module(ROOT, "layer_metrics", "mesh_region_join_s").read
    ops = [("jit_mesh_region_join", 6.3), ("jit_mesh_aggregate", 0.02)]
    assert read(_facts([{}, {}], ops, collects=2)) == 3.15
    # a one-chip cell's trace, or XLA:CPU's (no device plane): left out
    assert read(_facts([{}], [("jit_join_gather", 0.02)])) is None
    assert read(_facts([{}], [])) is None


def test_prepared_probes_none_zero_and_four():
    read = load_module(ROOT, "layer_metrics",
                       "mesh_join_prepared_probes").read
    # no mesh join ran (a one-chip cell counts join.probe.* only)
    assert read(_facts([{"join.probe.direct": 28, "d2h_calls": 3}] * 2)) \
        is None
    # the parent of PR 44: mesh joins counted, no probe kind
    parent = {"mesh_join_replicated": 5, "mesh_join_broadcast_bytes": 9e5}
    assert read(_facts([parent, parent])) == 0
    # a region join left on the sort path is not a prepared probe
    assert read(_facts([{"mesh_join_replicated": 1,
                         "mesh_join.probe.sorted": 1}])) == 0
    assert read(_facts([{"mesh_join_partitioned": 2}])) == 0
    # q6: four dense builds a collect; a search counts too; a collect
    # that moved nothing counts as 0 in the mean
    q6 = {**parent, "mesh_join.probe.direct": 4}
    assert read(_facts([q6, q6, q6])) == 4
    mixed = {**parent, "mesh_join.probe.direct": 3,
             "mesh_join.probe.search": 1, "mesh_join.probe.sorted": 2}
    assert read(_facts([mixed, mixed])) == 4
    assert read(_facts([q6, {"d2h_calls": 3}])) == 2


def test_mesh_cell_reports_four_prepared_probes_at_cpu_scale(bench_copy):
    """Through ``harness.run``, traced, at SF0.1 on four virtual devices
    (XLA:CPU: never a measurement): q6's four region joins each probe a
    direct-address table, and the seconds of the region program are
    left out where the trace has no device plane."""
    root, bench, save = bench_copy
    bench["configs"].append(_small_config(root, "small", "tpcds-sf1-mesh4",
                                          0.1))
    name = "small.q6"
    bench["workloads"].append({"name": name, "config": "small",
                               "traffic": "q6", "chips": 4, "why": "test"})
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append(name)
    save(bench)
    cell = load_cell(name, root)
    out = run(name, seed=2**31 + 44, seconds=1, trace=True, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=True)
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["mesh_join_prepared_probes"] == 4
    assert got["window_compiles"] == 0
    assert got.get("mesh_region_join_s", 0) >= 0
