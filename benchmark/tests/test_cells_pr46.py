"""PR 46's cell ``tpch-sf10-chip1-lineitem.q6_bounds``, rehearsed on
the CPU backend (never a measurement) at SF0.1 through ``harness.run``,
traced and untraced, and every new reader on facts with and without the
engine's counters."""
import os

import pytest

from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

CONFIG = "tpch-sf10-chip1-lineitem"
CELL = f"{CONFIG}.q6_bounds"
SCAN = "scan + staging: io/scan.py, columnar/batch.py"
METRICS = [
    ("double_scaled_leaves", "count", "higher", "wire.double.scaled"),
    ("double_raw_leaves", "count", "lower", "wire.double.raw"),
    ("double_wire_bytes", "bytes", "lower", "wire.double.bytes"),
]


def _entry(entries, name):
    """The entry called ``name`` (a later PR appends after it)."""
    return next(e for e in entries if e["name"] == name)


def test_q6_bounds_cell_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "q6_bounds", 1)
    assert len(entry["why"]) <= 200
    cell = load_cell(CELL, root)
    conf_entry = _entry(bench["configs"], CONFIG)
    assert conf_entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf_entry["source"] == cell.config["source"]
    assert conf_entry["source"].startswith(
        "TPC-H spec 2.4.6, Q6 forecasting revenue change")
    assert len(conf_entry["source"]) <= 200 and len(conf_entry["why"]) <= 200
    assert conf_entry["reduced"] == cell.config["reduced"] == ["scale_factor"]
    assert len({c["source"] for c in bench["configs"]}) \
        == len(bench["configs"])
    # a generator file of its own; the conf every one-chip cell sets
    assert cell.config["datagen"] == "tpch_lineitem"
    assert (cell.config["suite"], cell.config["scale_factor"],
            cell.config["chips"]) == ("tpch", 10, 1)
    assert cell.config["conf"] \
        == load_cell("tpch-sf1-chip1.q1", root).config["conf"]
    assert cell.traffic == {**cell.traffic, "loop": "closed", "clients": 1,
                            "queries": ["q6", "q6_bounds"]}
    assert {"source", "suite", "datagen", "scale_factor", "chips", "conf",
            "guarantees", "reduced", "reduced_why", "assumed", "layout",
            "tables", "why"} <= set(cell.config)
    assert {"answers", "comparison", "placement", "predicate"} \
        == set(cell.config["guarantees"])
    assert "both inclusive" in cell.config["guarantees"]["predicate"]
    # Q6 as it stood, and its filter grouped by discount, over the same
    # four columns; neither hands the reader a predicate
    q6 = load_module(root, "queries", "tpch_q6")
    bounds = load_module(root, "queries", "tpch_q6_bounds")
    assert q6.TABLES == bounds.TABLES == {"lineitem": [
        "l_extendedprice", "l_discount", "l_shipdate", "l_quantity"]}
    for mod in (q6, bounds):
        with open(mod.__file__) as f:
            assert "pushdown" not in f.read()
    load_module(root, "reference", "tpch_q6").rows
    load_module(root, "reference", "tpch_q6_bounds").rows
    load_module(root, "datagen", "tpch_lineitem").generate
    for m in cell.per_layer:
        load_module(root, "layer_metrics", m["name"]).read
    assert {m["name"] for m in cell.end_to_end} \
        == {"query_s", "rows_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, _ in METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, "program_counter", SCAN,
                                    "query_s", [CELL])
    # and no other cell reports them
    for other in bench["workloads"]:
        names = {m["name"] for m in load_cell(other["name"], root).per_layer}
        assert other is entry or not names & {m[0] for m in METRICS}


def test_reference_imports_nothing_of_the_engine():
    for name in ("tpch_q6", "tpch_q6_bounds"):
        with open(os.path.join(ROOT, "benchmark", "reference",
                               name + ".py")) as f:
            text = f.read()
        assert "spark_rapids_tpu" not in text.replace(
            "copied from\nspark_rapids_tpu", "")
        assert "import jax" not in text


def test_q6_bounds_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    for m in bench["per_layer"]:
        if m["name"] in {q[0] for q in METRICS}:
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)     # ends ``correct``, both queries
    # SF0.1 is 600,000 lines in one file, one staged batch of 2^20
    # slots a collect: discount, quantity and price all travel scaled
    # (4 + 8 + 24 bits a slot), none raw
    assert got["double_scaled_leaves"] == 3
    assert got["double_raw_leaves"] == 0
    assert got["double_wire_bytes"] == (1 << 20) * (4 + 8 + 24) // 8
    assert got["double_wire_bytes"] < got["h2d_bytes"]


@pytest.mark.parametrize("metric,unit,better,counter", METRICS)
def test_reader_with_and_without_the_counters(metric, unit, better, counter):
    read = load_module(ROOT, "layer_metrics", metric).read
    # an engine from before the counters: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a collect that did not move it counts as 0 in the mean
    moved = {"wire.double.bytes": 6, counter: 6}
    assert read(_facts([moved, {"d2h_calls": 3}, moved])) == 4
    # no raw leaf among the float64 columns shipped: 0, not nothing
    if metric == "double_raw_leaves":
        assert read(_facts([{"wire.double.bytes": 64,
                             "wire.double.scaled": 3}])) == 0
