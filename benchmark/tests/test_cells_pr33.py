"""PR 33's cell ``tpcds-sf10-chip1-returns.q93``, rehearsed on the CPU
backend (never a measurement) at SF0.1 through ``harness.run``, traced
and untraced, and every new reader on facts with and without the
engine's counters and programs."""
import pytest

from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

CELL = "tpcds-sf10-chip1-returns.q93"
CONFIG = "tpcds-sf10-chip1-returns"
OPS = "operator programs: exec/, ops/"
Q93_METRICS = [
    ("join_search_probe_s", "s", "lower", "device_trace"),
    ("join_gather_s", "s", "lower", "device_trace"),
    ("join_packed_build_s", "s", "lower", "device_trace"),
    ("join_search_batches", "count", "higher", "program_counter"),
    ("join_sorted_batches", "count", "lower", "program_counter"),
    ("join_rows_out", "count", "lower", "program_counter"),
]
PROBE_METRICS = {"join_search_batches": "join.probe.search",
                 "join_sorted_batches": "join.probe.sorted"}
TRACE_METRICS = {
    "join_search_probe_s": ("jit_join_probe_fast",),
    "join_gather_s": ("jit_join_gather",),
    "join_packed_build_s": ("jit_join_build_prep", "jit_join_build_table")}


def _entry(entries, name):
    """The entry called ``name`` (a later PR appends after it)."""
    return next(e for e in entries if e["name"] == name)


def test_q93_cell_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    assert CELL == f"{CONFIG}.q93"
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "q93", 1)
    assert len(entry["why"]) <= 200
    cell = load_cell(CELL, root)
    conf_entry = _entry(bench["configs"], CONFIG)
    assert conf_entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf_entry["source"] == cell.config["source"]
    assert len(conf_entry["source"]) <= 200 and len(conf_entry["why"]) <= 200
    assert conf_entry["reduced"] == cell.config["reduced"] == ["scale_factor"]
    assert len({c["source"] for c in bench["configs"]}) \
        == len(bench["configs"])
    # the other SF10 cells' files, seed for seed: one suite, scale and conf
    q6 = load_cell("tpcds-sf10-chip1.q6", root)
    assert cell.dataset == q6.dataset == "tpcds-sf10"
    assert cell.config["conf"] == q6.config["conf"]
    assert cell.traffic == {**cell.traffic, "loop": "closed", "clients": 1,
                            "queries": ["q93", "q93_all"]}
    assert {"source", "suite", "datagen", "scale_factor", "chips", "conf",
            "guarantees", "reduced", "assumed"} <= set(cell.config)
    tables = load_module(root, "queries", "tpcds_q93").TABLES
    assert set(tables) == {"store_sales", "store_returns", "reason"}
    assert sum(len(c) for c in tables.values()) == 11
    load_module(root, "reference", "tpcds_q93").rows
    # q93 with its limit lifted: the same scan, the same DataFrame code
    assert load_module(root, "queries", "tpcds_q93_all").TABLES == tables
    load_module(root, "reference", "tpcds_q93_all").rows
    for m in cell.per_layer:
        load_module(root, "layer_metrics", m["name"]).read
    assert {m["name"] for m in cell.end_to_end} \
        == {"query_s", "rows_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, source in Q93_METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, source, OPS, "query_s",
                                    [CELL])
    # and no other cell reports them
    for other in bench["workloads"]:
        names = {m["name"] for m in load_cell(other["name"], root).per_layer}
        assert other is entry or not names & {m[0] for m in Q93_METRICS}


def test_q93_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    for m in bench["per_layer"]:
        if m["name"] in {q[0] for q in Q93_METRICS}:
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    # both queries were collected and compared, every customer's sum
    # among the rows (the harness keeps them beside the data)
    import glob
    import json
    import os
    kept = {os.path.basename(f): len(json.load(open(f))) for f in glob.glob(
        os.path.join(root, ".bench_data", "*", "*", "reference_*.json"))}
    assert kept["reference_tpcds_q93.json"] == 100
    assert kept["reference_tpcds_q93_all.json"] > 700
    # SF0.1: 288k sales in one stream batch against 28.8k returns, the
    # two keys packed: searched, never sorted together with the build
    assert got["join_search_batches"] == 1
    assert got["join_sorted_batches"] == 0
    # the left join hands on its whole stream (and a sale returned twice
    # twice); the semi-join after it keeps about 1 return in 35
    assert 288_000 <= got["join_rows_out"] < 288_000 + 28_800
    assert got["sync_calls"] >= 4
    # XLA:CPU has no device plane: device seconds are not invented
    assert not set(TRACE_METRICS) & set(got)


@pytest.mark.parametrize("metric", sorted(PROBE_METRICS))
def test_probe_reader_with_and_without_the_counters(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    name = PROBE_METRICS[metric]
    # an engine that counts no probes: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a record holds only what moved: batches that all went another way
    # read 0, and a collect that did not move it counts as 0 in the mean
    assert read(_facts([{"join.probe.direct": 28}] * 2)) == 0
    assert read(_facts([{name: 6}, {"join.probe.direct": 3}, {name: 3}])) \
        == 3


def test_rows_out_reader_with_and_without_the_counter():
    read = load_module(ROOT, "layer_metrics", "join_rows_out").read
    assert read(_facts([{"join.probe.search": 28}] * 2)) is None
    assert read(_facts([{"join.probe.rows_out": 10},
                        {"join.probe.rows_out": 20}])) == 15


@pytest.mark.parametrize("metric", sorted(TRACE_METRICS))
def test_trace_reader_with_and_without_its_programs(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    ops = [(p, 1.5) for p in TRACE_METRICS[metric]] + [("jit_other", 9.0)]
    assert read(_facts([{}, {}], ops, collects=2)) == \
        1.5 * len(TRACE_METRICS[metric]) / 2
    assert read(_facts([{}], [("jit_other", 9.0)])) is None
    assert read(_facts([{}], [])) is None
