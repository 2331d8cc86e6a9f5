"""PR 38's cell ``tpcds-sf10-chip1-cumulative.q51``, rehearsed on the
CPU backend (never a measurement) at SF0.1 through ``harness.run``,
traced and untraced, and every new reader on facts with and without the
engine's counters and programs."""
import pytest

from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.harness.window_bytes import record_bytes, window_bytes
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

CELL = "tpcds-sf10-chip1-cumulative.q51"
CONFIG = "tpcds-sf10-chip1-cumulative"
OPS = "operator programs: exec/, ops/"
KERNELS = "kernels: XLA programs"
Q51_METRICS = [
    ("window_frame_s", "s", "lower", "device_trace", OPS),
    ("window_rows", "count", "lower", "program_counter", OPS),
    ("window_launches", "count", "lower", "program_counter", OPS),
    ("window_exact_sums", "count", "higher", "program_counter", OPS),
    ("join_full_tail_rows", "count", "lower", "program_counter", OPS),
    ("window_roofline", "%", "higher", "device_trace", KERNELS),
]
COUNTER_METRICS = {"window_rows": "window.rows",
                   "window_launches": "window.launches",
                   "window_exact_sums": "window.sum.cents",
                   "join_full_tail_rows": "join.full.unmatched_rows"}
PRE = "window.rows@int,date,double>double"
POST = "window.rows@int,date,double,double>double,double"


def _entry(entries, name):
    """The entry called ``name`` (a later PR appends after it)."""
    return next(e for e in entries if e["name"] == name)


def test_q51_cell_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    assert CELL == f"{CONFIG}.q51"
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "q51", 1)
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
    q93 = load_cell("tpcds-sf10-chip1-returns.q93", root)
    assert cell.dataset == q93.dataset == "tpcds-sf10"
    assert cell.config["conf"] == q93.config["conf"]
    assert cell.traffic == {**cell.traffic, "loop": "closed", "clients": 1,
                            "queries": ["q51", "q51_all"]}
    assert {"source", "suite", "datagen", "scale_factor", "chips", "conf",
            "guarantees", "reduced", "assumed", "layout", "tables"} \
        <= set(cell.config)
    assert "tie" in cell.config["guarantees"]["answers"]
    tables = load_module(root, "queries", "tpcds_q51").TABLES
    assert set(tables) == {"web_sales", "store_sales", "date_dim"}
    assert sum(len(c) for c in tables.values()) == 9
    load_module(root, "reference", "tpcds_q51").rows
    # q51 with its limit lifted: the same scan, the same DataFrame code
    assert load_module(root, "queries", "tpcds_q51_all").TABLES == tables
    load_module(root, "reference", "tpcds_q51_all").rows
    for m in cell.per_layer:
        load_module(root, "layer_metrics", m["name"]).read
    assert {m["name"] for m in cell.end_to_end} \
        == {"query_s", "rows_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, source, layer in Q51_METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, source, layer, "query_s",
                                    [CELL])
    # and no other cell reports them
    for other in bench["workloads"]:
        names = {m["name"] for m in load_cell(other["name"], root).per_layer}
        assert other is entry or not names & {m[0] for m in Q51_METRICS}


def test_q51_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    for m in bench["per_layer"]:
        if m["name"] in {q[0] for q in Q51_METRICS}:
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    # both queries were collected and compared, every qualifying row
    # among the rows (the harness keeps them beside the data)
    import glob
    import json
    import os
    kept = {os.path.basename(f): len(json.load(open(f))) for f in glob.glob(
        os.path.join(root, ".bench_data", "*", "*", "reference_*.json"))}
    assert kept["reference_tpcds_q51.json"] == 100
    assert kept["reference_tpcds_q51_all.json"] > 3000
    # a running sum a channel and ONE program for the two running maxes
    assert got["window_launches"] == 3
    assert got["window_exact_sums"] == 2
    # SF0.1: about 47k store and 12k web (item, day) groups, and their
    # union once more under the two maxes
    assert 100_000 < got["window_rows"] < 130_000
    assert 40_000 < got["join_full_tail_rows"] < 50_000
    # XLA:CPU has no device plane: device seconds are not invented
    assert "window_frame_s" not in got and "window_roofline" not in got


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_with_and_without_the_counter(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    name = COUNTER_METRICS[metric]
    # an engine from before the counter: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a collect that did not move it counts as 0 in the mean
    assert read(_facts([{name: 6}, {"d2h_calls": 3}, {name: 3}])) == 3


def test_window_seconds_with_and_without_the_program():
    read = load_module(ROOT, "layer_metrics", "window_frame_s").read
    ops = [("jit_window_frame", 1.5), ("jit_other", 9.0)]
    assert read(_facts([{}, {}], ops, collects=2)) == 0.75
    assert read(_facts([{}], [("jit_other", 9.0)])) is None
    assert read(_facts([{}], [])) is None


def test_window_bytes_take_rows_and_schema():
    # rows in x the input's width, rows out x (the input's + appended)
    assert window_bytes(10, ["int", "date", "double"], ["double"]) \
        == 10 * (16 + 16 + 8)
    assert window_bytes(3, ["int", "string", "long"], ["int", "long"]) \
        == 3 * (16 + 16 + 12)
    with pytest.raises(KeyError):
        window_bytes(1, ["array<int>"], ["int"])
    assert record_bytes({"window.rows": 7, "d2h_calls": 3}) is None
    assert record_bytes({PRE: 100, POST: 10, "window.rows": 110}) \
        == 100 * 40 + 10 * 64
    assert record_bytes({"window.rows@int,array<int>>int": 5}) is None


def test_window_roofline_with_and_without_its_sources():
    read = load_module(ROOT, "layer_metrics", "window_roofline").read
    ops = [("jit_window_frame", 2.0), ("jit_other", 9.0)]
    record = {PRE: 5_000_000, POST: 5_000_000, "window.rows": 10_000_000}

    def facts(records, device_ops, peaks=True):
        f = _facts(records, device_ops, collects=1)
        f["counters"]["chips"] = 1
        f["peaks"] = {"hbm_bytes_per_s": 1e9} if peaks else None
        return f
    # 5M x 40 + 5M x 64 bytes at 1 GB/s = 0.52 s of the program's 2 s
    assert read(facts([record, record], ops)) == pytest.approx(26.0)
    assert read(facts([record], ops, peaks=False)) is None   # off the chip
    assert read(facts([record], [("jit_other", 9.0)])) is None
    assert read(facts([{"window.rows": 5}], ops)) is None    # no schema
    assert read(facts([{"d2h_calls": 3}], ops)) is None      # old engine
