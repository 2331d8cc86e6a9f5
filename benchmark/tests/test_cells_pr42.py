"""PR 42's cell ``tpch-sf10-chip1-orders.q13``, rehearsed on the CPU
backend (never a measurement) at SF0.1 through ``harness.run``, traced
and untraced, and every new reader on facts with and without the
engine's counters and programs."""
import os

import pytest

from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.harness.like_bytes import (MATCH_PROGRAMS, column_bytes,
                                          matched_bytes)
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

CELL = "tpch-sf10-chip1-orders.q13"
CONFIG = "tpch-sf10-chip1-orders"
OPS = "operator programs: exec/, ops/"
SCAN = "scan + staging: io/scan.py, columnar/batch.py"
KERNELS = "kernels: XLA programs"
Q13_METRICS = [
    ("like_s", "s", "lower", "device_trace", OPS),
    ("like_rows", "count", "higher", "program_counter", OPS),
    ("like_roofline", "%", "higher", "device_trace", KERNELS),
    ("string_stage_bytes", "bytes", "lower", "program_counter", SCAN),
    ("join_build_rows", "count", "lower", "program_counter", OPS),
]
COUNTER_METRICS = {"like_rows": "like.device.rows",
                   "string_stage_bytes": "scan.stage.string_bytes",
                   "join_build_rows": "join.build.rows"}


def _entry(entries, name):
    """The entry called ``name`` (a later PR appends after it)."""
    return next(e for e in entries if e["name"] == name)


def test_q13_cell_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    assert CELL == f"{CONFIG}.q13"
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "q13", 1)
    assert len(entry["why"]) <= 200
    cell = load_cell(CELL, root)
    conf_entry = _entry(bench["configs"], CONFIG)
    assert conf_entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf_entry["source"] == cell.config["source"]
    assert len(conf_entry["source"]) <= 200 and len(conf_entry["why"]) <= 200
    assert conf_entry["reduced"] == cell.config["reduced"] == ["scale_factor"]
    assert len({c["source"] for c in bench["configs"]}) \
        == len(bench["configs"])
    # a generator file of its own; the conf of the other one-chip cells
    assert cell.config["datagen"] == "tpch_orders"
    assert cell.dataset == "tpch-sf10"
    assert cell.config["conf"] \
        == load_cell("tpch-sf1-chip1.q18", root).config["conf"]
    assert cell.traffic == {**cell.traffic, "loop": "closed", "clients": 1,
                            "queries": ["q13"]}
    assert {"source", "suite", "datagen", "scale_factor", "chips", "conf",
            "guarantees", "reduced", "reduced_why", "assumed", "layout",
            "tables", "why"} <= set(cell.config)
    assert "on the device" in cell.config["guarantees"]["predicate"]
    query = load_module(root, "queries", "tpch_q13")
    assert query.TABLES == {"customer": ["c_custkey"],
                            "orders": ["o_orderkey", "o_custkey",
                                       "o_comment"]}
    assert (query.WORD1, query.WORD2) == ("special", "requests")
    load_module(root, "reference", "tpch_q13").rows
    load_module(root, "datagen", "tpch_orders").generate
    for m in cell.per_layer:
        load_module(root, "layer_metrics", m["name"]).read
    assert {m["name"] for m in cell.end_to_end} \
        == {"query_s", "rows_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, source, layer in Q13_METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, source, layer, "query_s",
                                    [CELL])
    # and no other cell reports them
    for other in bench["workloads"]:
        names = {m["name"] for m in load_cell(other["name"], root).per_layer}
        assert other is entry or not names & {m[0] for m in Q13_METRICS}


def test_q13_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    for m in bench["per_layer"]:
        if m["name"] in {q[0] for q in Q13_METRICS}:
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    # every new metric that needs no device plane is read: SF0.1 is
    # 150k orders, every one matched on the device, about 1.2 % of them
    # left out of the build
    assert got["like_rows"] == 150_000
    assert 146_000 < got["join_build_rows"] < 149_500
    # one raw 128-byte-wide matrix of 2^18 slots a collect
    assert got["string_stage_bytes"] == (1 << 18) * 128
    # XLA:CPU has no device plane: device seconds are not invented
    assert "like_s" not in got and "like_roofline" not in got
    # the footers of the run's data say what the match must read: 150k
    # comments of 19..78 bytes
    nbytes = matched_bytes(root, "tpch-sf0.1", {"orders": ["o_comment"]})
    assert 150_000 * 47 < nbytes < 150_000 * 50


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_with_and_without_the_counter(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    name = COUNTER_METRICS[metric]
    # an engine from before the counter: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a collect that did not move it counts as 0 in the mean
    assert read(_facts([{name: 6}, {"d2h_calls": 3}, {name: 3}])) == 3


def test_like_seconds_with_and_without_the_program():
    read = load_module(ROOT, "layer_metrics", "like_s").read
    ops = [("jit_string_match_stage", 1.5), ("jit_string_match_filter", 0.5),
           ("jit_fused_stage_body", 9.0)]
    assert set(MATCH_PROGRAMS) == {ops[0][0], ops[1][0]}
    assert read(_facts([{}, {}], ops, collects=2)) == 1.0
    assert read(_facts([{}], [("jit_fused_stage_body", 9.0)])) is None
    assert read(_facts([{}], [])) is None


def test_column_bytes_are_the_strings_own(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    base = tmp_path / ".bench_data" / "tpch-sf10"
    tdir = base / "seed7" / "orders"
    os.makedirs(tdir)
    vals = [f"comment {i:07d} " + "x" * (i % 50) for i in range(20_000)]
    want = sum(len(v) for v in vals)
    for part, lo in enumerate((0, 12_000)):
        pq.write_table(pa.table({"o_comment": vals[lo:lo + 12_000],
                                 "k": list(range(len(vals[lo:lo + 12_000])))}),
                       tdir / f"part-{part}.parquet", use_dictionary=False,
                       row_group_size=5_000)
    got = column_bytes(str(tdir), "o_comment")
    assert want <= got < want * 1.001
    assert matched_bytes(str(tmp_path), "tpch-sf10",
                         {"orders": ["o_comment"]}) == got
    # no data, or two seeds' data: nothing to read
    assert matched_bytes(str(tmp_path), "tpch-sf1", {"orders": []}) is None
    os.makedirs(base / "seed8")
    assert matched_bytes(str(tmp_path), "tpch-sf10",
                         {"orders": ["o_comment"]}) is None


def test_like_roofline_with_and_without_its_sources(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    read = load_module(ROOT, "layer_metrics", "like_roofline").read
    tdir = tmp_path / ".bench_data" / "tpch-sf10" / "seed7" / "orders"
    os.makedirs(tdir)
    pq.write_table(pa.table({"o_comment": ["x" * 50] * 10_000}),
                   tdir / "part-0.parquet", use_dictionary=False)
    nbytes = column_bytes(str(tdir), "o_comment")
    assert 500_000 <= nbytes < 500_500
    ops = [("jit_string_match_stage", 2.0), ("jit_other", 9.0)]

    def facts(device_ops, peaks=True, collects=2):
        f = _facts([{}] * collects, device_ops, collects=collects)
        f["counters"]["chips"] = 1
        f["peaks"] = {"hbm_bytes_per_s": 1e6} if peaks else None
        return f
    # two traced collects x 0.5 MB at 1 MB/s = 1 s of the program's 2 s
    assert read(facts(ops), root=str(tmp_path)) \
        == pytest.approx(100.0 * 2 * nbytes / 1e6 / 2.0)
    assert read(facts(ops, peaks=False), root=str(tmp_path)) is None
    assert read(facts([("jit_other", 9.0)]), root=str(tmp_path)) is None
    assert read(facts(ops), root=str(tmp_path / "nowhere")) is None
