"""PR 31's cell ``tpch-sf1-chip1.q18``, rehearsed on the CPU backend
(never a measurement) at SF0.1 through ``harness.run``, traced and
untraced, and every new reader on facts with and without the engine's
counters."""
import pytest

from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

CELL = "tpch-sf1-chip1.q18"
#: the driver takes a ``model_config`` PR only with a configuration of its
#: own, so the cell keeps the issue's name and runs the deployment that Q18
#: defines: q1's configuration with ``orders`` and ``customer`` beside it
CONFIG = "tpch-sf1-chip1-q18"
OPS = "operator programs: exec/, ops/"
Q18_METRICS = [
    ("agg_update_s", "s", "lower", "device_trace"),
    ("agg_groups", "count", "higher", "program_counter"),
    ("join_build_s", "s", "lower", "device_trace"),
    ("join_probe_s", "s", "lower", "device_trace"),
    ("semi_join_batches", "count", "lower", "program_counter"),
]
COUNTER_METRICS = {"agg_groups": "agg.update.groups",
                   "semi_join_batches": "join.semi.batches"}
TRACE_METRICS = {
    "agg_update_s": ("jit_agg_update",),
    "join_build_s": ("jit_join_build_prep", "jit_join_build_table"),
    "join_probe_s": ("jit_join_probe_direct", "jit_join_gather")}


def _entry(entries, name):
    """The entry called ``name``, wherever later PRs' entries left it."""
    return next(e for e in entries if e["name"] == name)


def test_q18_cell_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    assert entry["config"] == CONFIG
    assert (entry["traffic"], entry["chips"]) == ("q18", 1)
    cell = load_cell(CELL, root)
    conf_entry = _entry(bench["configs"], CONFIG)
    assert conf_entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf_entry["source"] == cell.config["source"]
    assert conf_entry["reduced"] == cell.config["reduced"] == ["scale_factor"]
    assert len({c["source"] for c in bench["configs"]}) \
        == len(bench["configs"])
    # q1's files, seed for seed: one suite, one scale, one session conf
    q1 = load_cell("tpch-sf1-chip1.q1", root)
    assert cell.dataset == q1.dataset and cell.config["conf"] \
        == q1.config["conf"]
    assert cell.traffic == {**cell.traffic, "loop": "closed", "clients": 1,
                            "queries": ["q18"]}
    # what test_contract.py checks of a cell after its name
    assert {"source", "suite", "datagen", "scale_factor", "chips", "conf",
            "guarantees", "reduced", "assumed"} <= set(cell.config)
    assert load_module(root, "queries", "tpch_q18").TABLES
    load_module(root, "reference", "tpch_q18").rows
    for m in cell.per_layer:
        load_module(root, "layer_metrics", m["name"]).read
    assert {m["name"] for m in cell.end_to_end} \
        == {"query_s", "rows_per_s", "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, source in Q18_METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
                m["workloads"]) == (unit, better, source, OPS, "query_s",
                                    [CELL])
    # and no other cell reports them
    for other in bench["workloads"]:
        names = {m["name"] for m in load_cell(other["name"], root).per_layer}
        assert other is entry or not names & {m[0] for m in Q18_METRICS}


def test_q18_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["workloads"], CELL)
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    for m in bench["per_layer"]:
        if m["name"] in {q[0] for q in Q18_METRICS}:
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    # SF0.1: 600k lineitem rows in about 150k orders, one lineitem batch
    assert 140_000 < got["agg_groups"] < 160_000
    assert got["semi_join_batches"] == 1
    # lineitem's one batch staged once, handed to both of its readers
    assert got["h2d_calls"] >= 3 and got["sync_calls"] >= 3
    # XLA:CPU has no device plane: device seconds are not invented
    assert not set(TRACE_METRICS) & set(got)


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_with_and_without_the_counter(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    name = COUNTER_METRICS[metric]
    # an engine from before the counter: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a collect that did not move it counts as 0 in the mean
    assert read(_facts([{name: 6}, {"d2h_calls": 3}, {name: 3}])) == 3


@pytest.mark.parametrize("metric", sorted(TRACE_METRICS))
def test_trace_reader_with_and_without_its_programs(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    ops = [(p, 1.5) for p in TRACE_METRICS[metric]] + [("jit_other", 9.0)]
    assert read(_facts([{}, {}], ops, collects=2)) == \
        1.5 * len(TRACE_METRICS[metric]) / 2
    assert read(_facts([{}], [("jit_other", 9.0)])) is None
    assert read(_facts([{}], [])) is None
