"""PR 43's per-layer metric ``join_aligned_batches``: its entry in
``BENCHMARK.json`` and its reader on facts with and without the engine's
counter (an addition only: no cell, bound or harness file moves)."""
from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _facts, _run_both, _small_cell

METRIC = "join_aligned_batches"
CELLS = ["tpcds-sf10-chip1-returns.q93", "tpcds-sf10-chip1-cumulative.q51",
         "tpch-sf10-chip1-orders.q13"]


def test_metric_is_declared_as_the_issue_names_it(bench_copy):
    root, bench, _ = bench_copy
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter",
        "layer": "operator programs: exec/, ops/", "moves": "query_s",
        "workloads": CELLS}
    for other in bench["workloads"]:
        cell = load_cell(other["name"], root)
        names = {m["name"] for m in cell.per_layer}
        assert (METRIC in names) == (other["name"] in CELLS)
        # every cell that reports it reports the metric it moves
        assert METRIC not in names or "query_s" in {
            m["name"] for m in cell.end_to_end}


def test_reader_with_and_without_the_counter():
    read = load_module(ROOT, "layer_metrics", METRIC).read
    # no join ran, or an engine that counts no probes: left out
    assert read(_facts([{"d2h_calls": 3}] * 2)) is None
    # probes counted and none aligned (q13; the parent of PR 43): 0
    assert read(_facts([{"join.probe.direct": 2,
                         "join.probe.rows_out": 15_000_000}] * 2)) == 0
    # q93: the left join's 28 batches of 56 probed; a collect that moved
    # nothing counts as 0 in the mean
    q93 = {"join.probe.search": 28, "join.probe.direct": 28,
           "join.gather.aligned": 28}
    assert read(_facts([q93, q93])) == 28
    assert read(_facts([q93, {"join.probe.direct": 28}])) == 14


def test_q93_reports_it_at_cpu_scale(bench_copy):
    """Through ``harness.run``, traced, at SF0.1 (XLA:CPU: never a
    measurement): the left join's one stream batch hands on every sale
    once and counts, the semi-join's drops rows and does not."""
    _, bench, _ = bench_copy
    root, bench, save, name = _small_cell(
        bench_copy, "tpcds-sf10-chip1-returns", "q93")
    for m in bench["per_layer"]:
        if m["name"] in (METRIC, "join_search_batches", "join_rows_out"):
            m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    assert got[METRIC] == got["join_search_batches"] == 1
    assert got["join_rows_out"] >= 288_000
