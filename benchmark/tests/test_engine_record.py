"""The per-layer metrics that read the engine's own per-query record
(harness/engine_record.py), rehearsed on the CPU backend: every one but
the two roofline shares (XLA:CPU has no peaks and no device planes)
returns a number, and the cross-checks that need no chip hold."""
import json

import pytest

from benchmark.harness import run
from benchmark.harness.engine_record import (mean_per_collect,
                                             program_bytes, total,
                                             window_records)
from benchmark.tests.test_new_cell import _small_config

ENGINE_METRICS = ["engine_plan_s", "h2d_calls", "h2d_bytes",
                  "scan_decode_s", "scan_stage_s", "sync_calls",
                  "sync_wait_s", "engine_launches"]
ROOFLINES = ["launch_bytes_roofline", "top_program_roofline"]


def test_entries_are_additions_that_every_cell_reports(bench_copy):
    _, bench, _ = bench_copy
    by_name = {m["name"]: m for m in bench["per_layer"]}
    mine = set(ENGINE_METRICS + ROOFLINES)
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in mine}
    for name in ENGINE_METRICS + ROOFLINES:
        m = by_name[name]
        assert m["moves"] == "query_s" and "workloads" not in m
        assert m["layer"] in layers     # a layer the benchmark names
    # in this order, wherever later PRs' entries leave them
    assert [m["name"] for m in bench["per_layer"] if m["name"] in mine] == \
        ENGINE_METRICS + ROOFLINES


@pytest.mark.parametrize("of,traffic,sf", [
    ("tpcds-sf10-chip1", "q6", 0.1),
    ("tpch-sf1-chip1", "q1", 0.05),
])
def test_rehearsal_reports_the_engine_metrics(bench_copy, of, traffic, sf,
                                              capsys):
    root, bench, save = bench_copy
    bench["configs"].append(_small_config(root, "small", of, sf))
    name = f"small.{traffic}"
    bench["workloads"].append({"name": name, "config": "small",
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    save(bench)
    out = run(name, seed=7, seconds=1, trace=True, root=root,
              expect_platform="cpu")
    assert out["correct"] is True and out["failed"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for metric in ENGINE_METRICS:
        assert isinstance(got[metric], (int, float)), metric
    for metric in ROOFLINES:        # no peaks, no device planes on CPU
        assert metric not in got
    # a scan decodes, stages and ships; an aggregate fetches its counts
    assert got["h2d_calls"] >= 1 and got["h2d_bytes"] > 0
    assert got["scan_decode_s"] > 0 and got["scan_stage_s"] > 0
    assert got["sync_calls"] >= 1 and got["sync_wait_s"] > 0
    assert got["engine_launches"] >= 1
    # the harness's view from outside holds the engine's own
    assert 0 < got["engine_plan_s"] <= got["plan_s"] + 1e-3
    # XLA:CPU traces hold no device planes, so program_launches reads 0
    # there; on the chip engine_launches <= program_launches (PERF.md)
    facts = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    window = next(f for f in facts if f["phase"] == "window")
    assert got["sync_wait_s"] <= sum(window["seconds"])


def test_window_records_takes_the_newest_in_order():
    from spark_rapids_tpu.obs.registry import get_registry
    reg = get_registry()
    for i in range(5):
        reg.note_query({"query_id": str(i), "counters": {
            "span.decode@AScanExec.seconds": float(i),
            "span.decode@BScanExec.seconds": 1.0,
            "program.p.arg_bytes": 10 * i, "program.p.result_bytes": i,
            "program.q.arg_bytes": 1000}})
    facts = {"counters": {"traced_collect_seconds": [0.1],
                          "collect_seconds": [0.1, 0.1]}}
    traced, untraced = window_records(facts)
    assert [c["program.p.result_bytes"] for c in traced] == [2]
    assert [c["program.p.result_bytes"] for c in untraced] == [3, 4]
    assert total(traced[0], "span.decode@", ".seconds") == 3.0
    assert program_bytes(untraced[1], "p") == 44
    assert program_bytes(untraced[1]) == 1044
    assert mean_per_collect(facts, "span.decode@", ".seconds") == 4.0
    assert window_records({"counters": {"traced_collect_seconds": [],
                                        "collect_seconds": []}}) is None
