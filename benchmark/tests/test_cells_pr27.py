"""PR 27's cells ``tpcds-sf10-chip1.q44`` and ``tpcds-sf1-chip1.q6``,
rehearsed on the CPU backend (never a measurement) at SF0.1 through
``harness.run``, traced and untraced, and every new reader on facts
with and without the engine's counters."""
import pytest

from benchmark.harness import run
from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_new_cell import _result_ok, _small_config

SCAN = "scan + staging: io/scan.py, columnar/batch.py"
OPS = "operator programs: exec/, ops/"
#: q44's own metrics, and filter_s which it shares with TPC-H q1
Q44_METRICS = [
    ("shared_scan_replays", "count", "lower", "program_counter", SCAN),
    ("shared_scan_parked_bytes", "bytes", "lower", "program_counter", SCAN),
    ("filter_s", "s", "lower", "device_trace", OPS),
    ("agg_sorted_updates", "count", "higher", "program_counter", OPS),
    ("window_s", "s", "lower", "device_trace", OPS),
]
COUNTER_METRICS = {"shared_scan_parked_bytes": "scan.shared.parked_bytes",
                   "agg_sorted_updates": "agg.update.sorted"}
TRACE_METRICS = {"filter_s": ("jit_filter_batch", "jit_batch_shrink"),
                 "window_s": ("jit_window_frame",)}


def _small_cell(bench_copy, config, traffic):
    """``small.<traffic>``: ``config`` at SF0.1 in the test's copy."""
    root, bench, save = bench_copy
    bench["configs"].append(_small_config(root, "small", config, 0.1))
    name = f"small.{traffic}"
    bench["workloads"].append({"name": name, "config": "small",
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    return root, bench, save, name


def _run_both(root, name):
    cell = load_cell(name, root)
    out = run(name, seed=2**31 + 11, seconds=1, trace=True, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=True)
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["window_compiles"] == 0
    out = run(name, seed=2**31 + 11, seconds=1, trace=False, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=False)
    assert set(out["metrics"]) == {"query_s", "rows_per_s", "setup_s"}
    return got


def test_short_q6_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = next(w for w in bench["workloads"]
                 if w["name"] == "tpcds-sf1-chip1.q6")
    assert entry["chips"] == 1 and entry["traffic"] == "q6"
    root, bench, save, name = _small_cell(bench_copy, entry["config"], "q6")
    save(bench)
    got = _run_both(root, name)
    assert not {m[0] for m in Q44_METRICS} & set(got)


def test_q44_cell_at_cpu_scale(bench_copy):
    _, bench, _ = bench_copy
    entry = next(w for w in bench["workloads"]
                 if w["name"] == "tpcds-sf10-chip1.q44")
    assert entry["chips"] == 1 and entry["config"] == "tpcds-sf10-chip1"
    root, bench, save, name = _small_cell(bench_copy, entry["config"],
                                          entry["traffic"])
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric, unit, better, source, layer in Q44_METRICS:
        m = by_name[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer, "query_s")
        assert entry["name"] in m["workloads"]
        m["workloads"].append(name)
    save(bench)
    got = _run_both(root, name)
    # one store_sales batch and one item batch at this scale: three and
    # one consumers after the first
    assert got["shared_scan_replays"] == 4
    assert got["shared_scan_parked_bytes"] > 0
    assert got["agg_sorted_updates"] >= 1
    # XLA:CPU has no device plane: device seconds are not invented
    assert "filter_s" not in got and "window_s" not in got


def _facts(records, device_ops=(), collects=1):
    """Facts as the runner hands them to a reader, with the engine's
    newest query records replaced by ``records``."""
    from spark_rapids_tpu.obs.registry import get_registry
    for i, counters in enumerate(records):
        get_registry().note_query({"query_id": f"t{i}",
                                   "counters": counters})
    return {"trace": {"device_ops": [list(op) for op in device_ops],
                      "collects": [{}] * collects},
            "counters": {"traced_collect_seconds": [0.1] * collects,
                         "collect_seconds":
                             [0.1] * (len(records) - collects)},
            "peaks": None}


@pytest.mark.parametrize("metric", sorted(COUNTER_METRICS))
def test_counter_reader_with_and_without_the_counter(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    name = COUNTER_METRICS[metric]
    # an engine from before the counter: the metric is left out
    assert read(_facts([{"d2h_calls": 3}, {"d2h_calls": 3}])) is None
    # a collect that did not move it counts as 0 in the mean
    assert read(_facts([{name: 6}, {"d2h_calls": 3}, {name: 3}])) == 3


def test_replays_are_handed_less_staged():
    read = load_module(ROOT, "layer_metrics", "shared_scan_replays").read
    assert read(_facts([{"d2h_calls": 3}])) is None
    assert read(_facts([{"scan.shared.staged_batches": 3}])) is None
    both = {"scan.shared.staged_batches": 3, "scan.shared.handed_batches": 9}
    assert read(_facts([both, {"d2h_calls": 3}, both])) == 4


@pytest.mark.parametrize("metric", sorted(TRACE_METRICS))
def test_trace_reader_with_and_without_its_programs(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    ops = [(p, 1.5) for p in TRACE_METRICS[metric]] + [("jit_other", 9.0)]
    assert read(_facts([{}, {}], ops, collects=2)) == \
        1.5 * len(TRACE_METRICS[metric]) / 2
    assert read(_facts([{}], [("jit_other", 9.0)])) is None
    assert read(_facts([{}], [])) is None
