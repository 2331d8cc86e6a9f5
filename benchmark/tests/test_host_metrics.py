"""PR 41's four per-layer metrics of what the host was doing around a
window (``collect_iqr_s``, ``host_cpu_s``, ``host_disk_read_bytes``,
``host_load``): the readers on hand-made facts, ``harness/host.py`` with
and without the system's files, and a CPU rehearsal (never a
measurement) in which a traced run reports all four and an untraced
run prints them in its ``window`` fact."""
import json
import statistics

import pytest

from benchmark.harness import host, run
from benchmark.harness.cell import ROOT, load_cell, load_module
from benchmark.tests.test_cells_pr27 import _small_cell

HOST = "host: the machine under the run"
METRICS = {
    # name -> (unit, source, layer)
    "collect_iqr_s": ("s", "host_clock",
                      "session + planner: session.py, plan/"),
    "host_cpu_s": ("s", "host_clock", HOST),
    "host_disk_read_bytes": ("bytes", "program_counter", HOST),
    "host_load": ("count", "program_counter", HOST),
}


def test_entries_are_found_by_name_and_every_cell_reports_them():
    with open(f"{ROOT}/BENCHMARK.json") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, source, layer) in METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["moves"],
                m["better"]) == (unit, source, layer, "query_s", "lower")
        assert "workloads" not in m
        load_module(ROOT, "layer_metrics", name).read
    for w in bench["workloads"]:
        assert set(METRICS) <= {m["name"] for m in
                                load_cell(w["name"]).per_layer}


def test_quartile_distance_is_the_drivers_spread_before_the_division():
    quartile_distance = load_module(ROOT, "layer_metrics",
                                    "collect_iqr_s").quartile_distance
    two_modes = [5.1, 5.2, 5.0, 5.1, 6.3, 6.2, 6.4, 6.3]
    q1, _, q3 = statistics.quantiles(two_modes, n=4)
    assert quartile_distance(two_modes) == q3 - q1 > 1.0
    assert quartile_distance([5.0] * 8) == 0.0
    assert quartile_distance([5.0]) is None and quartile_distance([]) is None
    read = load_module(ROOT, "layer_metrics", "collect_iqr_s").read
    assert read({"counters": {"collect_seconds": two_modes}}) == q3 - q1
    assert read({"counters": {"collect_seconds": [1.0]}}) is None


@pytest.mark.parametrize("metric", ["host_cpu_s", "host_disk_read_bytes",
                                    "host_load"])
def test_host_reader_hands_on_the_runners_counter_or_nothing(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    assert read({"counters": {metric: 1.5}}) == 1.5
    assert read({"counters": {metric: None}}) is None
    assert read({"counters": {}}) is None


def test_snapshot_differences_and_a_system_without_proc(monkeypatch):
    before = host.snapshot()
    sum(i * i for i in range(200_000))      # burn some CPU
    after = host.snapshot()
    moved = host.moved(before, after)
    assert moved["cpu_s"] > 0
    assert moved["disk_read_bytes"] is None or moved["disk_read_bytes"] >= 0
    assert host.cores() >= 1 and host.load_per_core() >= 0
    # no /proc/self/io: the reading is None, never 0, and so is a difference
    def no_proc(path):
        raise FileNotFoundError(path)
    monkeypatch.setattr("builtins.open", no_proc)
    bare = host.snapshot()
    monkeypatch.undo()
    assert bare["disk_read_bytes"] is None
    assert host.moved(before, bare)["disk_read_bytes"] is None
    assert host.moved(before, bare)["cpu_s"] >= 0


def _facts_printed(capsys) -> list:
    return [json.loads(line) for line in
            capsys.readouterr().out.splitlines()]


def _fact(capsys, phase: str) -> dict:
    return next(f for f in _facts_printed(capsys) if f.get("phase") == phase)


def test_rehearsal_reports_the_four_and_prints_them_untraced(bench_copy,
                                                            capsys):
    root, bench, save, name = _small_cell(bench_copy, "tpcds-sf1-chip1",
                                          "q6")
    save(bench)
    out = run(name, seed=2**31 + 41, seconds=2, trace=True, root=root,
              expect_platform="cpu")
    assert out["correct"] is True
    got = {k: v["value"] for k, v in out["metrics"].items()}
    window = _fact(capsys, "window")
    # the traced collects, then the untraced ones: two make a distance
    assert ("collect_iqr_s" in got) == (
        window["collects"] - window["traced"] >= 2)
    assert got.get("collect_iqr_s", 0) >= 0 and got["host_cpu_s"] > 0
    assert got["host_load"] >= 0
    assert got.get("host_disk_read_bytes", 0) >= 0
    out = run(name, seed=2**31 + 41, seconds=1, trace=False, root=root,
              expect_platform="cpu")
    assert set(out["metrics"]) == {"query_s", "rows_per_s", "setup_s"}
    facts = _facts_printed(capsys)
    window = next(f for f in facts if f.get("phase") == "window")
    assert (window["collect_iqr_s"] is None) == (window["collects"] < 2)
    assert (window["collect_iqr_s"] or 0) >= 0 and window["host_cpu_s"] > 0
    assert window["host_load"] >= 0 and window["cores"] >= 1
    # the second run of the seed made its data anew, as the first did
    data = next(f for f in facts if f.get("phase") == "data")
    assert data["gen_s"] > 0.05
    assert window["memory_pool"] == host.memory_pool()


def test_run_py_starts_anew_under_the_process_env_once():
    """benchmark/process_env.json is every cell's: run.py hands on the
    environment to start under, the first start's clock reading with
    it, and nothing where the variables are already as the file says."""
    run_py = load_module(ROOT, "", "run")
    with open(f"{ROOT}/benchmark/process_env.json") as f:
        want = json.load(f)["env"]
    assert want["ARROW_DEFAULT_MEMORY_POOL"] == "system"
    assert all(isinstance(v, str) for v in want.values())
    env = run_py.under_process_env({"HOME": "/h", "BENCH_RUN": "7"}, 12.5)
    assert env == {"HOME": "/h", "BENCH_RUN": "7", **want,
                   run_py.STARTED_AT: "12.5"}
    assert run_py.under_process_env(env, 99.0) is None
    # a variable set to something else is set right, not kept
    other = {**env, "MALLOC_TOP_PAD_": "0"}
    assert run_py.under_process_env(other, 1.0)["MALLOC_TOP_PAD_"] == \
        want["MALLOC_TOP_PAD_"]
