"""A later PR adds a cell as data: a ``workloads`` entry and new files,
no edit to a file that is there.  Here one throw-away cell made of a new
datagen, query, reference, configuration, traffic mix and per-layer
metric, and small-scale configurations of the real queries, run through
``harness.run`` on the CPU backend (a rehearsal, never a measurement).
"""
import json
import os

import pytest

from benchmark.harness import run
from benchmark.harness.cell import load_cell
from benchmark.harness.runner import BenchError

NEW_FILES = {
    "benchmark/datagen/toy.py": '''
import os
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


def generate(data_dir, sf, seed, tables):
    rng = np.random.default_rng(seed)
    n = int(1000 * sf)
    os.makedirs(os.path.join(data_dir, "t"), exist_ok=True)
    pq.write_table(pa.table({
        "k": rng.integers(0, 5, n).astype(np.int32),
        "v": rng.uniform(0, 1, n)}),
        os.path.join(data_dir, "t", "part-0.parquet"))
    return {"t": n}
''',
    "benchmark/queries/toy_sums.py": '''
import os
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col

TABLES = {"t": ["k", "v"]}


def build(session, data_dir):
    return session.read_parquet(os.path.join(data_dir, "t"),
                                columns=TABLES["t"]) \\
        .group_by("k").agg(Sum(col("v")).alias("s"))
''',
    "benchmark/reference/toy_sums.py": '''
import os
import pandas as pd


def rows(data_dir):
    t = pd.read_parquet(os.path.join(data_dir, "t"))
    return [(int(k), float(s)) for k, s in t.groupby("k").v.sum().items()]
''',
    "benchmark/configs/toy-sf2.json": json.dumps({
        "source": "none: a test's throw-away deployment",
        "suite": "toy", "datagen": "toy", "scale_factor": 2, "chips": 1,
        "conf": {"spark.rapids.sql.test.enabled": "true",
                 "spark.rapids.sql.resultCache.enabled": "false"}}),
    "benchmark/traffic/toy/sums.json": json.dumps({
        "loop": "closed", "clients": 1, "queries": ["sums"]}),
    "benchmark/layer_metrics/toy_collects.py": '''
def read(facts):
    return len(facts["counters"]["collect_seconds"])
''',
}


def _add_files(root, files):
    for rel, text in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            f.write(text)


def _small_config(root, name, of, sf):
    """A configuration file of its own for ``of`` at a scale the CPU can
    run: new data again, no code."""
    with open(os.path.join(root, "benchmark", "configs", of + ".json")) as f:
        config = json.load(f)
    config["scale_factor"] = sf
    _add_files(root, {f"benchmark/configs/{name}.json": json.dumps(config)})
    return {"name": name, "source": "test", "reduced": ["scale_factor"],
            "file": f"benchmark/configs/{name}.json", "why": "test"}


def _result_ok(out, cell, traced):
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device"} | ({"breakdown"} if traced else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    names = {m["name"] for m in
             (cell.per_layer if traced else cell.end_to_end)}
    assert set(out["metrics"]) <= names
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))
    json.dumps(out)


def test_throwaway_cell_from_new_files_only(bench_copy, capsys):
    root, bench, save = bench_copy
    _add_files(root, NEW_FILES)
    bench["configs"].append({
        "name": "toy-sf2", "source": "test", "reduced": [],
        "file": "benchmark/configs/toy-sf2.json", "why": "test"})
    bench["workloads"].append({
        "name": "toy-sf2.sums", "config": "toy-sf2", "traffic": "sums",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "toy_collects", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "rows_per_s", "workloads": ["toy-sf2.sums"]})
    save(bench)

    cell = load_cell("toy-sf2.sums", root)
    assert cell.dataset == "toy-sf2" and cell.chips == 1
    assert "toy_collects" in {m["name"] for m in cell.per_layer}
    assert "collective_s" not in {m["name"] for m in cell.per_layer}

    out = run("toy-sf2.sums", seed=3, seconds=0.5, trace=False, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=False)
    assert set(out["metrics"]) == {"query_s", "rows_per_s", "setup_s"}

    out = run("toy-sf2.sums", seed=3, seconds=0.5, trace=True, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=True)
    assert out["metrics"]["toy_collects"]["value"] >= 0
    assert out["metrics"]["window_compiles"]["value"] == 0
    # the second run found this seed's data and reference rows
    assert out["metrics"]["gen_s"]["value"] < 1.0
    # the XLA:CPU trace has host planes only: annotations are read,
    # device numbers are not invented
    assert "device_busy_s" not in out["metrics"]
    assert out["metrics"]["plan_s"]["value"] > 0
    facts = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [f["phase"] for f in facts][:3] == ["device", "data", "reference"]


def test_wrong_device_fails_before_data(bench_copy):
    root, _, _ = bench_copy
    with pytest.raises(BenchError, match="platform is 'cpu'"):
        run("tpch-sf1-chip1.q1", seed=1, seconds=1, trace=False, root=root)
    assert not os.path.exists(os.path.join(root, ".bench_data"))


@pytest.mark.parametrize("of,traffic,sf,chips", [
    ("tpch-sf1-chip1", "q1", 0.05, 1),
    ("tpch-sf1-chip1", "q6", 0.05, 1),
    ("tpcds-sf10-chip1", "q6", 0.1, 1),
    ("tpcds-sf1-mesh4", "q6", 0.1, 4),
])
def test_real_queries_at_cpu_scale(bench_copy, of, traffic, sf, chips):
    root, bench, save = bench_copy
    bench["configs"].append(_small_config(root, "small", of, sf))
    name = f"small.{traffic}"
    bench["workloads"].append({"name": name, "config": "small",
                               "traffic": traffic, "chips": chips,
                               "why": "test"})
    for m in bench["per_layer"]:
        if "workloads" in m and chips == 4:
            m["workloads"].append(name)
    save(bench)
    cell = load_cell(name, root)
    out = run(name, seed=5, seconds=1, trace=True, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=True)
    out = run(name, seed=5, seconds=1, trace=False, root=root,
              expect_platform="cpu")
    _result_ok(out, cell, traced=False)
