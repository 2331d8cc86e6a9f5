"""BENCHMARK.json against the rules a driver refuses a benchmark by,
as far as a file can show them, and every name resolving to its file."""
import json
import os
import re

from benchmark.harness.cell import ROOT, load_cell, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units_and_limits():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert all(1 <= len(c[k]) <= 200 for k in ("source", "why"))
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    layers = set()
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        layers.add(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # the layers are PERF.md's, letter for letter
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, layer


def test_every_name_resolves_to_its_files():
    b = _bench()
    for w in b["workloads"]:
        cell = load_cell(w["name"])
        # a cell is found by its name; the name need not spell its
        # configuration (tpch-sf1-chip1.q18 is of tpch-sf1-chip1-q18)
        assert cell.name == w["name"] and cell.name.endswith(
            "." + w["traffic"])
        assert {"source", "suite", "datagen", "scale_factor", "chips",
                "conf", "guarantees", "reduced", "assumed"} <= set(cell.config)
        assert {"setup_s", "query_s"} <= {m["name"] for m in cell.end_to_end}
        assert cell.traffic["loop"] == "closed"
        load_module(ROOT, "datagen", cell.config["datagen"]).generate
        for q in cell.traffic["queries"]:
            assert load_module(ROOT, "queries", f"{cell.suite}_{q}").TABLES
            load_module(ROOT, "reference", f"{cell.suite}_{q}").rows
        for m in cell.per_layer:
            load_module(ROOT, "layer_metrics", m["name"]).read
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}


def test_nothing_outside_tests_imports_the_repos_bench_package():
    bench_dir = os.path.join(ROOT, "benchmark")
    for dirpath, _, files in os.walk(bench_dir):
        if os.path.relpath(dirpath, bench_dir).startswith("tests"):
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                text = f.read()
            assert not re.search(r"^\s*(from|import) spark_rapids_tpu\.bench",
                                 text, re.M), name
            if os.path.basename(dirpath) == "reference":
                assert not re.search(r"^\s*(from|import) spark_rapids_tpu",
                                     text, re.M), name
