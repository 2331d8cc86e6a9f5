"""The six readers of the scan pipeline's waits and of the host's seconds
inside a put and a launch (PR 36), on hand-made records of the engine's
ring: a float where the record holds the counter, 0.0 where it does not
(a commit from before the counter: the metric reads nothing, it does not
fail), None only where the engine keeps no record of the window."""
import json
import os

import pytest

from benchmark.harness.cell import ROOT, load_module

READS = {
    # metric -> (counters of one collect, what it reads from them)
    "scan_starved_s": ({"span.starved@ParquetScanExec.seconds": 0.5,
                        "span.starved@OrcScanExec.seconds": 0.25,
                        "span.starved@ParquetScanExec.count": 9}, 0.75),
    "scan_backpressure_s": ({"scan_backpressure_s": 1.5}, 1.5),
    "scan_wait_s": ({"scan.wait_s": 2.0, "scan.first_batch_s": 0.5}, 2.0),
    "scan_first_batch_s": ({"scan.wait_s": 2.0, "scan.first_batch_s": 0.5,
                            "scan.pipelines": 4}, 0.5),
    "h2d_put_s": ({"h2d_put_s": 0.125, "h2d_calls": 3, "h2d_bytes": 4096},
                  0.125),
    "engine_dispatch_s": ({"program.batch_unpack.dispatch_s": 0.25,
                           "program.agg_update.dispatch_s": 0.5,
                           "program.agg_update.launches": 7}, 0.75),
}


def _facts(traced: int, untraced: int) -> dict:
    return {"counters": {"traced_collect_seconds": [0.1] * traced,
                         "collect_seconds": [0.1] * untraced}}


def _note(*collects: dict) -> None:
    from spark_rapids_tpu.obs.registry import get_registry
    for i, counters in enumerate(collects):
        get_registry().note_query({"query_id": str(i), "counters": counters})


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_means_its_counter_over_the_windows_collects(metric):
    read = load_module(ROOT, "layer_metrics", metric).read
    counters, value = READS[metric]
    # an older record, then the window: one traced and two untraced
    # collects, of which one moved nothing this reader reads
    _note({k: 100.0 for k in counters}, counters, {"sync_wait_s": 1.0},
          counters)
    got = read(_facts(1, 2))
    assert isinstance(got, float) and got == pytest.approx(value * 2 / 3)
    # a window whose records hold none of it: nothing to read is 0.0
    _note({"sync_wait_s": 1.0}, {"h2d_bytes": 5})
    assert read(_facts(0, 2)) == 0.0
    # no collect, no record: the metric is left out of the line
    assert read(_facts(0, 0)) is None


def test_entries_are_under_the_layers_perf_md_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    layers = {m["name"]: m["layer"] for m in per_layer}
    # found by name: later PRs append their own entries behind these
    assert set(READS) <= set(layers)
    for m in (m for m in per_layer if m["name"] in READS):
        assert m["moves"] == "query_s" and "workloads" not in m
        assert m["unit"] == "s" and m["better"] == "lower"
        twin = "engine_launches" if m["name"] == "engine_dispatch_s" \
            else "scan_stage_s"
        assert m["layer"] == layers[twin]
        assert m["source"] == ("program_span" if m["name"] ==
                               "scan_starved_s" else "program_counter")
