"""The trace reduction against numbers worked out by hand: on a
hand-made two-thread trace (every rule, collectives included) and on
slices of traces recorded on the chip (tests/data, trimmed by
tests/trim_trace.py)."""
import json
import os

import pytest

from benchmark.harness import reduce_trace as rt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000.0     # the trace's unit is the nanosecond


def _ev(name, start_us, dur_us):
    return [name, start_us * US, dur_us * US]


@pytest.fixture
def handmade():
    """One device, a puller thread and a worker thread (times in us):

    device ops   |100 fusion.1 120| |110 fusion.2 150| |300 all-gather.3
                 330| |350 fusion.4 400| |700 fusion.1 750|
    modules      jit_a 100-150, jit_b 300-400, jit_a 700-750
    puller       bench.collect 0-500 { AExec 50-450 { ParquetScanExec
                 60-160, 200-250 } }, bench.collect 600-900 { AExec 650-850 }
    worker       BExec 320-340
    """
    planes = {"plane_names": ["/device:TPU:0", "/host:CPU"], "devices": [{
        "name": "/device:TPU:0",
        "modules": [_ev("jit_a(1)", 100, 50), _ev("jit_b(2)", 300, 100),
                    _ev("jit_a(1)", 700, 50)],
        "ops": [_ev("fusion.1", 100, 20), _ev("fusion.2", 110, 40),
                _ev("all-gather.3", 300, 30), _ev("fusion.4", 350, 50),
                _ev("fusion.1", 700, 50)]}],
        "host": [
            {"line": "puller", "events": [
                _ev("bench.collect", 0, 500), _ev("AExec", 50, 400),
                _ev("ParquetScanExec", 60, 100),
                _ev("ParquetScanExec", 200, 50),
                _ev("bench.collect", 600, 300), _ev("AExec", 650, 200)]},
            {"line": "worker", "events": [_ev("BExec", 320, 20)]}]}
    return planes


def test_pieces():
    assert rt.union([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    assert rt.clipped([[1, 4], [5, 8]], 3, 6) == 2
    assert rt.module_name("jit_update(123456)") == "jit_update"
    assert rt.module_name("jit_f(x)") == "jit_f(x)"
    # a parent's self time is its span minus its children's, not its
    # grandchildren's twice
    events = [["a", 0, 100], ["b", 10, 50], ["c", 20, 10], ["b", 70, 10]]
    assert sorted(rt.self_times(events)) == [
        ["a", 0, 40], ["b", 10, 40], ["b", 70, 10], ["c", 20, 10]]
    times, labels = rt.innermost_timeline(events)
    assert list(zip(times, labels)) == [
        (0, "a"), (10, "b"), (20, "c"), (30, "b"), (60, "a"), (70, "b"),
        (80, "a"), (100, None)]


def test_handmade_trace_by_hand(handmade, monkeypatch):
    monkeypatch.setattr(rt, "GAP_PIECE_NS", 10 * US)
    r = rt.reduce(handmade, 1)
    us = 1e-6
    # busy union: [100,150] + [300,330] + [350,400] + [700,750] = 180 us
    # of the 900 us from the first collect's start to the last one's end
    assert r["window_s"] == pytest.approx(900 * us)
    assert r["busy_s"] == pytest.approx(180 * us)
    assert r["devices"][0]["idle_pct"] == pytest.approx(80.0)
    first, second = r["collects"]
    assert first["seconds"] == pytest.approx(500 * us)
    assert first["device_busy_s"] == [pytest.approx(130 * us)]
    assert second["device_busy_s"] == [pytest.approx(50 * us)]
    assert (first["program_launches"], second["program_launches"]) == (2, 1)
    assert first["collective_s"] == pytest.approx(30 * us)
    assert second["collective_s"] == 0
    # plan_s: collect start -> first operator annotation
    assert first["plan_s"] == pytest.approx(50 * us)
    assert second["plan_s"] == pytest.approx(50 * us)
    # self time: AExec 400 - (100 + 50); the worker's BExec counts in the
    # collect it started in
    assert first["op_self_s"] == {
        "AExec": pytest.approx(250 * us),
        "ParquetScanExec": pytest.approx(150 * us),
        "BExec": pytest.approx(20 * us)}
    assert second["op_self_s"] == {"AExec": pytest.approx(200 * us)}
    assert r["device_ops"] == [["jit_a", pytest.approx(100 * us)],
                               ["jit_b", pytest.approx(100 * us)]]
    # idle gaps [0,100] [150,300] [330,350] [400,700] [750,900], named in
    # 10 us pieces by the innermost annotation (latest-entered thread):
    #   AExec 10+40+50+10+50+50+100, no_annotation 50+50+50+50,
    #   ParquetScanExec 40+10+50, between_collects 100, BExec 10
    assert dict(r["idle_gaps"]) == {
        "AExec": pytest.approx(310 * us),
        "no_annotation": pytest.approx(200 * us),
        "ParquetScanExec": pytest.approx(100 * us),
        "between_collects": pytest.approx(100 * us),
        "BExec": pytest.approx(10 * us)}
    assert [k for k, _ in r["idle_gaps"]][:2] == ["AExec", "no_annotation"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_two_devices_average_and_no_collect_is_an_error(handmade):
    second = dict(handmade["devices"][0], name="/device:TPU:1",
                  ops=[_ev("fusion.9", 0, 450)])
    handmade["devices"].append(second)
    r = rt.reduce(handmade, 2)
    assert [d["idle_pct"] for d in r["devices"]] == [
        pytest.approx(80.0), pytest.approx(50.0)]
    assert r["busy_s"] == pytest.approx((180 + 450) / 2 * 1e-6)
    assert r["collects"][0]["device_busy_s"] == [
        pytest.approx(130e-6), pytest.approx(450e-6)]
    handmade["host"] = [{"line": "t", "events": [_ev("AExec", 0, 5)]}]
    with pytest.raises(ValueError, match="no bench.collect"):
        rt.reduce(handmade, 1)


def _slice(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def test_recorded_q1_slice_by_hand():
    """125 us of a traced TPC-H Q1 collect on the chip (TPU v5 lite, PR
    23), 3.957 s in: between two batches the engine launches eleven tiny
    programs (six ``iota``, five ``less``), one operation each, while
    the puller sits in CoalesceBatchesExec under HashAggregateExec under
    SortExec.  Sums of the file's own numbers, in ns:

    ops      iota 7246+7303+7187+7188+7252+7161 = 43337,
             compare 8292+8338+8270+8286+8276 = 41462; none overlap
    modules  jit_iota 7247+7306+7188+7192+7255+7164 = 43352,
             jit_less 8646+8692+8623+8657+8628 = 43246
    """
    r = rt.reduce(_slice("tpch_q1_slice.json"), 1)
    busy_ns, window_ns = 43337 + 41462, 125000
    assert r["window_s"] == pytest.approx(window_ns * 1e-9)
    assert r["busy_s"] == pytest.approx(busy_ns * 1e-9)
    assert r["devices"][0]["plane"] == "/device:TPU:0"
    assert r["devices"][0]["idle_pct"] == pytest.approx(
        100 * (1 - busy_ns / window_ns))        # 32.16 %
    (collect,) = r["collects"]
    assert collect["program_launches"] == 11
    assert collect["device_busy_s"] == [pytest.approx(busy_ns * 1e-9)]
    assert collect["collective_s"] == 0
    assert collect["plan_s"] == 0       # the slice opens inside the plan
    assert collect["op_self_s"] == {
        "SortExec": 0, "HashAggregateExec": 0,
        "CoalesceBatchesExec": pytest.approx(window_ns * 1e-9)}
    assert r["device_ops"] == [["jit_iota", pytest.approx(43352e-9)],
                               ["jit_less", pytest.approx(43246e-9)]]
    assert r["idle_gaps"] == [
        ["CoalesceBatchesExec", pytest.approx((window_ns - busy_ns) * 1e-9)]]


def test_recorded_mesh_slice_by_hand():
    """23 us of a traced TPC-DS q6 collect over the four-chip mesh (PR
    23), 0.87 s in, inside the region's ``prog``: 21 operations a device,
    two of them all-reduces, none overlapping.  Device 0, in ns: the two
    all-reduces 4483 + 2638 = 7121; all 21 durations add to 16763 of the
    23000, so 6237 idle, all of it under MeshRegionExec (the innermost
    annotation open on each of the three host threads)."""
    planes = _slice("mesh_q6_slice.json")
    assert [d["name"] for d in planes["devices"]] == [
        f"/device:TPU:{i}" for i in range(4)]
    r = rt.reduce(planes, 4)
    (collect,) = r["collects"]
    assert collect["collective_s"] == pytest.approx(7121e-9)
    assert collect["program_launches"] == 0     # prog began before the slice
    assert collect["device_busy_s"][0] == pytest.approx(16763e-9)
    assert r["devices"][0]["idle_pct"] == pytest.approx(
        100 * 6237 / 23000)
    assert r["idle_gaps"] == [["MeshRegionExec", pytest.approx(6237e-9)]]
    # no operation overlaps another on any device, so busy is the plain sum
    sums = [sum(du for _, _, du in d["ops"]) * 1e-9
            for d in planes["devices"]]
    assert collect["device_busy_s"] == [pytest.approx(s) for s in sums]
    assert r["busy_s"] == pytest.approx(sum(sums) / 4)
