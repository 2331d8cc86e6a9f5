"""PR 39's reader ``unpack_s``: declared as an addition under the scan
layer's name with no ``workloads`` list (every cell's every batch goes
through ``jit_batch_unpack``), and silent where the trace holds no such
program (XLA:CPU has no device plane; so has a parent's traced run
nothing to fail on)."""
from benchmark.harness.cell import load_module


def _entry(entries, name):
    """The entry called ``name`` (a later PR appends after it)."""
    return next(e for e in entries if e["name"] == name)


def test_unpack_s_is_declared_for_every_cell(bench_copy):
    _, bench, _ = bench_copy
    entry = _entry(bench["per_layer"], "unpack_s")
    scan = _entry(bench["per_layer"], "scan_stage_s")
    assert entry == {"name": "unpack_s", "unit": "s", "better": "lower",
                     "source": "device_trace", "layer": scan["layer"],
                     "moves": "query_s"}


def test_unpack_s_reads_the_unpack_program_alone(bench_copy):
    root, _, _ = bench_copy
    read = load_module(root, "layer_metrics", "unpack_s").read
    ops = [["jit_join_gather", 4.0], ["jit_batch_unpack", 0.3],
           ["jit_filter_batch", 1.0]]
    assert read({"trace": {"device_ops": ops, "collects": [1, 2, 3]}}) \
        == 0.3 / 3
    assert read({"trace": {"device_ops": ops[:1], "collects": [1]}}) is None
    assert read({"trace": {"device_ops": [], "collects": []}}) is None
