"""Per collect, mean over the window: device bytes of the batches a
shared scan parked for replay (``scan.shared.parked_bytes``,
io/scan.py).  The whole table is staged before its first consumer sees
a batch, so this is HBM held, not traffic."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "scan.shared.parked_bytes")
