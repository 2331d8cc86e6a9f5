"""Per collect, mean over the window: batches a shared scan handed to
consumers other than the one it was staged for
(``scan.shared.handed_batches`` less ``scan.shared.staged_batches``,
io/scan.py: a scan that several branches of one plan share is staged on
the device once and every consumer, the first too, is handed the parked
batches)."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    handed = counter_per_collect(facts, "scan.shared.handed_batches")
    staged = counter_per_collect(facts, "scan.shared.staged_batches")
    if handed is None or staged is None:
        return None
    return handed - staged
