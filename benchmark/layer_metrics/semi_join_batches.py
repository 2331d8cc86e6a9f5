"""Per collect, mean over the window: stream batches probed by a semi
or an anti join (``join.semi.batches``, exec/joins.py).  None on an
engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "join.semi.batches")
