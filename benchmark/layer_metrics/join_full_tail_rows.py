"""Per collect, mean over the window: build rows no stream batch
matched that a FULL OUTER join emitted null-extended after its stream
(``join.full.unmatched_rows``, exec/joins.py: the tail's row count, a
number the join fetches anyway).  None on an engine from before the
counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "join.full.unmatched_rows")
