"""Per collect, mean over the window: valid rows of the join builds a
collect prepared (``join.build.rows``, exec/joins.py: the count each
build's one fetch brings).  In Q13 the kept orders, 14.8M: the build is
the fact.  None on an engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "join.build.rows")
