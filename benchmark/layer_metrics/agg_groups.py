"""Per collect, mean over the window: groups the ``agg_update``
launches ended in, summed (``agg.update.groups``, exec/aggregate.py:
the count each update's flush fetches anyway).  Guards that a cell
which is there for a high-cardinality group-by still groups by over a
million keys."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "agg.update.groups")
