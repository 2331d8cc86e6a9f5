"""Per collect, mean over the window: ``agg_update`` launches that took
the sort branch of ``segmented.group_by_update`` (more than 64 distinct
keys; ``agg.update.sorted``, exec/aggregate.py).  Guards that a cell
which is there for the sort branch still runs it."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "agg.update.sorted")
