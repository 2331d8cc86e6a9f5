"""Per collect, mean over the window: rows a device string match
evaluated (``like.device.rows``, exec/basic.py
``count_string_matches``: the rows of every batch a program that
matches strings was launched on).  In Q13 the orders' 15M rows: less,
and the predicate has left the device.  None on an engine from before
the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "like.device.rows")
