"""Per collect, mean over the window: rows handed to the window
operator's programs (``window.rows``, exec/window.py: the valid rows of
every batch a ``WindowExec`` launched its program on).  None on an
engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "window.rows")
