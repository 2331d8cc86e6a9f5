"""Per collect, mean over the window: launches of the window
operator's program (``window.launches``, exec/window.py: one a batch a
``WindowExec`` holds, so one a hash partition where the planner
partitioned the input on the window's keys).  None on an engine from
before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "window.launches")
