"""Per collect, mean over the window: ``sum`` / ``avg`` window frames
over doubles that the engine summed as whole cents (``window.sum.cents``,
exec/window.py: one a frame a launch whose addends were all whole cents,
so its sums are integer sums rounded once and equal totals compare
equal).  Better higher: 0 says the frames fell back to adding doubles.
None on an engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "window.sum.cents")
