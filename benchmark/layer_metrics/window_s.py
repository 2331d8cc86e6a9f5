"""Per traced collect, mean: seconds on device 0 in the window
operator's program (exec/window.py: sort by partition and order keys,
then every window function over the sorted batch)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_window_frame",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
