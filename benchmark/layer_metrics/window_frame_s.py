"""Per traced collect, mean: seconds on device 0 in the window
operator's program (exec/window.py ``jit_window_frame``: the sort by
partition and order keys, then every frame over the sorted batch).  The
program ``window_s`` reads in q44, under a name of this cell's own
(PERF.md, Open questions)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_window_frame",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
