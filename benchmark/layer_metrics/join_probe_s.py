"""Per traced collect, mean: seconds on device 0 probing stream batches
by address and gathering the joined rows (exec/joins.py:
``jit_join_probe_direct`` + ``jit_join_gather``)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_join_probe_direct", "jit_join_gather")


def read(facts):
    return program_seconds(facts, PROGRAMS)
