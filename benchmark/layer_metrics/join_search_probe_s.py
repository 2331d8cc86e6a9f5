"""Per traced collect, mean: seconds on device 0 in the search probe
(exec/joins.py ``jit_join_probe_fast``: a stream batch's keys, packed
into one where the join has several, searched in the build's sorted
keys; the packing is part of the same program)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_join_probe_fast",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
