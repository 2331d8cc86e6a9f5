"""Per traced collect, mean: seconds on device 0 gathering the joined
rows of the stream batches (exec/joins.py ``jit_join_gather``: a left
join hands on its whole stream, batch by batch)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_join_gather",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
