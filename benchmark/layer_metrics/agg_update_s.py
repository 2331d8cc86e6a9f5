"""Per traced collect, mean: seconds on device 0 in ``jit_agg_update``
(exec/aggregate.py): discovery of up to 64 keys, then the dense
reduction or, past 64 keys, the sort branch of
``segmented.group_by_update`` — a multi-operand sort of the whole
bucket, a scatter a key column, a segment reduction an aggregate."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_agg_update",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
