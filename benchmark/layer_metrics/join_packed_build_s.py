"""Per traced collect, mean: seconds on device 0 preparing the join
build sides (exec/joins.py ``prepare_fast_build``): ``jit_join_build_prep``
(where a join has several integral keys it finds their ranges, packs
them into one key and sorts the build by it, all in this program) and
``jit_join_build_table`` (the direct-address table of a dense build)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_join_build_prep", "jit_join_build_table")


def read(facts):
    return program_seconds(facts, PROGRAMS)
