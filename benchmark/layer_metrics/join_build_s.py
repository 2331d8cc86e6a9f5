"""Per traced collect, mean: seconds on device 0 preparing join build
sides (exec/joins.py ``prepare_fast_build``): ``jit_join_build_prep``
sorts a build by its key, ``jit_join_build_table`` makes the
direct-address table of a dense one.  Both run anew every collect."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_join_build_prep", "jit_join_build_table")


def read(facts):
    return program_seconds(facts, PROGRAMS)
