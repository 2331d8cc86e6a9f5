"""Per traced collect, mean: seconds on device 0 decoding the packed
host-to-device wire format into a batch's columns (columnar/batch.py
``jit_batch_unpack``: one launch a staged batch, the first program
every scan's rows meet)."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_batch_unpack",)


def read(facts):
    return program_seconds(facts, PROGRAMS)
