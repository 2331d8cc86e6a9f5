"""Per traced collect, mean: seconds on device 0 in the programs of a
stand-alone ``FilterExec`` — the cumsum + scatter front-pack
(ops/kernels.compact) and the shrink to the surviving rows' capacity
bucket (exec/basic.py).  A filter fused with its neighbours runs inside
``jit_fused_stage_body`` and is not counted here."""
from benchmark.harness.layer_reads import program_seconds

PROGRAMS = ("jit_filter_batch", "jit_batch_shrink")


def read(facts):
    return program_seconds(facts, PROGRAMS)
