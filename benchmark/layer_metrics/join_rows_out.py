"""Per collect, mean over the window: output rows of the joins' probes,
from the totals their flushes fetch anyway (``join.probe.rows_out``,
exec/joins.py): a left join hands on at least its stream.  None on an
engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "join.probe.rows_out")
