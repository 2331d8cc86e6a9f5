"""Per collect, mean over the window: stream batches that went through
the sort-path probe, which sorts each batch together with the whole
build side (``join.probe.sorted``, exec/joins.py).  0 where the join
streams; None where the engine counts no probes."""
from benchmark.harness.join_reads import probe_batches


def read(facts):
    return probe_batches(facts, "join.probe.sorted")
