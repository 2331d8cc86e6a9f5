"""Per collect, mean over the window: stream batches probed in a build's
sorted keys with no table (``join.probe.search``, exec/joins.py: since
PR 35 by a merge of the batch's keys into the build's, by steps where
the build is over 32 times the batch).  Guards that the cell still
searches: 0 where every batch went another way, None where the engine
counts no probes."""
from benchmark.harness.join_reads import probe_batches


def read(facts):
    return probe_batches(facts, "join.probe.search")
