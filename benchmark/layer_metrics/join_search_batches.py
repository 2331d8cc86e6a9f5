"""Per collect, mean over the window: stream batches probed by binary
search in a build's sorted keys (``join.probe.search``, exec/joins.py).
Guards that the cell still searches: 0 where every batch went another
way, None where the engine counts no probes."""
from benchmark.harness.join_reads import probe_batches


def read(facts):
    return probe_batches(facts, "join.probe.search")
