"""Per collect, mean over the window: stream batches whose ``join_gather``
took the aligned plan (``join.gather.aligned``, exec/joins.py, PR 43:
the batch's probe said every live stream row comes out exactly once, so
the stream's columns are sliced and only the build's are gathered).
Says how often the cheap plan engages: 28 a collect in q93 (the left
join's batches; the semi-join's keep 1 row in 360), 1 in q51 (the full
join's one stream batch), 1 in q13 (the customers past the last key
that has an order; the batch before expands tenfold), 0 from an engine
that has no such plan but counts its probes, None where no join ran."""
from benchmark.harness.join_reads import probe_batches


def read(facts):
    return probe_batches(facts, "join.gather.aligned")
