"""Per collect, mean over the window: host-to-device bytes of the
float64 columns of the staged batches, scaled and raw together
(``wire.double.bytes``, columnar/batch.py ``_PackBuilder.add_fixed``: a
scaled leaf its capacity times its bit width, a raw one 8 bytes a
slot).  A share of ``h2d_bytes``; what a repair of the decode that
ships doubles wider would raise.  None on an engine from before the
counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "wire.double.bytes")
