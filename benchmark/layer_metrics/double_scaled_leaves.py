"""Per collect, mean over the window: float64 columns of the staged
batches that travelled as bit-packed whole hundredths (or whole
numbers) and were rebuilt on the device into the doubles the host held
(``wire.double.scaled``, columnar/batch.py ``_PackBuilder.add_fixed``;
the rebuild is columnar/wirecodec.py ``rebuild_double``).  In Q6 three
a batch (discount, quantity, price).  Fewer, with ``double_raw_leaves``
up by as many: the columns went back to 8 bytes a row; fewer with
neither up: the counter's site has moved.  None on an engine from
before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "wire.double.scaled")
