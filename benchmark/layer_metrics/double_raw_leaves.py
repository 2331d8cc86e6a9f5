"""Per collect, mean over the window: float64 columns of the staged
batches that travelled as 8-byte doubles (``wire.double.raw``,
columnar/batch.py ``_PackBuilder.add_fixed``): a value that is not a
whole hundredth or a whole number, NaN, an infinity, a negative zero,
or a range the codec's 32 bits do not hold.  In Q6 none: 0 where the
window's collects shipped float64 columns and none of them raw.  None
on an engine from before the counters."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    if counter_per_collect(facts, "wire.double.bytes") is None:
        return None
    return counter_per_collect(facts, "wire.double.raw") or 0
