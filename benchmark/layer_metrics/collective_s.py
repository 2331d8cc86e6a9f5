"""Per traced collect, mean: summed durations of all-to-all,
all-gather, all-reduce, collective-permute and reduce-scatter
operations on device 0 (whether compute hid them is not known yet)."""
import statistics


def read(facts):
    return statistics.mean(c["collective_s"]
                           for c in facts["trace"]["collects"])
