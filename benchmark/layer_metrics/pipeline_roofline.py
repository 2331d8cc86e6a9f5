"""The least time the chips need to read the query's input once (bytes
of the scanned columns from harness/bytes.py over the HBM peak of
peaks.json, times the chips), as a share of the time they were busy per
traced collect.  Bound: memory bandwidth — these queries do a few
operations per byte."""
import statistics


def read(facts):
    peaks, counters = facts["peaks"], facts["counters"]
    busy = [statistics.mean(c["device_busy_s"])
            for c in facts["trace"]["collects"] if c["device_busy_s"]]
    if peaks is None or not busy or not statistics.mean(busy):
        return None
    least_s = counters["scanned_bytes"] / (
        peaks["hbm_bytes_per_s"] * counters["chips"])
    return 100.0 * least_s / statistics.mean(busy)
