"""Per collect, mean over the window: thread-seconds the pulling
threads were blocked in ``q.get()`` on their scan's prefetch queue (the
engine's ``scan.wait_s`` counter, io/scan.py ``_device_batches``),
summed over partitions.  The wait ``scan_self_s`` times from outside on
the traced collects, counted from inside on every collect."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "scan.wait_s")
