"""Per collect, mean over the window: thread-seconds the scans'
``scan-prefetch`` threads were blocked putting a staged batch on their
full queue of two (the engine's ``scan_backpressure_s`` counter,
io/scan.py ``_device_batches``): the staging thread waiting for its
output.  Large beside a small ``scan_wait_s``, and the host keeps up:
the pulling thread and the device behind it are the slower side."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "scan_backpressure_s")
