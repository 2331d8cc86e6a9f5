"""Per collect, mean over the window: thread-seconds the scans'
``scan-prefetch`` threads waited for their input, the next record batch
— blocked on the reader pool's future, or decoding in-thread where the
reader is pulled lazily (one file: ``decode@<Scan>Exec`` nests inside);
the engine's ``starved@<Scan>Exec`` spans (io/scan.py
``_device_batches``), summed over scan classes and threads.  With
``scan_stage_s`` and ``scan_backpressure_s`` it is the staging thread's
life: large, and decode sets the scan's pace."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "span.starved@", ".seconds")
