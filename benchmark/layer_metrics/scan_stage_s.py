"""Per collect, mean over the window: thread-seconds the scans'
prefetch threads spent staging a batch — wire-codec encode, packing and
the ``device_put`` (``ColumnBatch.from_arrow``); the engine's
``stage@<Scan>Exec`` spans (io/scan.py ``_device_batches``), summed
over scan classes and threads."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "span.stage@", ".seconds")
