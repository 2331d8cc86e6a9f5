"""Per collect, mean over the window: thread-seconds the scans' worker
threads spent decoding files into Arrow record batches — the engine's
``decode@<Scan>Exec`` spans (io/scan.py ``_decode_iter``), summed over
scan classes and threads, so it can exceed the collect's seconds."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "span.decode@", ".seconds")
