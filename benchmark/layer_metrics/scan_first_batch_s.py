"""Per collect, mean over the window: the share of ``scan_wait_s`` that
is each pipeline's first ``q.get()`` — the fill latency from a
partition's start to its first staged batch (the engine's
``scan.first_batch_s`` counter; ``scan.pipelines`` beside it counts the
pipelines, one a partition staged), summed over partitions."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "scan.first_batch_s")
