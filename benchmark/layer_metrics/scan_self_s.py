"""Per traced collect, mean: self time of the ``ParquetScanExec``
annotations (span minus nested spans, on the host) — Parquet decode,
packing and the H2D enqueue."""
import statistics


def read(facts):
    return statistics.mean(c["op_self_s"].get("ParquetScanExec", 0.0)
                           for c in facts["trace"]["collects"])
