"""Per collect, mean over the window: bytes of the raw (not dictionary)
string matrices the scan staged, at their staged width
(``scan.stage.string_bytes``, columnar/batch.py ``_PackBuilder``): what
the padded layout costs the host's staging and the link, beside
``h2d_bytes``.  None on an engine from before the counter."""
from benchmark.harness.layer_reads import counter_per_collect


def read(facts):
    return counter_per_collect(facts, "scan.stage.string_bytes")
