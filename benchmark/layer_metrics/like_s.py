"""Per traced collect, mean: seconds on device 0 in the programs whose
condition matches strings (exec/basic.py ``string_match_filter``,
exec/fused.py ``string_match_stage``: a filter or a fused stage holding
a ``LIKE`` or a literal-needle ``StartsWith`` / ``EndsWith`` /
``Contains`` runs under a name of its own; the compaction of the rows
it keeps and the stage's projections are inside it)."""
from benchmark.harness.layer_reads import program_seconds
from benchmark.harness.like_bytes import MATCH_PROGRAMS


def read(facts):
    return program_seconds(facts, MATCH_PROGRAMS)
