"""One read that the per-layer metrics counting a join's stream batches
by the probe that took them share (benchmark/layer_metrics/
join_search_batches.py, join_sorted_batches.py)."""
from __future__ import annotations

from .engine_record import window_records

#: the engine counts every stream batch of an equi-join under one of these
PROBES = ("join.probe.direct", "join.probe.search", "join.probe.sorted")


def probe_batches(facts, name: str):
    """Mean over the window's collects of the probe counter ``name``.
    A record holds only the counters that moved, so a probe no batch
    took is missing from it: that reads 0 where another probe counted
    the batches, and None where none did (an engine from before it
    counted its probes, or a query with no equi-join)."""
    found = window_records(facts)
    records = [] if found is None else found[0] + found[1]
    if not any(p in c for c in records for p in PROBES):
        return None
    return sum(c.get(name, 0) for c in records) / len(records)
