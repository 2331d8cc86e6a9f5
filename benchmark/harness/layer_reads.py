"""Two reads that several per-layer metrics under
benchmark/layer_metrics share: one named counter of the engine's
per-query records, and the device seconds of a few named programs.
Both return None where there is nothing to read, so the metric is left
out of the line instead of failing the run."""
from __future__ import annotations

from .engine_record import window_records


def counter_per_collect(facts, name: str):
    """Mean over the window's collects of the engine counter ``name``.
    A record holds only the counters that moved: None where no collect
    of the window moved it (an engine from before it had the counter)."""
    found = window_records(facts)
    records = [] if found is None else found[0] + found[1]
    if not any(name in c for c in records):
        return None
    return sum(c.get(name, 0) for c in records) / len(records)


def program_seconds(facts, programs):
    """Per traced collect, mean: seconds on device 0 in the programs
    named ``programs`` (``jit_<SharedJit.name>`` in the trace's
    ``device_ops``).  None where the trace holds none of them (XLA:CPU
    has no device plane)."""
    trace = facts["trace"]
    found = [s for name, s in trace["device_ops"] if name in programs]
    if not found or not trace["collects"]:
        return None
    return sum(found) / len(trace["collects"])
