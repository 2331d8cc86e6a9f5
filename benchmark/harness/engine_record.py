"""The engine's own per-query records, as the per-layer metrics under
benchmark/layer_metrics read them.

The engine (spark_rapids_tpu/obs/registry.py, ``recent_queries``) keeps
one record per finished query: the movement of its process-wide
counters over the query — ``span.<name>.count/seconds`` for its spans,
``h2d_*`` / ``d2h_*`` / ``sync_wait_s`` for transfers and blocking
fetches, ``program.<name>.launches/arg_bytes/result_bytes`` for every
compiled program of its own.  One collect is one query, so the window's
collects are the newest records, in order: the traced ones first.

Everything here returns None where the engine keeps no such record (a
commit from before it did), so a metric is left out of the line rather
than failing the run.
"""
from __future__ import annotations

import statistics


def window_records(facts) -> tuple | None:
    """``(traced, untraced)``: the counter movements (one dict a
    collect) of the window's traced and untraced collects."""
    try:
        from spark_rapids_tpu.obs.registry import get_registry
        recent = get_registry().recent_queries
    except (ImportError, AttributeError):
        return None
    counters = facts["counters"]
    n_traced = len(counters["traced_collect_seconds"])
    n = n_traced + len(counters["collect_seconds"])
    records = [r["counters"] for r in recent(n)] if n else []
    if not records:
        return None
    # a window of more collects than the ring holds lost its oldest
    n_traced = max(0, n_traced - (n - len(records)))
    return records[:n_traced], records[n_traced:]


def total(counters: dict, prefix: str, suffix: str = "") -> float:
    """Sum of the counters named ``<prefix>…<suffix>``."""
    return sum(v for k, v in counters.items()
               if k.startswith(prefix) and k.endswith(suffix))


def mean_per_collect(facts, prefix: str, suffix: str = ""):
    """Mean over the window's collects of ``total(prefix, suffix)``."""
    found = window_records(facts)
    if found is None:
        return None
    return statistics.mean(total(c, prefix, suffix)
                           for c in found[0] + found[1])


def program_bytes(counters: dict, name: str | None = None) -> float:
    """Argument plus result bytes handed to the engine's programs (to
    the program ``name`` alone, if given) in one collect: each launch's
    array leaves, counted once."""
    prefix = "program." if name is None else f"program.{name}."
    return total(counters, prefix, ".arg_bytes") \
        + total(counters, prefix, ".result_bytes")
