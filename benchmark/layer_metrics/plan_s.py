"""Per traced collect, mean: from the start of ``bench.collect`` to the
start of the first operator annotation — session, planner, verifier and
executor set-up, before any batch is pulled."""
import statistics


def read(facts):
    found = [c["plan_s"] for c in facts["trace"]["collects"]
             if c["plan_s"] is not None]
    return statistics.mean(found) if found else None
