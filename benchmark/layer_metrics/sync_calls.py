"""Per collect, mean over the window: blocking device-to-host fetches
(exec/core.py ``fetch_to_host``: the aggregate's and the join's chunked
count fetches, row-count syncs, the result); the engine's ``d2h_calls``
counter."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "d2h_calls")
