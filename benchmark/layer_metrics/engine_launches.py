"""Per collect, mean over the window: launches of the engine's own
compiled programs (the sum of ``program.<name>.launches`` over every
``SharedJit``).  ``program_launches`` minus this is what eager ``jnp``
operations outside any program launch (device 0's, on a mesh)."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "program.", ".launches")
