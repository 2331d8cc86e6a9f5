"""Per collect, mean over the window: host seconds inside the warm
launches of the engine's own compiled programs (the sum of
``program.<name>.dispatch_s`` over every ``SharedJit``,
exec/compile_cache.py), summed over threads: what the host pays to
launch a collect's ``engine_launches`` programs, and, where a full
device queue makes a launch block, the device's backlog seen from the
host."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "program.", ".dispatch_s")
