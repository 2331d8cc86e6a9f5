"""Per collect, mean over the window: seconds host threads were blocked
inside those fetches (the engine's ``sync_wait_s`` counter).  On the
chip a fetch waits for the device to finish what is queued before it,
so this is mostly the device's work seen from the host, summed over
threads: it is what chunking the fetches can hide, not a cost of its
own."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "sync_wait_s")
