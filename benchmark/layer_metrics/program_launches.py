"""Per traced collect, mean: executions of compiled programs on device
0 (events of its ``XLA Modules`` line)."""
import statistics


def read(facts):
    return statistics.mean(c["program_launches"]
                           for c in facts["trace"]["collects"])
