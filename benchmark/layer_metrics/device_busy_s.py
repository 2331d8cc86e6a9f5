"""Per traced collect, mean: seconds in which an operation ran on
device 0 (union of its ``XLA Ops`` intervals inside the collect)."""
import statistics


def read(facts):
    found = [c["device_busy_s"][0] for c in facts["trace"]["collects"]
             if c["device_busy_s"]]
    return statistics.mean(found) if found else None
