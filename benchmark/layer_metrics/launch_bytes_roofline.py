"""Over the traced collects: the least time the chips need to move what
the engine's programs were handed and gave back (argument plus result
bytes of every launch, each counted once, over the HBM peak times the
chips), as a share of the seconds the devices were busy.  An upper
bound on what a program must move only if it reads every argument;
``pipeline_roofline`` below it counts the scanned input once."""
import statistics

from benchmark.harness.engine_record import program_bytes, window_records


def read(facts):
    peaks, found = facts["peaks"], window_records(facts)
    collects = facts["trace"]["collects"]
    if peaks is None or found is None or len(found[0]) != len(collects):
        return None
    busy = sum(statistics.mean(c["device_busy_s"]) for c in collects
               if c["device_busy_s"])
    if not busy:
        return None
    least_s = sum(program_bytes(c) for c in found[0]) / (
        peaks["hbm_bytes_per_s"] * facts["counters"]["chips"])
    return 100.0 * least_s / busy
