"""Over the traced collects: the least time the chips need to move
what their window operators have to read and write
(benchmark/harness/window_bytes.py: rows in x the input columns' widths
plus rows out x the output's, from the rows and schemas the engine
counts, ``window.rows@<schema>``), as a share of the seconds device 0
spent in the window operator's program (``jit_window_frame``).  Bound
by HBM bandwidth: a window does no arithmetic worth counting.  None on
an engine from before the counter, off the chip, or where the trace
holds no window program."""
from benchmark.harness.engine_record import window_records
from benchmark.harness.window_bytes import record_bytes

PROGRAM = "jit_window_frame"


def read(facts):
    peaks, found = facts["peaks"], window_records(facts)
    if peaks is None or found is None \
            or len(found[0]) != len(facts["trace"]["collects"]):
        return None
    seconds = sum(s for name, s in facts["trace"]["device_ops"]
                  if name == PROGRAM)
    moved = [record_bytes(c) for c in found[0]]
    if not seconds or not moved or None in moved:
        return None
    least_s = sum(moved) / (peaks["hbm_bytes_per_s"]
                            * facts["counters"]["chips"])
    return 100.0 * least_s / seconds
