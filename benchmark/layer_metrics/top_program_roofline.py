"""Over the traced collects, for the engine program with the most
device seconds (the first ``jit_<name>`` of the trace's ``device_ops``
that is a ``SharedJit`` of that name): the least time the chips need to
move its argument and result bytes, as a share of its seconds on device
0."""
from benchmark.harness.engine_record import program_bytes, window_records


def read(facts):
    peaks, found = facts["peaks"], window_records(facts)
    if peaks is None or found is None \
            or len(found[0]) != len(facts["trace"]["collects"]):
        return None
    for op, seconds in facts["trace"]["device_ops"]:
        name = op[len("jit_"):] if op.startswith("jit_") else None
        moved = sum(program_bytes(c, name) for c in found[0]) if name else 0
        if moved and seconds:
            least_s = moved / (peaks["hbm_bytes_per_s"]
                               * facts["counters"]["chips"])
            return 100.0 * least_s / seconds
    return None
