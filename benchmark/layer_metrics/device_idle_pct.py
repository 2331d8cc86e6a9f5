"""Share of the traced window in which no operation ran on a device,
the mean over the cell's devices (each is in the ``trace`` fact)."""
import statistics


def read(facts):
    devices = facts["trace"]["devices"]
    return statistics.mean(d["idle_pct"] for d in devices) \
        if devices else None
