"""What the host was doing around a window of collects, read from the
operating system and from nothing of the program: CPU seconds the
process burnt, bytes it really read from the disk, the machine's load.
Where the system has no ``/proc/self/io`` the bytes are None, never 0;
the load is what ``os.getloadavg`` says, and a kernel that keeps no
load average (the chip machine's sandbox: ``/proc/loadavg`` reads
``0.00 0.00 0.00 0/0 0``) says 0.0 there."""
from __future__ import annotations

import os
import time


def disk_read_bytes() -> int | None:
    """``read_bytes`` of ``/proc/self/io``: bytes this process made the
    storage layer fetch, so a read the page cache answered is not in it."""
    try:
        with open("/proc/self/io") as f:
            fields = dict(line.split(":", 1) for line in f if ":" in line)
    except OSError:
        return None
    value = fields.get("read_bytes")
    return None if value is None else int(value)


def snapshot() -> dict:
    """The running totals a window's readings are differences of."""
    return {"cpu_s": time.process_time(),
            "disk_read_bytes": disk_read_bytes()}


def moved(before: dict, after: dict) -> dict:
    """``after - before``, key by key; None where either side is."""
    return {k: None if None in (before[k], after[k])
            else after[k] - before[k] for k in before}


def cores() -> int:
    """Cores this process may run on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:      # not Linux
        return os.cpu_count() or 1


def load_per_core() -> float:
    """The one-minute load average over the cores this process may use:
    what else the machine was doing, this process's own threads too.
    Reported as read, 0.0 included: ``host_load`` lists no cells, so a
    traced line without it would be refused."""
    return os.getloadavg()[0] / cores()


def memory_pool() -> str:
    """The pool pyarrow decodes into in this process (``system`` under
    benchmark/process_env.json, else pyarrow's default): every run's
    ``window`` fact says it, so two runs under different allocators are
    not compared unknowingly."""
    import pyarrow as pa
    return pa.default_memory_pool().backend_name
