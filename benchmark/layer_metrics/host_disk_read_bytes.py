"""Per collect, mean over the window: bytes the process made the
storage layer fetch (``read_bytes`` of ``/proc/self/io``), so what the
page cache answered is not in it: 0 where the window's files were all
in memory.  None where the system has no such file."""


def read(facts):
    return facts["counters"].get("host_disk_read_bytes")
