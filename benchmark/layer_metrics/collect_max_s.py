"""The longest untraced collect of the window (host clock): the tail,
until a cell has the hundreds of collects a percentile needs."""


def read(facts):
    seconds = facts["counters"]["collect_seconds"]
    return max(seconds) if seconds else None
