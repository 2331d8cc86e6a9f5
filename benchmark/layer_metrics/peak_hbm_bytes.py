"""``memory_stats()["peak_bytes_in_use"]`` after the window, the
fullest device."""


def read(facts):
    return facts["counters"]["peak_hbm_bytes"] or None
