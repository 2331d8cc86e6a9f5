"""Seconds the benchmark's own generator took (host clock): the
benchmark's share of set-up, never the engine's."""


def read(facts):
    return facts["counters"]["gen_s"]
