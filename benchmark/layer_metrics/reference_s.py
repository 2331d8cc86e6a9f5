"""Seconds the benchmark's plain reference took, or the load of its
kept rows (host clock): the benchmark's share of set-up."""


def read(facts):
    return facts["counters"]["reference_s"]
