"""XLA backend compiles during set-up, same listener as ``compile_s``
(persistent-cache hits and misses are printed beside it in the
``warmup`` fact)."""


def read(facts):
    return facts["counters"]["xla_compiles"]
