"""XLA backend compiles inside the measured window; 0, or the warm-up
missed a shape."""


def read(facts):
    return facts["counters"]["window_compiles"]
