"""Per collect, mean over the window: host seconds inside the
``jax.device_put`` calls of ``_PackBuilder.build`` (columnar/batch.py;
the engine's ``h2d_put_s`` counter), summed over threads: the put's
share of ``scan_stage_s``.  Near zero where the put returns before the
copy is done: the link's seconds are then no host span's."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "h2d_put_s")
