"""Per collect, mean over the window: host-to-device transfers the
engine made — one per ``jax.device_put`` of a packed host buffer
(columnar/batch.py ``_PackBuilder.build``) and one per miss of the
device-scalar cache (ops/kernels.py); the engine's ``h2d_calls``
counter."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "h2d_calls")
