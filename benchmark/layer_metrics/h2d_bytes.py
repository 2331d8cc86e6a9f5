"""Per collect, mean over the window: bytes of the host buffers the
engine handed to ``jax.device_put`` (wire-codec encoded and packed, so
fewer than the decoded bytes ``pipeline_roofline`` counts); the
engine's ``h2d_bytes`` counter."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "h2d_bytes")
