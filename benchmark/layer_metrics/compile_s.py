"""Seconds of XLA backend compilation during set-up (eager ops
included): the sum of ``/jax/core/compile/backend_compile_duration``
events the ``jax.monitoring`` listener saw before the window."""


def read(facts):
    return facts["counters"]["compile_s"]
