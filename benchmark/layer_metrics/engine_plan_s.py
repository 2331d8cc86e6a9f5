"""Per collect, mean over the window: seconds of the engine's own
``query.plan`` span (session.py: logical plan -> lowered, overridden and
verified physical plan), on the host clock.  ``plan_s`` beside it is the
harness's view from outside — start of ``bench.collect`` to the first
operator annotation — and also holds admission, context set-up and the
first partition's start: ``engine_plan_s <= plan_s``."""
from benchmark.harness.engine_record import mean_per_collect


def read(facts):
    return mean_per_collect(facts, "span.query.plan.seconds")
