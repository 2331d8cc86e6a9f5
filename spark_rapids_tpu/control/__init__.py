"""Self-driving control plane: telemetry that acts.

PR 15 made every chokepoint observable (registry histograms, /metrics,
query_history.jsonl) and PR 16 made the cluster elastic, but every knob
stayed a static conf — wrong the moment the workload shifts.  This
package closes the loop (ROADMAP item 5): ONE driver-side daemon thread
ticks on ``spark.rapids.control.intervalSeconds``, reads the same
registry deltas an operator would read off ``/metrics``, and actuates
four knobs that already exist:

* **admission autotune** — AIMD on the queue-wait vs query-wall
  histogram deltas moves ``AdmissionController.max_concurrent`` inside
  ``[minConcurrent, maxConcurrent]``; per-tenant p99 SLOs
  (``spark.rapids.control.slo.<tenant>.p99Seconds``) shed ONLY the
  sustained violator's over-share through the existing pressure-hook
  chokepoint (rules.SloTracker).
* **governor watermark adaptation** — the ``spill.io_seconds``
  histogram and grant-stall counters nudge the memory governor's
  high/low watermarks down when the spill tier is slow, so pressure
  backs off earlier (rules.WatermarkRule).
* **history-driven plan routing** — at plan time the query's
  fingerprint is looked up in the bounded in-memory
  :class:`~spark_rapids_tpu.obs.history.HistoryIndex`; plans whose
  observed wall sits below the express threshold skip the AQE/stage
  machinery and the mesh (the express-lane precursor of ROADMAP
  item 2), and plans observed under several mesh shapes route to the
  fastest one.
* **SLO-driven fleet sizing** — sustained aggregate p99-over-SLO with
  a backlog spawns a worker via ``ClusterDriver.add_worker``; a
  sustained idle fleet retires one via ``remove_worker(drain=True)``,
  under minWorkers/maxWorkers with hysteresis and a cooldown
  (rules.FleetRule).

Every decision is bounded (hard clamps per rule), rate-limited (one
actuation per rule per tick, fleet cooldown on top), recorded as a
``control.decision`` trace span + registry counters, idempotent (a
dropped actuation is simply re-derived from fresh signals next tick),
and reversible: with ``spark.rapids.control.enabled=false`` (the
default) this package is NEVER imported — the session gates on the raw
conf string, so plans, confs, and counters are byte-identical to the
static engine (tests/test_control.py::
test_disabled_is_byte_identical_to_static, and the control case of
tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

from spark_rapids_tpu.conf import ConfEntry, register

__all__ = ["CONTROL_ENABLED", "CONTROL_INTERVAL", "ControlLoop",
           "parse_tenant_slos"]

CONTROL_ENABLED = register(ConfEntry(
    "spark.rapids.control.enabled", False,
    "Run the self-driving control loop: one daemon thread ticking on "
    "control.intervalSeconds that autotunes the admission cap (AIMD), "
    "adapts the memory governor's spill watermarks, routes repeated "
    "plans from query history, sheds tenants that persistently violate "
    "their p99 SLO, and sizes the worker fleet. Off (default): the "
    "control package is never imported and every knob stays exactly "
    "its static conf value.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes")))
CONTROL_INTERVAL = register(ConfEntry(
    "spark.rapids.control.intervalSeconds", 1.0,
    "Control-loop tick period in seconds. Each tick reads one registry "
    "delta, merges it into a sliding window of "
    "control.windowTicks deltas, and derives at most one actuation per "
    "rule — the tick period is therefore also the actuation rate "
    "limit.", conv=float))
CONTROL_WINDOW_TICKS = register(ConfEntry(
    "spark.rapids.control.windowTicks", 5,
    "How many tick deltas the controller merges when computing "
    "percentile signals (queue wait, per-tenant p99, spill I/O). "
    "Larger = smoother/slower reactions; smaller = twitchier.",
    conv=int))
CONTROL_ADMISSION_ENABLED = register(ConfEntry(
    "spark.rapids.control.admission.enabled", True,
    "Enable the AIMD admission-cap rule (only meaningful when "
    "control.enabled). Set false to pin "
    "admission.maxConcurrentQueries back to its static conf value.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes")))
CONTROL_ADMISSION_MIN = register(ConfEntry(
    "spark.rapids.control.admission.minConcurrent", 1,
    "Lower clamp for the autotuned admission cap: multiplicative "
    "decrease never drops maxConcurrentQueries below this.", conv=int))
CONTROL_ADMISSION_MAX = register(ConfEntry(
    "spark.rapids.control.admission.maxConcurrent", 16,
    "Upper clamp for the autotuned admission cap: additive increase "
    "never raises maxConcurrentQueries above this.", conv=int))
CONTROL_QUEUE_WAIT_TARGET = register(ConfEntry(
    "spark.rapids.control.admission.queueWaitTargetSeconds", 0.25,
    "Queue-wait p99 (over the signal window) above which the AIMD "
    "rule adds one admission slot — queries are waiting while the "
    "engine is healthy, so concurrency is the bottleneck.",
    conv=float))
CONTROL_SLO_VIOLATION_TICKS = register(ConfEntry(
    "spark.rapids.control.slo.violationTicks", 3,
    "Consecutive ticks a tenant's observed p99 (end-to-end: queue "
    "wait + wall) must exceed its "
    "spark.rapids.control.slo.<tenant>.p99Seconds before its "
    "over-share is shed. Hysteresis against one slow query tripping "
    "a shed.", conv=int))
CONTROL_SLO_RECOVERY_TICKS = register(ConfEntry(
    "spark.rapids.control.slo.recoveryTicks", 3,
    "Consecutive ticks a shed tenant's p99 must sit back under its "
    "SLO (or show no traffic) before the shed is lifted.", conv=int))
CONTROL_GOVERNOR_ENABLED = register(ConfEntry(
    "spark.rapids.control.governor.enabled", True,
    "Enable the spill-watermark adaptation rule (only meaningful when "
    "control.enabled and the memory governor is on). Set false to pin "
    "the governor watermarks to their static conf values.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes")))
CONTROL_SPILL_P99_TARGET = register(ConfEntry(
    "spark.rapids.control.governor.spillP99TargetSeconds", 0.25,
    "spill.io_seconds p99 (over the signal window) above which — or "
    "any grant timeout in the window — the governor's high/low "
    "watermarks are stepped DOWN so spilling starts earlier on the "
    "slow tier; sustained health steps them back toward the conf "
    "values.", conv=float))
CONTROL_WATERMARK_STEP = register(ConfEntry(
    "spark.rapids.control.governor.watermarkStep", 0.05,
    "Occupancy-fraction step the watermark rule moves the governor's "
    "high watermark per actuation (bounded per tick, so adaptation is "
    "rate-limited by the tick period).", conv=float))
CONTROL_WATERMARK_MIN_HIGH = register(ConfEntry(
    "spark.rapids.control.governor.minHighWatermark", 0.50,
    "Lower clamp for the adapted high watermark: the rule never pushes "
    "spilling to start below this occupancy fraction.", conv=float))
CONTROL_ROUTE_ENABLED = register(ConfEntry(
    "spark.rapids.control.route.enabled", True,
    "Enable history-driven plan routing (only meaningful when "
    "control.enabled and obs.history.dir is set): repeated plan "
    "fingerprints with enough observed samples route to the express "
    "lane (below route.expressWallSeconds) or to the fastest mesh "
    "shape seen in history.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes")))
CONTROL_ROUTE_EXPRESS_WALL = register(ConfEntry(
    "spark.rapids.control.route.expressWallSeconds", 0.2,
    "Median observed wall (from query history) below which a repeated "
    "plan takes the express lane: single chip, no AQE stage "
    "boundaries — the per-query planning machinery costs more than "
    "re-planning could save.", conv=float))
CONTROL_ROUTE_MIN_SAMPLES = register(ConfEntry(
    "spark.rapids.control.route.minSamples", 3,
    "FINISHED history samples a plan fingerprint needs before routing "
    "decisions apply to it — one lucky wall must not reroute a "
    "query.", conv=int))
CONTROL_EXPRESS = register(ConfEntry(
    "spark.rapids.control.express", False,
    "Internal marker the plan router stamps on a routed conf: the "
    "prepare() pipeline skips the AQE stage-boundary pass for this "
    "plan. Not meant to be set by hand.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes"),
    internal=True))
CONTROL_FLEET_ENABLED = register(ConfEntry(
    "spark.rapids.control.fleet.enabled", True,
    "Enable SLO-driven fleet sizing (only meaningful when "
    "control.enabled and a cluster is attached): sustained p99-over-"
    "SLO with a backlog adds a worker, a sustained idle fleet drains "
    "one, inside cluster.minWorkers/maxWorkers.",
    conv=lambda v: str(v).lower() in ("true", "1", "yes")))
CONTROL_FLEET_UP_TICKS = register(ConfEntry(
    "spark.rapids.control.fleet.upTicks", 3,
    "Consecutive overloaded ticks (SLO violation or sustained queue "
    "backlog) before one worker is added.", conv=int))
CONTROL_FLEET_DOWN_TICKS = register(ConfEntry(
    "spark.rapids.control.fleet.downTicks", 10,
    "Consecutive idle ticks (no violation, empty queue) before one "
    "worker is drained and retired — deliberately slower than scale-up "
    "so the fleet rides out gaps between bursts.", conv=int))
CONTROL_FLEET_COOLDOWN = register(ConfEntry(
    "spark.rapids.control.fleet.cooldownSeconds", 30.0,
    "Minimum seconds between fleet actuations (either direction): "
    "worker spawn/drain cost dwarfs a tick, so scaling decisions must "
    "not flap at tick rate.", conv=float))

_SLO_PREFIX = "spark.rapids.control.slo."
_SLO_SUFFIX = ".p99Seconds"


def parse_tenant_slos(settings: dict) -> dict:
    """{tenant: p99 seconds} from the dynamic per-tenant keys
    ``spark.rapids.control.slo.<tenant>.p99Seconds`` (the structured
    keys under ``spark.rapids.control.slo.*`` — violationTicks,
    recoveryTicks — are registered entries and never match the
    suffix)."""
    out: dict = {}
    for key, val in settings.items():
        if key.startswith(_SLO_PREFIX) and key.endswith(_SLO_SUFFIX):
            tenant = key[len(_SLO_PREFIX):-len(_SLO_SUFFIX)]
            if tenant:
                try:
                    out[tenant] = float(val)
                except (TypeError, ValueError):
                    continue
    return out


def __getattr__(name):
    # ControlLoop drags in loop.py (and its lazy session wiring) only
    # when actually constructed — importing the package for its confs
    # (docs generation, tests of the pure rules) stays light
    if name == "ControlLoop":
        from spark_rapids_tpu.control.loop import ControlLoop
        return ControlLoop
    raise AttributeError(name)
