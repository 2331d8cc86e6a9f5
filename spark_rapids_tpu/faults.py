"""Deterministic, conf-driven fault injection for robustness testing.

Reference motivation (SURVEY §2.6): the UCX shuffle plane survives
transport failures by surfacing them to Spark's stage-retry machinery
(RapidsShuffleIterator), and the reference proves that behavior with
mocked transports (RapidsShuffleTestHelper.scala:26-95).  Here the REAL
server/client/store/spill code runs under seeded faults instead: the
engine carries injection points that are inert (a single ``is None``
check) unless ``spark.rapids.test.faults`` names a plan, so robustness
behavior is testable in-process on CPU with no cluster and no mocks.

Spec grammar (``spark.rapids.test.faults``)::

    spec  := rule (';' rule)*
    rule  := point ':' action (',' key '=' value)*

Injection points wired today (site -> actions it interprets):

    tcp.server.frame    per outgoing data frame (ctx: shuffle, part,
                        frame).  Actions: ``reset`` (abrupt connection
                        close mid-stream), ``stall`` (sleep ``seconds``
                        before sending, to trip the client timeout),
                        ``corrupt`` (flip one seeded byte of the wire
                        payload AFTER the checksum was computed —
                        in-transit corruption), ``error`` (send a
                        server error frame instead of data).
    tcp.client.connect  before dialing a peer (ctx: host, port).
                        Action ``reset`` fails the dial.
    store.fetch         local shuffle store reads (ctx: shuffle, part).
                        Action ``error`` raises from the store — over
                        TCP it reaches the client as an error frame.
    shuffle.peer.hang   accepted-then-stalled peer: checked at the TOP
                        of the server's fetch handling (ctx: shuffle,
                        part).  Any action name works (use ``hang``);
                        the server holds the connection open sending
                        nothing — no header, no error frame — for
                        ``seconds`` (default 3600, interrupted by
                        server close), so the CLIENT's
                        spark.rapids.shuffle.socketTimeout is what
                        breaks the wedge as a retryable
                        ShuffleFetchError.  Default ``times=1``: the
                        retry's reconnect succeeds.
    shuffle.peer.dead   terminal peer death, checked on every store /
                        remote fetch (ctx: shuffle, part).  Any action
                        name works (use ``dead``); once triggered the
                        fetch raises MapOutputLostError naming every
                        map output in the requested slice, driving the
                        stage-recovery layer instead of the transient
                        retry ladder.  Points ending in ``.dead``
                        default to ``times=0`` (a dead peer stays
                        dead); give an explicit ``times=N`` to model a
                        peer that is replaced after N failed pulls.
    spill.disk.corrupt  before a disk spill file is read back (ctx:
                        buffer_id, priority, size).  Action ``corrupt``
                        flips one seeded byte of the on-disk payload so
                        the CRC32C read-back check fails and the
                        catalog surfaces SpillCorruptionError — data
                        loss, not a crash.
    spill.disk.enospc   on each spill-to-disk write (ctx: buffer_id,
                        priority, size).  Action ``enospc`` makes the
                        write fail like a full disk; the catalog treats
                        the buffer as unspillable and lets the PR 2
                        OOM split-and-retry scope absorb the pressure.
    mesh.slice.lost     around a mesh program launch (ctx: op, devices).
                        Action ``lost`` simulates losing a device slice
                        mid-execution; mesh execs fall back to the
                        single-device recompute path and count a stage
                        recompute.
    memory.oom          run_with_spill_retry dispatch (ctx: op) and the
                        operator retry scopes in memory/retry.py (ctx:
                        op, and rows at with_retry sites).  Action
                        ``oom`` raises a simulated XLA
                        RESOURCE_EXHAUSTED, driving the spill-retry
                        loop exactly like a real HBM exhaustion.
    memory.oom.until_rows
                        with_retry dispatch sites only (ctx: op, rows).
                        Action ``oom`` with ``until_rows=N`` keeps
                        raising the simulated OOM while the dispatched
                        batch holds MORE than N rows — the exhaustion
                        "persists" until split-and-retry shrinks the
                        working set below the threshold, making the
                        split path deterministically provable without a
                        real device.
    memory.grant.stall  governor grant-wait entry (ctx: query_id,
                        need; memory/governor.py).  Action ``stall``
                        holds the waiter ``seconds`` (default 0.05)
                        before the normal bounded wait loop runs — a
                        deterministic mid-grant-wait window for chaos
                        tests to land cancellations in, proving the
                        reservation is released on terminal unwind.
    memory.governor.oom_storm
                        governor reclaim entry (ctx: query_id, need;
                        memory/governor.py).  Action ``oom`` makes the
                        arbitration report ZERO bytes freed — an OOM
                        storm spilling cannot keep up with — so the
                        requester's split-and-retry ladder absorbs the
                        pressure; chaos tests use it to prove bounded
                        wall time (no eviction livelock) under
                        concurrent queries.
    cache.result.corrupt
                        result-cache hit verification (ctx: kind;
                        exec/result_cache.py).  Action ``corrupt``
                        flips one seeded byte of the cached blob so the
                        per-hit CRC32 verify fails: the entry is
                        dropped, ``result_cache_corrupt`` counts it,
                        and the query recomputes — corruption is a
                        cache miss, never stale rows or a crash.
    cluster.worker.dead checked in the driver-side map-output tracker
                        on each reduce fetch (ctx: shuffle, part,
                        worker).  Any action name works (use ``dead``);
                        the driver SIGKILLs the worker owning the first
                        requested map output — a real process death,
                        driving heartbeat-loss detection plus lineage
                        reassignment onto surviving workers.  Never
                        fires when only one worker remains alive (a
                        0-worker cluster cannot recover anything).
                        ``.dead`` default times=0 applies; chaos plans
                        should pass ``times=1`` to kill exactly one.
    cluster.worker.hang checked in the driver's heartbeat handler (ctx:
                        worker).  Any action name works (use ``hang``);
                        once fired the driver IGNORES that worker's
                        subsequent heartbeats — the process lives but
                        goes silent, so the heartbeat monitor declares
                        it dead after cluster.heartbeat.timeoutSeconds
                        and recovery reassigns its partitions.
    cluster.worker.slow checked driver-side before each fragment RPC is
                        sent (ctx: worker, shuffle; cluster/exec.py).
                        Any action name works (use ``slow``); the
                        dispatch thread sleeps ``seconds`` (default 2)
                        before calling the worker, modelling a
                        straggling executor so speculation
                        (spark.rapids.cluster.speculation.enabled) can
                        be driven deterministically.
    cluster.worker.flaky
                        checked driver-side before each fragment RPC is
                        sent (ctx: worker, shuffle; cluster/exec.py).
                        Any action name works (use ``flaky``); the
                        dispatch fails with an RpcError as if the
                        worker's control plane dropped the call —
                        consecutive firings drive the quarantine
                        machinery (quarantine.maxFailures) without
                        killing the process, so its map outputs stay
                        servable.
    cluster.migrate.drop
                        checked driver-side per slot while planning a
                        graceful drain's map-output migration (ctx:
                        shuffle, part, map; cluster/driver.py).  Any
                        action name works (use ``drop``); the slot is
                        excluded from migration and left on the
                        retiring worker, so removal marks it lost and
                        the reader's MapOutputLostError -> lineage
                        fallback is exercised for real.
    cluster.rpc.drop    before each control-plane RPC send (ctx: op).
                        Any action name works (use ``drop``); the dial
                        fails with a ConnectionError the RPC retry
                        ladder absorbs — a dropped/blackholed control
                        message, distinct from a dead worker.
    admission.tenant.storm
                        weighted-fair admission entry (ctx: tenant,
                        query_id; exec/lifecycle.py).  Action ``storm``
                        (any name works) rejects the arrival with
                        QueryRejected before it takes a queue slot —
                        a deterministic per-tenant admission storm for
                        chaos tests to prove other tenants' queries
                        still flow (no cross-tenant starvation).
    io.write.partial    after each file a write task attempt finishes
                        (ctx: task, attempt, worker, file;
                        io/writer.py write_task_attempt).  Action
                        ``crash`` raises InjectedFault so the attempt
                        dies mid-write leaving a partial private
                        staging dir; action ``truncate`` first shears
                        the just-written file to half its bytes —
                        garbage that must never become visible and that
                        a later attempt must not be confused by.
    io.write.commit.drop
                        on manifest registration at the driver's write
                        commit coordinator (ctx: task, attempt, worker;
                        io/writer.py WriteCommitCoordinator.register).
                        Any action name works (use ``drop``); the
                        attempt's commit message is treated as lost in
                        flight — no winner is recorded, the task is
                        re-attempted, and the orphaned attempt's files
                        stay in staging for GC.
    io.write.rename.fail
                        per staging->final rename during job commit
                        (ctx: file; io/writer.py
                        WriteCommitCoordinator._rename).  Any action
                        name works (use ``fail``); the rename raises
                        OSError, exercising the commit retry ladder
                        and — once retries are exhausted — the
                        roll-back path that un-renames every already
                        published file.
    control.signal.stale
                        per control-loop tick (ctx: tick;
                        control/loop.py ControlLoop.tick).  Any action
                        name works (use ``stale``); the tick reads a
                        FROZEN copy of the previous registry snapshot
                        instead of a fresh one — an empty delta, as if
                        the metrics pipeline wedged.  Chaos tests
                        assert the rules decay to no-ops on frozen
                        signals instead of oscillating.
    control.actuate.drop
                        per derived control decision, before actuation
                        (ctx: rule, action; control/loop.py
                        ControlLoop.tick).  Any action name works (use
                        ``drop``); the decision is lost in flight —
                        never applied, recorded with dropped=true.
                        Safe by design: decisions are idempotent and
                        re-derived from fresh signals next tick, so a
                        dropped actuation only delays convergence by
                        one interval.
    cluster.driver.crash
                        named driver-death points, all routed through
                        ``faults.crash_point`` (ctx: point, plus
                        site-specific keys like round or job).  Any
                        action name works (use ``kill``); the DRIVER
                        process SIGKILLs itself on the spot — no
                        cleanup, no atexit, exactly an OOM-killed or
                        power-cut driver.  Filter on ``point=`` to pick
                        the death site: ``dispatch`` (top of a fragment
                        dispatch round, cluster/exec.py), ``shuffle_read``
                        (first reduce-side fetch, cluster/exec.py),
                        ``write.commit`` (mid-rename during job commit,
                        io/writer.py), ``drain`` (mid graceful drain,
                        cluster/driver.py).  Recovery tests pair it
                        with reattachGraceSeconds + journal.dir and
                        rebuild via ClusterDriver.recover().
    cluster.journal.torn
                        after a journal group-commit writes its batch
                        (cluster/journal.py).  Any action name works
                        (use ``torn``); the freshly appended tail is
                        sheared mid-record, as if the process died
                        inside the write syscall — replay must heal the
                        torn tail back to the last intact record.
    cluster.journal.fsync.fail
                        on the journal's group-commit fsync
                        (cluster/journal.py).  Any action name works
                        (use ``fail``); the fsync raises OSError.  The
                        journal ABSORBS the failure — counts
                        journal_fsync_failures and degrades to
                        flush-only durability — rather than failing
                        the query.

Trigger keys (all optional):

    nth=N      first eligible hit that fires (1-based, default 1) —
               "reset after 2 frames" is ``nth=3`` on a frame point
    times=N    how many hits fire once triggered (default 1 so a retry
               can succeed; 0 = every hit forever).  Rules carrying
               ``until_rows`` default to 0: the row threshold is the
               natural stop condition
    p=F        per-hit probability, drawn from the rule's seeded PRNG
    seconds=F  action parameter (stall duration)
    until_rows=N  fire only when the site reports a ``rows`` context
               above N (sites that report no row count never match)

Any other ``key=value`` is a FILTER compared (as strings) against the
call-site context, e.g. ``shuffle=9,part=0`` scopes a rule to one
partition stream and ``frame=2`` fires on the third frame regardless of
how many eligible hits preceded it.

Determinism: every rule owns a ``random.Random`` seeded from
``spark.rapids.test.faults.seed`` plus the rule's index and text, so a
fault plan replays identically run to run and process to process.
Counters live on the registry instance — components build ONE registry
at construction (transport, catalog), so a ``times=1`` rule fires once
per component lifetime, not once per fetch attempt.
"""
from __future__ import annotations

import random
import threading

from spark_rapids_tpu.conf import TEST_FAULTS, TEST_FAULTS_SEED

__all__ = ["FaultRegistry", "FaultRule", "FaultAction", "InjectedFault",
           "KNOWN_POINTS", "crash_point"]

#: every injection point wired into the engine (the module docstring
#: documents each).  enginelint RL005 cross-checks this registry against
#: the live ``.check("point", ...)`` call sites in both directions, so a
#: renamed site or a stale entry fails tests/test_enginelint.py instead
#: of silently turning a fault plan into a no-op.
KNOWN_POINTS = frozenset({
    "tcp.server.frame",
    "tcp.client.connect",
    "store.fetch",
    "shuffle.peer.hang",
    "shuffle.peer.dead",
    "spill.disk.corrupt",
    "spill.disk.enospc",
    "mesh.slice.lost",
    "memory.oom",
    "memory.oom.until_rows",
    "memory.grant.stall",
    "memory.governor.oom_storm",
    "cache.result.corrupt",
    "admission.tenant.storm",
    "cluster.worker.dead",
    "cluster.worker.hang",
    "cluster.worker.slow",
    "cluster.worker.flaky",
    "cluster.migrate.drop",
    "cluster.rpc.drop",
    "io.write.partial",
    "io.write.commit.drop",
    "io.write.rename.fail",
    "control.signal.stale",
    "control.actuate.drop",
    "cluster.driver.crash",
    "cluster.journal.torn",
    "cluster.journal.fsync.fail",
})

#: keys with registry-level meaning; everything else in a rule is a
#: context filter
_RESERVED = ("nth", "times", "p", "seconds", "until_rows")


class InjectedFault(RuntimeError):
    """Raised by injection sites whose action surfaces as an error."""


class FaultRule:
    def __init__(self, index: int, text: str, seed: int):
        self.text = text
        point, _, rest = text.partition(":")
        self.point = point.strip()
        if not self.point or not rest.strip():
            raise ValueError(f"fault rule {text!r}: want 'point:action"
                             "[,k=v...]'")
        parts = [p.strip() for p in rest.split(",")]
        self.action = parts[0]
        self.params: dict[str, str] = {}
        for kv in parts[1:]:
            k, sep, v = kv.partition("=")
            if not sep:
                raise ValueError(f"fault rule {text!r}: bad param {kv!r}")
            self.params[k.strip()] = v.strip()
        self.nth = int(self.params.get("nth", 1))
        self.until_rows = (int(self.params["until_rows"])
                           if "until_rows" in self.params else None)
        # until_rows rules fire forever by default: the row threshold,
        # not a hit budget, is what stops them.  ``.dead`` points also
        # default to forever — a dead peer stays dead unless the plan
        # explicitly revives it with times=N
        default_times = (0 if self.until_rows is not None
                         or self.point.endswith(".dead") else 1)
        self.times = int(self.params.get("times", default_times))
        self.p = float(self.params.get("p", 1.0))
        self.filters = {k: v for k, v in self.params.items()
                        if k not in _RESERVED}
        self.rng = random.Random(f"{seed}:{index}:{text}")
        self.hits = 0
        self.fired = 0

    def _try_fire(self, ctx: dict) -> bool:
        if self.until_rows is not None:
            rows = ctx.get("rows")
            if rows is None or int(rows) <= self.until_rows:
                return False
        for k, v in self.filters.items():
            if k not in ctx or str(ctx[k]) != v:
                return False
        self.hits += 1
        if self.hits < self.nth:
            return False
        if self.times > 0 and self.fired >= self.times:
            return False
        if self.p < 1.0 and self.rng.random() >= self.p:
            return False
        self.fired += 1
        return True


class FaultAction:
    """What an injection site got back: the action name, its params,
    and the rule's seeded PRNG (for e.g. picking the corrupted byte)."""

    __slots__ = ("point", "action", "params", "rng")

    def __init__(self, rule: FaultRule):
        self.point = rule.point
        self.action = rule.action
        self.params = rule.params
        self.rng = rule.rng

    def param(self, key: str, default: float) -> float:
        return float(self.params.get(key, default))


def crash_point(faults, point: str, **ctx) -> None:
    """Driver-death injection site: when a ``cluster.driver.crash``
    rule matches (``point=`` filters pick the site), SIGKILL the
    CURRENT process — no cleanup, no atexit, the same instant death as
    an OOM-killed driver.  One shared helper so enginelint sees exactly
    one call site for the point."""
    if faults is None:
        return
    if faults.check("cluster.driver.crash", point=point, **ctx) is not None:
        import os
        import signal
        os.kill(os.getpid(), signal.SIGKILL)


class FaultRegistry:
    """Parsed fault plan + firing state.  Thread-safe: the TCP server
    checks points from its per-connection threads."""

    def __init__(self, spec: str, seed: int = 0):
        self.spec = spec
        self.seed = seed
        self.rules = [FaultRule(i, r.strip(), seed)
                      for i, r in enumerate(spec.split(";")) if r.strip()]
        self._lock = threading.Lock()
        #: audit log of fired injections: (point, action, ctx)
        self.log: list[tuple[str, str, dict]] = []

    @classmethod
    def from_conf(cls, conf) -> "FaultRegistry | None":
        """None (inert) unless spark.rapids.test.faults is set.  Accepts
        a TpuConf or a raw settings dict."""
        if conf is None:
            return None
        settings = conf.settings if hasattr(conf, "settings") else dict(conf)
        spec = TEST_FAULTS.get(settings)
        if not spec:
            return None
        return cls(spec, TEST_FAULTS_SEED.get(settings))

    def check(self, point: str, /, **ctx) -> FaultAction | None:
        """Called by an injection site; returns the action to perform
        when a rule on this point matches and its trigger fires."""
        with self._lock:
            for rule in self.rules:
                if rule.point != point:
                    continue
                if rule._try_fire(ctx):
                    self.log.append((point, rule.action, dict(ctx)))
                    # chaos runs assert injection actually fired via the
                    # process metrics registry (obs.registry is stdlib-
                    # only; this class only exists when faults are on)
                    from spark_rapids_tpu.obs.registry import get_registry
                    reg = get_registry()
                    reg.inc("faults.injected")
                    reg.inc(f"faults.injected.{point}")
                    return FaultAction(rule)
        return None

    def fired_count(self, point: str | None = None) -> int:
        with self._lock:
            return len([1 for p, _, _ in self.log
                        if point is None or p == point])
