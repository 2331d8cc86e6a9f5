"""Query lifecycle control plane: deadlines, cooperative cancellation,
admission control.

Reference mapping: the plugin leans on Spark's task-kill machinery —
``TaskContext.isInterrupted`` polled inside long loops, and
GpuSemaphore releasing the device when a task is killed
(GpuSemaphore.scala:74-126) — so one runaway task cannot wedge the GPU
for the queries queued behind it.  This standalone engine has no Spark
scheduler to inherit that from, so the equivalent plane lives here:

* :class:`QueryLifecycle` — a per-query handle minted in ``ExecCtx``
  alongside the query id.  It carries a cancellation
  ``threading.Event`` plus a monotonic deadline
  (``spark.rapids.sql.queryTimeout`` or ``collect(timeout=...)``) and
  moves through ``ADMITTED -> RUNNING -> {FINISHED, FAILED, CANCELLED,
  DEADLINE_EXCEEDED}``.  Cancellation is **cooperative**: the engine
  calls :meth:`QueryLifecycle.check` at its chokepoints (every
  ``ctx.dispatch``/``dispatch_retry`` entry, every drain batch
  boundary, the shuffle retry ladder's backoff waits, the recovery
  recompute loop, spill I/O, the pandas-UDF slot queue) and the first
  check after a cancel/deadline raises a **terminal** error.

* :class:`QueryCancelled` / :class:`QueryDeadlineExceeded` — terminal
  taxonomy in the ``shuffle/errors.py`` style: ``terminal = True`` is
  a class attribute so every retry ladder (OOM split-and-retry in
  memory/retry.py, the shuffle fetch ladder, stage recovery) can
  refuse to swallow them with one ``getattr(ex, "terminal", False)``
  check and no import.

* :class:`AdmissionController` — session-level admission bounding
  concurrent queries (``spark.rapids.sql.admission.*``).  Beyond the
  queue bound (or queue wait timeout, or after shutdown began) new
  queries are load-shed with :class:`QueryRejected` instead of piling
  onto the DeviceSemaphore and worker pool.  Admission is
  **weighted-fair across tenants** (``collect(tenant=...)`` or the
  ``spark.rapids.sql.tenant`` default): each tenant owns a FIFO queue
  and a virtual-time stride — the next admitted query comes from the
  backlogged tenant with the smallest virtual time, which converges on
  ``tenantWeights`` shares under saturation while a SINGLE tenant
  degenerates to exactly the old FIFO token deque.  Queue bounds and
  per-tenant ``tenantMaxConcurrent`` caps apply per tenant, so one
  storming tenant sheds only itself.  When the cross-query memory
  governor is enabled the session also wires its pressure hook here:
  sustained device occupancy above the shed watermark rejects NEW
  queries rather than admitting them into an OOM-retry storm — but
  only for tenants AT OR ABOVE their weighted share of the running
  set, so the noisy tenant absorbs the shed, not its neighbors
  (memory/governor.py; the governor first evicts the result cache,
  its lowest-priority occupant, before any query is shed).  A query
  cancelled while still QUEUED releases its queue slot and surfaces
  ``QueryCancelled`` (counted once by the cancel itself) — never
  ``queries_rejected``.

Post-cancel invariants (asserted by tests/test_lifecycle.py): the
DeviceSemaphore is back at full capacity, the spill directory is
empty, parked spillable batches are closed, and the peer's server
sessions for the dead query are dropped — cancellation unwinds through
the same ``finally`` blocks as success, it never leaks by design.

Dependency discipline: stdlib + conf + obs.registry only, so hot
modules may import this at module level without dragging jax in.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from spark_rapids_tpu.conf import ConfEntry, register
from spark_rapids_tpu.obs.registry import get_registry

__all__ = [
    "QueryLifecycle", "AdmissionController", "QueryLifecycleError",
    "QueryCancelled", "QueryDeadlineExceeded", "QueryRejected",
    "SQL_TENANT", "parse_tenant_map",
    "ADMITTED", "RUNNING", "FINISHED", "FAILED", "CANCELLED",
    "DEADLINE_EXCEEDED",
]

QUERY_TIMEOUT = register(ConfEntry(
    "spark.rapids.sql.queryTimeout", 0.0,
    "Per-query deadline in seconds (0 disables). Measured on the "
    "monotonic clock from query start; once exceeded, the next "
    "cooperative cancellation point (dispatch entry, drain batch "
    "boundary, shuffle backoff wait, spill I/O, UDF slot acquire) "
    "raises the terminal QueryDeadlineExceeded and the query unwinds, "
    "releasing the device semaphore and spill files on the way out. "
    "DataFrame.collect(timeout=...) overrides it per call (the "
    "tighter of the two wins).", conv=float))
ADMISSION_MAX_CONCURRENT = register(ConfEntry(
    "spark.rapids.sql.admission.maxConcurrentQueries", 0,
    "Queries allowed to run concurrently per session (0 = unbounded). "
    "Excess queries wait FIFO in the admission queue instead of piling "
    "onto the device semaphore and drain worker pool; size it near the "
    "device concurrency (spark.rapids.sql.concurrentDeviceTasks) so "
    "admitted queries actually progress (reference: GpuSemaphore "
    "bounding concurrent tasks on the GPU).", conv=int))
ADMISSION_MAX_QUEUED = register(ConfEntry(
    "spark.rapids.sql.admission.maxQueuedQueries", 16,
    "Queries allowed to WAIT for admission beyond the concurrent "
    "bound. Arrivals past this are load-shed immediately with "
    "QueryRejected — under sustained overload a bounded queue keeps "
    "latency finite instead of growing it without limit.", conv=int))
ADMISSION_QUEUE_TIMEOUT = register(ConfEntry(
    "spark.rapids.sql.admission.queueTimeoutSeconds", 30.0,
    "Longest a query may wait in the admission queue before it is "
    "rejected with QueryRejected (0 = wait forever). Keeps a wedged "
    "run from silently stalling everything queued behind it.",
    conv=float))
SQL_TENANT = register(ConfEntry(
    "spark.rapids.sql.tenant", "default",
    "Tenant name queries run under when DataFrame.collect(tenant=...) "
    "does not name one. Tenants are the unit of weighted-fair "
    "admission, per-tenant queue bounds/concurrency caps, and "
    "per-tenant memory-pressure shedding — one noisy tenant cannot "
    "starve the rest. A single tenant (the default) makes admission "
    "behave exactly like the plain FIFO queue."))
ADMISSION_TENANT_WEIGHTS = register(ConfEntry(
    "spark.rapids.sql.admission.tenantWeights", "",
    "Comma-separated tenant:weight pairs (e.g. 'etl:3,dashboards:1'; "
    "unlisted tenants weigh 1). Under saturation each backlogged "
    "tenant is admitted in proportion to its weight via virtual-time "
    "stride scheduling; an idle tenant accrues no credit, so it "
    "cannot burst past its share after sitting out."))
ADMISSION_TENANT_MAX_CONCURRENT = register(ConfEntry(
    "spark.rapids.sql.admission.tenantMaxConcurrent", "",
    "Comma-separated tenant:N pairs capping how many of a tenant's "
    "queries may run concurrently (unlisted/0 = only the global "
    "maxConcurrentQueries bound applies). A capped tenant's surplus "
    "waits in ITS queue; other tenants admit past it — per-tenant "
    "caps never cause cross-tenant head-of-line blocking."))
ADMISSION_DEADLINE_ORDERING = register(ConfEntry(
    "spark.rapids.sql.admission.deadlineOrdering", False,
    "Order each tenant's admission queue earliest-deadline-first "
    "(queries carrying collect(timeout=)/queryTimeout deadlines jump "
    "ahead of unbounded ones) instead of strict FIFO. Off by default: "
    "FIFO within a tenant preserves the pre-tenant admission order "
    "byte for byte.", conv=lambda v: str(v).lower() in
    ("true", "1", "yes")))

# -- states ----------------------------------------------------------------

ADMITTED = "ADMITTED"
RUNNING = "RUNNING"
FINISHED = "FINISHED"
FAILED = "FAILED"
CANCELLED = "CANCELLED"
DEADLINE_EXCEEDED = "DEADLINE_EXCEEDED"

#: states a query never leaves
TERMINAL_STATES = frozenset({FINISHED, FAILED, CANCELLED,
                             DEADLINE_EXCEEDED})


# -- terminal taxonomy (shuffle/errors.py style) ---------------------------

class QueryLifecycleError(RuntimeError):
    """Base of the lifecycle taxonomy.  ``terminal`` mirrors the
    shuffle/errors.py convention: retry ladders check
    ``getattr(ex, "terminal", False)`` and re-raise instead of
    retrying — a cancelled query must not be split, backed off, or
    lineage-recomputed back to life."""

    terminal: bool = True

    def __init__(self, query_id: str, msg: str):
        super().__init__(msg)
        self.query_id = query_id


class QueryCancelled(QueryLifecycleError):
    """The query was cancelled (session.cancel / cancel_all / early
    consumer exit) and a cooperative checkpoint observed it."""

    def __init__(self, query_id: str, reason: str = "cancelled"):
        super().__init__(query_id,
                         f"query {query_id} cancelled: {reason}")
        self.reason = reason


class QueryDeadlineExceeded(QueryLifecycleError):
    """The query ran past its deadline (spark.rapids.sql.queryTimeout
    or collect(timeout=...))."""

    def __init__(self, query_id: str, timeout: float):
        super().__init__(query_id,
                         f"query {query_id} exceeded its deadline "
                         f"({timeout:g}s)")
        self.timeout = timeout


class QueryRejected(QueryLifecycleError):
    """Load-shed at admission: the session is shutting down, the
    admission queue is full, or the queue wait timed out.  The query
    never started, so there is nothing to unwind."""


class TargetedShed(str):
    """A pressure-hook reason that already NAMES its victim: the hook
    returned it for this tenant specifically (the control plane's SLO
    shed), so admission must reject without the over-share spare.  A
    plain-``str`` reason keeps the global-pressure semantics — a
    tenant running below its weighted share is spared, because the
    pressure is someone else's doing.  Without this distinction a
    targeted shed can never hold: the moment the victim's running
    queries drain, its active count is below share and every new
    arrival is spared straight back in."""


# -- per-query handle ------------------------------------------------------

class QueryLifecycle:
    """State machine + cancellation event + monotonic deadline for one
    query.  Thread-safe: the session cancels from its thread while
    drain workers call :meth:`check` from theirs.

    The cancellation event is the single broadcast channel: ``cancel``
    and a tripped deadline both set it, so every blocked
    ``event.wait(pause)`` (shuffle backoff, UDF slot poll) wakes
    promptly and the next :meth:`check` raises the terminal error.
    """

    def __init__(self, query_id: str, timeout: "float | None" = None,
                 tenant: str = "default"):
        self.query_id = query_id
        self.timeout = timeout if timeout and timeout > 0 else None
        self.tenant = tenant
        self.cancel_event = threading.Event()
        self._lock = threading.Lock()
        self._state = ADMITTED
        self._started_at: "float | None" = None
        self._deadline: "float | None" = None
        self._cancel_reason = "cancelled"
        # stamped by AdmissionController.admit on the admitted path;
        # the control loop's per-tenant SLOs are end-to-end (queue wait
        # + wall), so admission latency must ride along with the
        # lifecycle to the terminal observation
        self.queue_wait_s: "float | None" = None
        # set by control-enabled sessions only: emits the
        # query.tenant.<t>.e2e_seconds histogram at the terminal
        # transition.  Off by default so a static engine's counter set
        # stays byte-identical with the control plane disabled.
        self.observe_e2e = False
        # free-form execution annotations (e.g. cluster resume facts
        # from a recovered driver) surfaced on /queries and in history
        # records; empty for the overwhelming majority of queries
        self.annotations: dict = {}
        # the per-query record (open_record / seal_record below)
        self.record: "dict | None" = None
        self.spill: dict = {}
        self._record_open: "tuple | None" = None

    @classmethod
    def from_conf(cls, query_id: str, conf, timeout: "float | None" = None,
                  tenant: "str | None" = None) -> "QueryLifecycle":
        """Effective deadline = the tighter of the conf queryTimeout
        and the per-call ``timeout``; tenant defaults from
        ``spark.rapids.sql.tenant``."""
        settings = getattr(conf, "settings", None) or {}
        conf_tmo = QUERY_TIMEOUT.get(settings)
        cands = [t for t in (conf_tmo, timeout) if t and t > 0]
        return cls(query_id, timeout=min(cands) if cands else None,
                   tenant=tenant or SQL_TENANT.get(settings))

    # -- transitions -------------------------------------------------------

    @property
    def state(self) -> str:
        return self._state

    def start(self) -> None:
        """ADMITTED -> RUNNING; the deadline clock starts here, not at
        admission, so queue wait does not eat the query's budget."""
        with self._lock:
            if self._state == ADMITTED:
                self._state = RUNNING
                self._started_at = time.monotonic()
                if self.timeout is not None:
                    self._deadline = self._started_at + self.timeout

    def _observe_wall(self) -> None:
        """Record the query's wall time into the latency histograms at
        its FIRST terminal transition (queries cancelled while still
        queued never started — no wall to record)."""
        started = self._started_at
        if started is None:
            return
        wall = time.monotonic() - started
        reg = get_registry()
        reg.observe("query.wall_seconds", wall)
        reg.observe(f"query.tenant.{self.tenant}.wall_seconds", wall)
        if self.observe_e2e:
            reg.observe(f"query.tenant.{self.tenant}.e2e_seconds",
                        wall + (self.queue_wait_s or 0.0))

    def finish(self) -> bool:
        """RUNNING -> FINISHED (no-op once terminal)."""
        with self._lock:
            if self._state in TERMINAL_STATES:
                return False
            self._state = FINISHED
        self._observe_wall()
        return True

    def fail(self) -> bool:
        """RUNNING -> FAILED on a non-lifecycle error (no-op once
        terminal)."""
        with self._lock:
            if self._state in TERMINAL_STATES:
                return False
            self._state = FAILED
        self._observe_wall()
        return True

    def cancel(self, reason: str = "cancelled") -> bool:
        """Request cooperative cancellation.  Idempotent: only the
        first call transitions (and counts queries_cancelled); a query
        already finished/failed/deadline-exceeded is left alone and
        ``False`` is returned."""
        with self._lock:
            if self._state in TERMINAL_STATES:
                return False
            self._state = CANCELLED
            self._cancel_reason = reason
        self.cancel_event.set()
        get_registry().inc("queries_cancelled")
        self._observe_wall()
        return True

    def _expire(self) -> bool:
        with self._lock:
            if self._state in TERMINAL_STATES:
                return False
            self._state = DEADLINE_EXCEEDED
        self.cancel_event.set()
        get_registry().inc("queries_deadline_exceeded")
        self._observe_wall()
        return True

    # -- the per-query record ----------------------------------------------

    def open_record(self) -> None:
        """Start this query's record: the registry's counters as they
        stand now (a copy of one dict; no pull source is walked)."""
        self._record_open = (time.time(), get_registry().counters())

    def seal_record(self, err: "BaseException | None" = None) \
            -> "dict | None":
        """Close the record and put it in the registry's ring
        (``get_registry().recent_queries()``, served by ``/queries`` as
        ``finished``): query_id, tenant, ``state`` (the terminal state,
        or — for a query ``err`` stopped before any transition: planning
        failed, admission shed it — REJECTED / FAILED), start/end,
        ``spill`` (the query's own BufferCatalog totals) and
        ``counters`` — every registry counter that moved between
        ``open_record`` and now: the span table
        (``span.<name>.count/seconds``), the transfer and wait counters,
        and the per-program table
        (``program.<name>.launches/arg_bytes/result_bytes/dispatch_s``).
        The session calls this once the collect has unwound, so a
        cancelled query's record holds all it did.  The counters are the
        PROCESS's movement over the query's interval: exact with one
        running query, shared among queries that overlap.  Idempotent;
        None when no record was opened."""
        opened, self._record_open = self._record_open, None
        if opened is None:
            return self.record
        reg = get_registry()
        started, before = opened
        state = self._state
        if state not in TERMINAL_STATES and err is not None:
            state = "REJECTED" if isinstance(err, QueryRejected) \
                else "FAILED"
        self.record = {
            "query_id": self.query_id,
            "tenant": self.tenant,
            "state": state,
            "start_unix_s": started,
            "end_unix_s": time.time(),
            "spill": dict(self.spill),
            "counters": reg.counters_since(before),
        }
        reg.note_query(self.record)
        return self.record

    # -- cooperative checkpoints -------------------------------------------

    def remaining(self) -> "float | None":
        """Seconds to the deadline (None when no deadline; never
        negative)."""
        if self._deadline is None:
            return None
        return max(0.0, self._deadline - time.monotonic())

    def check(self) -> None:
        """The cancellation point.  Raises :class:`QueryCancelled` or
        :class:`QueryDeadlineExceeded` once the query is cancelled or
        past its deadline; otherwise returns immediately.  Cheap on
        the happy path (one Event read + one clock read)."""
        if not self.cancel_event.is_set():
            if self._deadline is None or \
                    time.monotonic() < self._deadline:
                return
            self._expire()
        state = self._state
        if state == DEADLINE_EXCEEDED:
            raise QueryDeadlineExceeded(self.query_id,
                                        self.timeout or 0.0)
        raise QueryCancelled(self.query_id, self._cancel_reason)

    def wait(self, seconds: float) -> None:
        """Interruptible sleep: waits up to ``seconds`` (capped at the
        time left to the deadline) on the cancel event, then
        :meth:`check`.  Replaces ``time.sleep`` in retry backoff so a
        cancel or deadline aborts the ladder mid-pause instead of
        after it."""
        self.check()
        rem = self.remaining()
        pause = seconds if rem is None else min(seconds, rem)
        if pause > 0:
            self.cancel_event.wait(pause)
        self.check()


# -- session-level admission -----------------------------------------------

def parse_tenant_map(spec: str, conv=float) -> dict:
    """'a:3,b:1' -> {'a': 3.0, 'b': 1.0} (tenantWeights /
    tenantMaxConcurrent grammar; blanks ignored, bad pairs raise)."""
    out: dict = {}
    for pair in (spec or "").split(","):
        pair = pair.strip()
        if not pair:
            continue
        name, sep, val = pair.rpartition(":")
        if not sep or not name.strip():
            raise ValueError(f"bad tenant map entry {pair!r}: "
                             "want 'tenant:value'")
        out[name.strip()] = conv(val.strip())
    return out


class _TenantState:
    """One tenant's admission book-keeping: its FIFO/EDF wait queue,
    running count, and virtual-time stride (1/weight per admission)."""

    __slots__ = ("name", "weight", "max_concurrent", "active", "vtime",
                 "queue")

    def __init__(self, name: str, weight: float = 1.0,
                 max_concurrent: int = 0):
        self.name = name
        self.weight = weight if weight > 0 else 1.0
        self.max_concurrent = max_concurrent
        self.active = 0
        self.vtime = 0.0
        self.queue: deque = deque()


class _Waiter:
    __slots__ = ("tenant", "seq", "deadline_key")

    def __init__(self, tenant: _TenantState, seq: int,
                 deadline_key: float):
        self.tenant = tenant
        self.seq = seq
        self.deadline_key = deadline_key


class AdmissionController:
    """Weighted-fair admission: at most ``max_concurrent`` queries run
    and at most ``max_queued`` wait PER TENANT; the rest are load-shed
    with :class:`QueryRejected`.  One condition variable guards every
    counter.  Each tenant keeps its own FIFO queue; when a slot frees,
    the backlogged tenant with the smallest virtual time admits its
    head, and admitting advances that tenant's virtual time by
    1/weight — stride scheduling, so saturated tenants converge on
    ``tenantWeights`` shares while a single tenant reduces to exactly
    the old FIFO token deque (a waiter only proceeds when it is the
    deterministic selection, so a late arrival can never overtake a
    same-tenant query that queued first)."""

    def __init__(self, max_concurrent: int = 0, max_queued: int = 16,
                 queue_timeout: float = 30.0,
                 tenant_weights: "dict | None" = None,
                 tenant_max_concurrent: "dict | None" = None,
                 deadline_ordering: bool = False):
        self.max_concurrent = max_concurrent
        self.max_queued = max_queued
        self.queue_timeout = queue_timeout
        self.tenant_weights = dict(tenant_weights or {})
        self.tenant_max_concurrent = dict(tenant_max_concurrent or {})
        self.deadline_ordering = deadline_ordering
        self._cond = threading.Condition()
        self._tenants: "dict[str, _TenantState]" = {}
        self._active = 0
        self._seq = 0
        self._vclock = 0.0
        self._shutdown = False
        #: audit trail of admissions in order — (tenant, query_id) —
        #: so fairness is observable, not just statistical (the CI
        #: serving gate asserts weighted order against this)
        self.admission_log: deque = deque(maxlen=1024)
        # memory-pressure shed hook (memory/governor.py, wired by the
        # session when the governor is enabled): a callable returning a
        # reason string when NEW admissions should be load-shed —
        # sustained occupancy above the shed watermark — or None.
        # Late-bound attribute, not an import: this module stays
        # stdlib + conf + obs so hot modules can import it freely
        self.pressure_hook = None
        # serving-tier fault registry (faults.py, wired by the session
        # when spark.rapids.test.faults is set) for the
        # admission.tenant.storm injection point — late-bound for the
        # same dependency reason as pressure_hook
        self.faults = None

    @classmethod
    def from_conf(cls, conf) -> "AdmissionController":
        settings = getattr(conf, "settings", None) or {}
        return cls(
            max_concurrent=ADMISSION_MAX_CONCURRENT.get(settings),
            max_queued=ADMISSION_MAX_QUEUED.get(settings),
            queue_timeout=ADMISSION_QUEUE_TIMEOUT.get(settings),
            tenant_weights=parse_tenant_map(
                ADMISSION_TENANT_WEIGHTS.get(settings)),
            tenant_max_concurrent=parse_tenant_map(
                ADMISSION_TENANT_MAX_CONCURRENT.get(settings), conv=int),
            deadline_ordering=ADMISSION_DEADLINE_ORDERING.get(settings))

    @property
    def active(self) -> int:
        with self._cond:
            return self._active

    @property
    def queued(self) -> int:
        with self._cond:
            return sum(len(t.queue) for t in self._tenants.values())

    @property
    def shutting_down(self) -> bool:
        return self._shutdown

    def tenant_stats(self) -> dict:
        """{tenant: {active, queued, weight, vtime}} — the fairness
        ledger (bench observability block, chaos assertions)."""
        with self._cond:
            return {t.name: {"active": t.active, "queued": len(t.queue),
                             "weight": t.weight, "vtime": t.vtime}
                    for t in self._tenants.values()}

    # -- internals (under self._cond) --------------------------------------

    def _tenant_locked(self, name: str) -> _TenantState:
        st = self._tenants.get(name)
        if st is None:
            st = _TenantState(
                name, weight=self.tenant_weights.get(name, 1.0),
                max_concurrent=int(
                    self.tenant_max_concurrent.get(name, 0)))
            self._tenants[name] = st
        return st

    def _head_locked(self, st: _TenantState) -> "_Waiter | None":
        if not st.queue:
            return None
        if not self.deadline_ordering:
            return st.queue[0]
        return min(st.queue, key=lambda w: (w.deadline_key, w.seq))

    def _select_locked(self) -> "_Waiter | None":
        """The deterministic next admission: among tenants with
        waiters and per-tenant headroom, the smallest (vtime, head
        seq).  Tenants at their own cap are skipped — a capped
        tenant's backlog never blocks its neighbors."""
        best = None
        best_key = None
        for st in self._tenants.values():
            if st.max_concurrent > 0 and st.active >= st.max_concurrent:
                continue
            head = self._head_locked(st)
            if head is None:
                continue
            key = (st.vtime, head.seq)
            if best_key is None or key < best_key:
                best, best_key = head, key
        return best

    def _admitted_locked(self, st: _TenantState, query_id: str) -> None:
        self._active += 1
        st.active += 1
        # stride bookkeeping: service starts at max(own vtime, the
        # global virtual clock) so an idle tenant re-enters at "now"
        # with no hoarded credit, then advances by 1/weight
        start = max(st.vtime, self._vclock)
        st.vtime = start + 1.0 / st.weight
        self._vclock = start
        self.admission_log.append((st.name, query_id))
        reg = get_registry()
        reg.inc("queries_admitted")
        reg.inc(f"admission.tenant.{st.name}.admitted")

    def _tenant_over_share(self, tenant: str) -> bool:
        """Is this tenant at/above its weighted share of the running
        set?  The per-tenant pressure-shed predicate: with a single
        tenant this is always True (identical to the old
        shed-everyone behavior); a tenant running BELOW its share is
        spared — the pressure is someone else's doing."""
        with self._cond:
            st = self._tenant_locked(tenant)
            total = self._active
            if total <= 0:
                return True
            sum_w = sum(t.weight for t in self._tenants.values()
                        if t.active > 0 or t is st)
            return st.active * sum_w >= total * st.weight

    def _reject(self, reg, tenant: str, query_id: str,
                why: str) -> "QueryRejected":
        reg.inc("queries_rejected")
        reg.inc(f"admission.tenant.{tenant}.rejected")
        return QueryRejected(query_id,
                             f"query {query_id} rejected: {why}")

    def admit(self, query_id: str = "?", timeout: "float | None" = None,
              tenant: str = "default",
              lifecycle: "QueryLifecycle | None" = None) -> None:
        """Block until admitted.  Raises :class:`QueryRejected` when
        the session is shutting down, the tenant's wait queue is full,
        the queue wait exceeds ``timeout`` (default: the
        queueTimeoutSeconds conf; 0 waits forever), or the memory
        governor's pressure hook reports sustained overload AND this
        tenant is at/above its weighted share.  With ``lifecycle``,
        a cancel landing while still queued releases the queue slot
        and raises the terminal :class:`QueryCancelled` instead —
        counted once as ``queries_cancelled`` by the cancel itself,
        never as a rejection."""
        reg = get_registry()
        t_admit = time.monotonic()
        tmo = self.queue_timeout if timeout is None else timeout
        faults = self.faults
        if faults is not None:
            act = faults.check("admission.tenant.storm", tenant=tenant,
                               query_id=query_id)
            if act is not None:
                # the tenant's traffic storm saturated its own queue:
                # shed THIS arrival exactly like a full tenant queue
                raise self._reject(
                    reg, tenant, query_id,
                    f"injected admission storm on tenant {tenant!r}")
        hook = self.pressure_hook
        if hook is not None:
            # checked OUTSIDE the condition (the hook takes the
            # governor's own lock) and before queueing: a query shed
            # for memory pressure never occupied a queue slot.  Only
            # the over-share tenant absorbs the shed.  The hook sees
            # the tenant so a tenant-scoped policy (the control
            # plane's SLO shed) can target exactly one tenant while
            # returning None for its neighbors.
            reason = hook(tenant)
            if reason:
                if isinstance(reason, TargetedShed) or \
                        self._tenant_over_share(tenant):
                    raise self._reject(reg, tenant, query_id, reason)
                reg.inc("admission_pressure_spared")
                reg.inc(f"admission.tenant.{tenant}.pressure_spared")
        with self._cond:
            st = self._tenant_locked(tenant)
            if self._shutdown:
                raise self._reject(reg, tenant, query_id,
                                   "session is shutting down")
            if self.max_concurrent <= 0:
                self._admitted_locked(st, query_id)
                waited = time.monotonic() - t_admit
                reg.observe("admission.queue_wait_seconds", waited)
                if lifecycle is not None:
                    lifecycle.queue_wait_s = waited
                return
            if self._active < self.max_concurrent \
                    and not any(t.queue for t in self._tenants.values()) \
                    and (st.max_concurrent <= 0
                         or st.active < st.max_concurrent):
                self._admitted_locked(st, query_id)
                waited = time.monotonic() - t_admit
                reg.observe("admission.queue_wait_seconds", waited)
                if lifecycle is not None:
                    lifecycle.queue_wait_s = waited
                return
            if len(st.queue) >= self.max_queued:
                raise self._reject(
                    reg, tenant, query_id,
                    f"admission queue full for tenant {tenant!r} "
                    f"({len(st.queue)} >= "
                    f"maxQueuedQueries={self.max_queued})")
            self._seq += 1
            dkey = float("inf")
            if lifecycle is not None and lifecycle.timeout:
                dkey = time.monotonic() + lifecycle.timeout
            me = _Waiter(st, self._seq, dkey)
            st.queue.append(me)
            deadline = time.monotonic() + tmo if tmo and tmo > 0 \
                else None
            admitted = False
            try:
                while True:
                    if self._shutdown:
                        raise self._reject(reg, tenant, query_id,
                                           "session is shutting down")
                    if lifecycle is not None:
                        # cancel-while-queued: surface the terminal
                        # lifecycle error; the finally below frees the
                        # queue slot, and queries_cancelled was already
                        # counted exactly once by cancel() itself
                        lifecycle.check()
                    if self._active < self.max_concurrent and \
                            self._select_locked() is me:
                        st.queue.remove(me)
                        self._admitted_locked(st, query_id)
                        admitted = True
                        waited = time.monotonic() - t_admit
                        reg.observe("admission.queue_wait_seconds",
                                    waited)
                        if lifecycle is not None:
                            lifecycle.queue_wait_s = waited
                        return
                    rem = None if deadline is None \
                        else deadline - time.monotonic()
                    if rem is not None and rem <= 0:
                        raise self._reject(
                            reg, tenant, query_id,
                            f"waited {tmo:g}s in the admission queue "
                            "(queueTimeoutSeconds)")
                    # a condition wait cannot observe the lifecycle's
                    # cancel event, so cancellable waiters poll in
                    # bounded slices
                    if lifecycle is not None:
                        rem = 0.05 if rem is None else min(rem, 0.05)
                    self._cond.wait(rem)
            finally:
                if not admitted:
                    try:
                        st.queue.remove(me)
                    except ValueError:
                        pass
                    # the selection may have changed: wake the queue
                    self._cond.notify_all()

    def set_max_concurrent(self, n: int) -> None:
        """Retune the global cap at runtime (the control plane's AIMD
        actuation).  Raising it wakes the queue so newly-legal waiters
        admit immediately; lowering it never evicts running queries —
        the active set just drains below the new cap before anyone
        else admits."""
        with self._cond:
            self.max_concurrent = int(n)
            self._cond.notify_all()

    def release(self, tenant: str = "default") -> None:
        """One admitted query finished (success, failure, or cancel):
        free its slot — global and per-tenant — and wake the queue."""
        with self._cond:
            if self._active > 0:
                self._active -= 1
            st = self._tenants.get(tenant)
            if st is not None and st.active > 0:
                st.active -= 1
            self._cond.notify_all()

    def begin_shutdown(self) -> None:
        """Stop admitting: every queued waiter and every future
        ``admit`` raises :class:`QueryRejected`.  Already-admitted
        queries are unaffected (the session drains or cancels them)."""
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()
