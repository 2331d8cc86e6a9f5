"""Cross-query HBM memory governor: accounting, arbitration, shedding.

PR 5 made the engine admit N concurrent queries; each one owns a
private :class:`~spark_rapids_tpu.memory.catalog.BufferCatalog`, so the
memory plane was query-blind: query A's OOM retry spilled a blind
quarter of A's budget while B did the same, each evicting what the
other was about to unspill — the thrash/livelock shape ROADMAP item 4
names as the serving-tier failure mode.  The reference arbitrates this
with GpuSemaphore task gating, per-buffer SpillPriorities, and the
DeviceMemoryEventHandler alloc-failure callback (PAPER.md §L1-L2);
PJRT exposes none of those hooks, so the TPU-native analog is this
process-wide governor layered over the per-query catalogs:

* **Per-query accounting** — every catalog registers under its
  ``ExecCtx`` query_id; every ``add_batch``/pin/release/spill/unspill
  moves the owner's device-byte ledger, so the MetricsRegistry (pull
  source ``governor``), EXPLAIN ANALYZE footers, and diagnostic
  bundles show who holds HBM, not just that it is held.

* **Need-sized, ownership-aware arbitration** — :meth:`reclaim`
  replaces the blind ``device_limit // 4`` sweep: the requester spills
  its OWN lowest-priority buffers first, sized to the failed
  allocation (with a conf'd floor), then — only for the shortfall —
  idle peers' unpinned buffers, youngest owner first.  Pinned working
  sets are never touched (the catalog only ever spills refcount==0
  entries), and **wound-wait** ordering (older query wins) breaks the
  two-mid-retry-queries livelock: an older requester may evict a
  younger peer's spillables, a younger requester must wait for the
  older to release instead of evicting it.

* **Watermarks + background spill** — aggregate occupancy above the
  high watermark wakes a daemon that pushes idle queries' buffers to
  host until the low watermark, off the query hot path.

* **Bounded, lifecycle-integrated grant waits** — a younger loser
  parks in :meth:`reclaim` with a reservation on the wanted bytes,
  re-checking its ``QueryLifecycle`` every wakeup so cancellation and
  deadlines abort the wait (terminal errors are never swallowed), and
  gives up after ``grantTimeoutSeconds`` so a wedged peer cannot hold
  it forever.

* **Pressure-shed admission** — sustained aggregate occupancy above
  the shed watermark makes :meth:`admission_pressure` (wired into
  ``AdmissionController.pressure_hook`` by the session) reject NEW
  queries with ``QueryRejected`` instead of admitting them into an
  OOM-retry storm.

Gate-off reversibility: with ``spark.rapids.memory.governor.enabled=
false`` nothing registers, catalogs keep ``governor=None``, and every
retry path falls back to the pre-governor quarter-budget sweep —
plans and single-query behavior are byte-identical to the ungoverned
engine (tests/test_memory_governor.py proves it).

Dependency discipline: stdlib + conf + obs.registry only (like
exec/lifecycle.py), so the catalog and retry modules import this at
module level without dragging jax into light paths.
"""
from __future__ import annotations

import threading
import time
import weakref

from spark_rapids_tpu.conf import (ConfEntry, bool_conf, float_conf,
                                   int_conf, register)
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["MemoryGovernor", "get_governor", "maybe_register"]


GOVERNOR_ENABLED = bool_conf(
    "spark.rapids.memory.governor.enabled", True,
    "Cross-query HBM memory governor: per-query device-byte "
    "accounting, need-sized ownership-aware spill arbitration with "
    "wound-wait ordering (older query wins), watermark-driven "
    "background spill, and pressure-shed admission.  Disabled: "
    "catalogs stay query-blind and OOM retries fall back to the "
    "legacy quarter-budget spill sweep — byte-identical to the "
    "pre-governor engine.")
GOVERNOR_MIN_SPILL = register(ConfEntry(
    "spark.rapids.memory.governor.minSpillBytes", 16 << 20,
    "Floor for a need-sized spill request: an OOM retry asks the "
    "governor for max(failed allocation estimate, this floor) instead "
    "of the legacy blind quarter of the device budget, so tiny "
    "allocations stop evicting whole working sets.", conv=int))
GOVERNOR_HIGH_WM = float_conf(
    "spark.rapids.memory.governor.highWatermark", 0.85,
    "Aggregate device occupancy fraction above which the governor's "
    "background thread starts spilling idle queries' lowest-priority "
    "buffers to host (proactive, off the query hot path).")
GOVERNOR_LOW_WM = float_conf(
    "spark.rapids.memory.governor.lowWatermark", 0.65,
    "Background spill stops once aggregate occupancy is back under "
    "this fraction (hysteresis partner of highWatermark).")
GOVERNOR_SHED_WM = float_conf(
    "spark.rapids.memory.governor.shedWatermark", 0.95,
    "Aggregate occupancy fraction above which — once sustained for "
    "shedHoldSeconds — NEW queries are load-shed at admission with "
    "QueryRejected instead of joining an OOM-retry storm.  Admitted "
    "queries are never shed, only throttled by arbitration.")
GOVERNOR_SHED_HOLD = float_conf(
    "spark.rapids.memory.governor.shedHoldSeconds", 1.0,
    "How long aggregate occupancy must stay above shedWatermark "
    "before admission sheds — a single transient spike between two "
    "batches must not reject a query.")
GOVERNOR_GRANT_TIMEOUT = float_conf(
    "spark.rapids.memory.governor.grantTimeoutSeconds", 10.0,
    "Longest a wound-wait loser blocks for a memory grant before the "
    "OOM propagates to its split-and-retry ladder.  Cancellation and "
    "deadlines abort the wait early at every wakeup (the wait is a "
    "cooperative cancellation point); 0 disables waiting entirely.")
GOVERNOR_POLL_MS = int_conf(
    "spark.rapids.memory.governor.pollIntervalMs", 50,
    "Background watermark-spill thread poll interval.  The thread "
    "exists only while governed catalogs are registered and parks on "
    "an event otherwise.")


class _QueryState:
    """Ledger for one registered query (one catalog)."""

    __slots__ = ("query_id", "seq", "cat_ref", "lifecycle",
                 "device_bytes", "pinned_bytes", "peak_bytes",
                 "reserved_bytes")

    def __init__(self, query_id: str, seq: int, catalog, lifecycle):
        self.query_id = query_id
        self.seq = seq                      # admission order: lower = older
        self.cat_ref = weakref.ref(catalog)
        self.lifecycle = lifecycle
        self.device_bytes = 0
        self.pinned_bytes = 0
        self.peak_bytes = 0
        self.reserved_bytes = 0


class MemoryGovernor:
    """Process-wide arbiter over every registered per-query catalog.

    All public entry points are thread-safe; ``_cond`` guards the
    ledgers AND doubles as the grant-wait channel (released bytes
    notify parked waiters)."""

    def __init__(self):
        self._cond = threading.Condition()
        self._states: dict[int, _QueryState] = {}   # id(catalog) -> state
        self._seq = 0
        self._budget = 0          # max of registered catalogs' device limits
        self._over_since: float | None = None
        self._bg_thread: threading.Thread | None = None
        self._bg_wake = threading.Event()
        self._bg_stop = threading.Event()
        # conf snapshot, refreshed at each register() from that query's
        # conf — one session's settings win for process-wide knobs,
        # matching how the shared pinned arena is sized today
        self._min_spill = GOVERNOR_MIN_SPILL.default
        self._high_wm = GOVERNOR_HIGH_WM.default
        self._low_wm = GOVERNOR_LOW_WM.default
        self._shed_wm = GOVERNOR_SHED_WM.default
        self._shed_hold = GOVERNOR_SHED_HOLD.default
        self._grant_timeout = GOVERNOR_GRANT_TIMEOUT.default
        self._poll_s = GOVERNOR_POLL_MS.default / 1000.0
        # the process result cache (exec/result_cache.py), weakly held:
        # its entries are the governor's LOWEST-priority occupants —
        # unpinned, rebuildable — evicted before any query is wounded
        # or load-shed
        self._cache_ref = None
        # control-plane watermark overrides (None = static conf): the
        # register() conf refresh below would silently clobber an
        # adapted watermark on the next query, so overrides are
        # re-applied after every refresh
        self._wm_override: "tuple[float, float] | None" = None
        get_registry().register_source("governor", self._source)

    def set_watermark_overrides(self, high: "float | None",
                                low: "float | None") -> None:
        """Control-plane actuation: pin the high/low spill watermarks
        to adapted values that survive the per-query conf refresh in
        :meth:`register`.  ``(None, None)`` clears the override — the
        next register() restores the static conf values (the
        controller calls that on stop(), so a stopped control plane
        leaves no residue).  Waiters are woken: a lowered watermark
        may make spilling (and therefore grants) possible right now."""
        with self._cond:
            if high is None or low is None:
                self._wm_override = None
            else:
                self._wm_override = (float(high), float(low))
                self._high_wm, self._low_wm = self._wm_override
            self._bg_wake.set()
            self._cond.notify_all()

    def watermarks(self) -> dict:
        """Current effective watermarks (+ whether the control plane
        has them overridden) for the /control endpoint."""
        with self._cond:
            return {"high": self._high_wm, "low": self._low_wm,
                    "shed": self._shed_wm,
                    "overridden": self._wm_override is not None}

    def register_cache(self, cache) -> None:
        """Bind the process-wide result/fragment cache as the first
        eviction victim under memory pressure (weakref: the governor
        must never keep the cache alive)."""
        self._cache_ref = weakref.ref(cache)

    def _evict_cache(self, need_bytes, kind=None) -> int:
        """Drop idle cache entries; returns DEVICE bytes freed (host
        result blobs relieve RAM, not HBM, so only fragment bytes
        count toward device pressure)."""
        ref = self._cache_ref
        cache = ref() if ref is not None else None
        if cache is None:
            return 0
        dev_before = cache.device_bytes()
        freed = cache.evict(need_bytes, kind=kind)
        if freed:
            get_registry().inc("governor_cache_evict_bytes", freed)
        return dev_before - cache.device_bytes()

    # -- registration ------------------------------------------------------

    def register(self, catalog, query_id: str, lifecycle, settings) -> None:
        """Bind a per-query catalog to the governor.  Called by
        ``ExecCtx.catalog`` right after construction; the catalog
        mirrors every device-byte move here until ``unregister``."""
        self._min_spill = GOVERNOR_MIN_SPILL.get(settings)
        self._high_wm = GOVERNOR_HIGH_WM.get(settings)
        self._low_wm = GOVERNOR_LOW_WM.get(settings)
        self._shed_wm = GOVERNOR_SHED_WM.get(settings)
        self._shed_hold = GOVERNOR_SHED_HOLD.get(settings)
        self._grant_timeout = GOVERNOR_GRANT_TIMEOUT.get(settings)
        self._poll_s = max(GOVERNOR_POLL_MS.get(settings), 1) / 1000.0
        ov = self._wm_override
        if ov is not None:
            self._high_wm, self._low_wm = ov
        with self._cond:
            st = _QueryState(query_id, self._seq, catalog, lifecycle)
            # a catalog garbage-collected without close() (leaked by
            # its owner) must not pin its ledger forever: stale bytes
            # would inflate aggregate occupancy for every later query,
            # turning headroom permanently negative
            key = id(catalog)
            st.cat_ref = weakref.ref(
                catalog, lambda _r, _s=self, _k=key: _s._drop_dead(_k))
            self._seq += 1
            self._states[key] = st
            self._budget = max((s.cat_ref().device_limit
                                for s in self._states.values()
                                if s.cat_ref() is not None), default=0)
            catalog.governor = self
            catalog.query_id = query_id
            self._ensure_bg_locked()

    def unregister(self, catalog) -> None:
        """Drop a catalog's ledger (catalog.close()).  Its bytes are
        already zero by then — close() dropped every entry — but the
        ledger is cleared defensively and waiters are woken since a
        whole query's worth of HBM just went away."""
        with self._cond:
            self._states.pop(id(catalog), None)
            catalog.governor = None
            if not self._states:
                self._stop_bg_locked()
            self._cond.notify_all()

    def _drop_dead(self, key: int) -> None:
        """Weakref callback: a governed catalog died without close().
        Drop its ledger so leaked bytes cannot masquerade as occupancy
        (``_cond`` is an RLock underneath, so firing on a thread that
        already holds it is safe)."""
        with self._cond:
            st = self._states.get(key)
            if st is not None and st.cat_ref() is None:
                del self._states[key]
                if not self._states:
                    self._stop_bg_locked()
                self._cond.notify_all()

    # -- accounting --------------------------------------------------------

    def account(self, catalog, delta: int) -> None:
        """Mirror a device_used move (+add/unspill, -spill/remove) into
        the owner's ledger.  Called under the catalog lock from the
        sites that mutate ``device_used`` — cheap: one dict hit."""
        with self._cond:
            st = self._states.get(id(catalog))
            if st is None:
                return
            st.device_bytes += delta
            if st.device_bytes > st.peak_bytes:
                st.peak_bytes = st.device_bytes
            if delta < 0:
                # memory came free: wake grant waiters
                self._cond.notify_all()
            else:
                self._update_pressure_locked()

    def account_pinned(self, catalog, delta: int) -> None:
        """Mirror a pin/unpin transition (refcount 0->1 / 1->0) so
        arbitration can see how much of a query's footprint is
        working set vs spillable."""
        with self._cond:
            st = self._states.get(id(catalog))
            if st is not None:
                st.pinned_bytes += delta

    # -- arbitration -------------------------------------------------------

    def reclaim(self, catalog, need_bytes: int) -> int:
        """Free at least ``need_bytes`` of device memory for ``catalog``
        (best effort; returns bytes actually freed, possibly 0).

        Order: the requester's own lowest-priority unpinned buffers,
        then — for the shortfall — peers' unpinned buffers, youngest
        owner first, skipping owners OLDER than the requester
        (wound-wait: the older query wins; the younger parks in a
        bounded, cancellable grant wait for the older to release).
        Pinned buffers are never candidates at any step."""
        need = max(int(need_bytes), self._min_spill)
        st = None
        with self._cond:
            st = self._states.get(id(catalog))
        faults = getattr(catalog, "faults", None)
        if faults is not None:
            act = faults.check("memory.governor.oom_storm",
                               query_id=getattr(st, "query_id", "?"),
                               need=need)
            if act is not None:
                # storm mode: arbitration "cannot keep up" — report
                # nothing freed so the caller's split ladder absorbs
                # the pressure (deterministic livelock-shape chaos)
                get_registry().inc("governor_storm_denials")
                return 0
        reg = get_registry()
        reg.inc("governor_reclaims")
        # lowest priority first: idle shared-scan fragments in the
        # result cache are rebuildable — drop them before spilling the
        # requester's own working set, let alone wounding a peer
        freed = self._evict_cache(need, kind="fragment")
        if freed >= need:
            return freed
        own = catalog.spill_device(need - freed)
        freed += own
        reg.inc("governor_spill_bytes_own", own)
        if freed >= need or st is None:
            return freed
        freed += self._reclaim_from_peers(st, need - freed)
        if freed > 0:
            return freed
        # nothing anywhere the requester may touch: park for a grant
        # (older peers may be about to release), then report whatever
        # the wait yielded — 0 lets the caller split
        return self._wait_for_grant(catalog, st, need)

    def _reclaim_from_peers(self, st: _QueryState, shortfall: int) -> int:
        """Spill unpinned buffers from YOUNGER peers, youngest first.
        Peers older than the requester are off limits (wound-wait)."""
        reg = get_registry()
        with self._cond:
            peers = sorted((s for s in self._states.values()
                            if s is not st and s.seq > st.seq),
                           key=lambda s: -s.seq)
            victims = [(s, s.cat_ref()) for s in peers]
        freed = 0
        for vs, vcat in victims:
            if freed >= shortfall:
                break
            if vcat is None:
                continue
            try:
                got = vcat.spill_device(shortfall - freed)
            # enginelint: disable=RL001 (a victim's failure — terminal lifecycle or spill I/O — is the VICTIM's state; it must never kill the requester)
            except Exception:
                reg.inc("governor_victim_errors")
                continue
            if got:
                freed += got
                reg.inc("governor_spills_peer")
                reg.inc("governor_spill_bytes_peer", got)
        return freed

    def _wait_for_grant(self, catalog, st: _QueryState, need: int) -> int:
        """Park until peers release at least ``need`` bytes (observed as
        aggregate occupancy dropping enough to plausibly fit), the
        grant times out, or the query's lifecycle turns terminal.
        The reservation is visible in the ``governor.reserved_bytes``
        gauge and ALWAYS released on exit — success, timeout,
        cancellation, or deadline."""
        timeout = self._grant_timeout
        if timeout <= 0:
            return 0
        with self._cond:
            # only park when a wait can plausibly be granted:
            # * headroom already >= need: the OOM is outside the
            #   ledger's model (fragmentation, injected storm) and no
            #   peer release changes anything — split instead
            # * no LIVE peer registered: nobody exists to release the
            #   shortfall — a solo query waiting on itself is pure stall
            # * need unreachable: even every peer byte released leaves
            #   less than need under the requester's budget
            if self._headroom_locked(st) >= need:
                return 0
            if not any(s is not st and s.cat_ref() is not None
                       for s in self._states.values()):
                return 0
            cat = st.cat_ref()
            limit = cat.device_limit if cat is not None else self._budget
            if need > limit - st.device_bytes:
                return 0
        reg = get_registry()
        reg.inc("governor_grant_waits")
        lc = st.lifecycle
        faults = getattr(catalog, "faults", None)
        if faults is not None:
            act = faults.check("memory.grant.stall",
                               query_id=st.query_id, need=need)
            if act is not None:
                # injected stall: hold the waiter the full configured
                # seconds before the normal wait loop, cancellation
                # still honored (chaos proves mid-wait cancel unwinds)
                stall = act.param("seconds", 0.05)
                if lc is not None:
                    lc.wait(stall)
                else:
                    time.sleep(stall)
        deadline = time.monotonic() + timeout
        with self._cond:
            st.reserved_bytes = need
            try:
                while True:
                    if lc is not None:
                        lc.check()  # terminal -> raises, finally releases
                    if self._headroom_locked(st) >= need:
                        reg.inc("governor_grants")
                        return need
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        reg.inc("governor_grant_timeouts")
                        return 0
                    self._cond.wait(min(rem, 0.05))
            finally:
                st.reserved_bytes = 0
                self._cond.notify_all()

    def _headroom_locked(self, st: _QueryState) -> int:
        """Device bytes the requester could allocate right now: its
        catalog budget minus everything currently registered across
        queries (catalogs share one physical HBM)."""
        cat = st.cat_ref()
        limit = cat.device_limit if cat is not None else self._budget
        return limit - self._total_locked()

    def _total_locked(self) -> int:
        return sum(s.device_bytes for s in self._states.values())

    # -- admission pressure ------------------------------------------------

    def _update_pressure_locked(self) -> None:
        if self._budget <= 0:
            self._over_since = None
            return
        frac = self._total_locked() / self._budget
        now = time.monotonic()
        if frac >= self._shed_wm:
            if self._over_since is None:
                self._over_since = now
        else:
            self._over_since = None
        if frac >= self._high_wm:
            self._bg_wake.set()

    def admission_pressure(self, tenant: "str | None" = None
                           ) -> str | None:
        """AdmissionController pressure hook: a reason string when new
        admissions should be shed (aggregate occupancy has sat above
        shedWatermark for shedHoldSeconds), else None.  Reading is
        cheap — admission already takes a lock of its own.  Memory
        pressure is tenant-blind (``tenant`` is accepted for the hook
        signature; per-tenant targeting lives in the control plane's
        composed hook) — the controller's over-share gate decides who
        absorbs the shed."""
        with self._cond:
            self._update_pressure_locked()
            over = self._over_since
            if over is None or self._budget <= 0:
                return None
            held = time.monotonic() - over
            if held < self._shed_hold:
                return None
            frac = self._total_locked() / self._budget
        # lowest-priority occupant goes first: if dropping idle cached
        # scan fragments actually freed device bytes, this pressure
        # event is absorbed by the cache and no query is shed (result
        # blobs are host memory and cannot relieve HBM — they don't
        # spare a shed)
        if self._evict_cache(None, kind="fragment") > 0:
            return None
        get_registry().inc("governor_pressure_sheds")
        return (f"memory pressure: device occupancy {frac:.0%} above "
                f"shedWatermark={self._shed_wm:g} for {held:.1f}s "
                "(spark.rapids.memory.governor.*)")

    # -- background watermark spill ----------------------------------------

    def _ensure_bg_locked(self) -> None:
        if self._bg_thread is not None and self._bg_thread.is_alive():
            return
        self._bg_stop.clear()
        t = threading.Thread(target=self._bg_loop, daemon=True,
                             name="tpu-mem-governor")
        self._bg_thread = t
        t.start()

    def _stop_bg_locked(self) -> None:
        self._bg_stop.set()
        self._bg_wake.set()
        self._bg_thread = None

    def _bg_loop(self) -> None:
        """Proactive spill off the hot path: when aggregate occupancy
        crosses the high watermark, push idle (youngest-first) queries'
        unpinned buffers to host until the low watermark.  The loop
        parks on an event between checks and exits when the last
        catalog unregisters."""
        reg = get_registry()
        # enginelint: disable=RL004 (daemon loop; bounded by _bg_stop, set when the last catalog unregisters)
        while not self._bg_stop.is_set():
            self._bg_wake.wait(self._poll_s)
            self._bg_wake.clear()
            if self._bg_stop.is_set():
                return
            with self._cond:
                budget = self._budget
                total = self._total_locked()
                if budget <= 0 or total < self._high_wm * budget:
                    continue
                target = total - int(self._low_wm * budget)
                victims = [s.cat_ref() for s in
                           sorted(self._states.values(),
                                  key=lambda s: -s.seq)]
            moved = 0
            for vcat in victims:
                if moved >= target or vcat is None:
                    break
                try:
                    got = vcat.spill_device(target - moved)
                # enginelint: disable=RL001 (one victim's failure must not kill the watermark daemon; the per-query retry paths surface real errors)
                except Exception:
                    reg.inc("governor_victim_errors")
                    continue
                if got:
                    moved += got
            if moved:
                reg.inc("governor_background_spills")
                reg.inc("governor_spill_bytes_background", moved)

    # -- introspection -----------------------------------------------------

    def reserved_bytes(self) -> int:
        """Outstanding grant reservations (must be 0 when no query is
        mid-wait — the leak check of tests/test_governor_chaos.py)."""
        with self._cond:
            return sum(s.reserved_bytes for s in self._states.values())

    def query_stats(self, query_id: str | None = None) -> dict:
        """Per-query ledgers: {query_id: {device_bytes, pinned_bytes,
        peak_bytes, reserved_bytes, seq}} (one entry when filtered)."""
        with self._cond:
            out = {}
            for s in self._states.values():
                if query_id is not None and s.query_id != query_id:
                    continue
                out[s.query_id] = {
                    "device_bytes": s.device_bytes,
                    "pinned_bytes": s.pinned_bytes,
                    "peak_bytes": s.peak_bytes,
                    "reserved_bytes": s.reserved_bytes,
                    "seq": s.seq,
                }
            return out

    def occupancy_sample(self) -> dict:
        """One compact occupancy snapshot in a SINGLE lock acquisition:
        total device bytes, per-query ledger bytes, and the effective
        watermark position.  The cost-attribution plane's HBM sampler
        (obs/profile.py) polls this at tens of Hz, so it must not take
        the condition lock four separate times the way composing
        ``query_stats``+``watermarks``+``reserved_bytes`` would."""
        with self._cond:
            return {
                "device_bytes_total": self._total_locked(),
                "reserved_bytes": sum(s.reserved_bytes
                                      for s in self._states.values()),
                "budget_bytes": self._budget,
                "per_query": {s.query_id: s.device_bytes
                              for s in self._states.values()},
                "watermarks": {"high": self._high_wm, "low": self._low_wm,
                               "shed": self._shed_wm,
                               "overridden":
                                   self._wm_override is not None},
            }

    def _source(self) -> dict:
        """MetricsRegistry pull source: aggregate + per-query gauges
        (bounded — entries exist only while their query runs)."""
        with self._cond:
            vals = {
                "device_bytes_total": self._total_locked(),
                "reserved_bytes": sum(s.reserved_bytes
                                      for s in self._states.values()),
                "queries_registered": len(self._states),
                "budget_bytes": self._budget,
            }
            for s in self._states.values():
                q = s.query_id
                vals[f"q.{q}.device_bytes"] = s.device_bytes
                vals[f"q.{q}.pinned_bytes"] = s.pinned_bytes
                vals[f"q.{q}.peak_bytes"] = s.peak_bytes
            return vals


_GOVERNOR: MemoryGovernor | None = None
_GOV_LOCK = threading.Lock()


def get_governor() -> MemoryGovernor:
    """The process-wide governor singleton (created on first use)."""
    global _GOVERNOR
    with _GOV_LOCK:
        if _GOVERNOR is None:
            _GOVERNOR = MemoryGovernor()
        return _GOVERNOR


def maybe_register(catalog, query_id: str, lifecycle, conf) -> None:
    """Register ``catalog`` with the governor when the conf enables it;
    a strict no-op otherwise (the catalog keeps ``governor=None`` and
    every retry path stays on the legacy quarter-budget sweep)."""
    settings = getattr(conf, "settings", None) or {}
    if not GOVERNOR_ENABLED.get(settings):
        return
    get_governor().register(catalog, query_id, lifecycle, settings)
