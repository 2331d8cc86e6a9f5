"""Cluster worker process: ``python -m spark_rapids_tpu.cluster.worker``.

One worker = one long-lived process hosting

- a persistent :class:`LocalShuffleTransport` (``ctx=None`` so map
  outputs live as serialized bytes, never entangled with any query's
  spill catalog) — the worker-local shard of the DCN shuffle plane,
- the existing :class:`TcpShuffleServer` serving those outputs to the
  driver and to peer workers (shuffle/tcp.py — the same data plane,
  codec + checksum negotiation included, that single-process remote
  reads use),
- an :class:`RpcServer` control plane (cluster/rpc.py) accepting plan
  fragments from the driver.

Protocol with the driver (cluster/driver.py): the driver writes one
JSON config line on stdin ``{worker_id, driver: [host, port], conf}``;
the worker binds its servers and prints one READY line on stdout, then
heartbeats liveness + a metrics-registry snapshot to the driver until
told to shut down.  The reference splits these roles the same way:
Spark executors host RapidsShuffleServer for their locally-cached map
output and answer the driver's scheduler over the RPC env.

A ``run_fragment`` call carries a pickled clone of one
ShuffleExchangeExec whose child subtree reads upstream cluster
shuffles through WorkerShuffleReaderExec leaves (cluster/exec.py).
The worker executes the assigned child partitions and writes the
partitioned pieces into its local store under composite map ids
``cpid * MAP_ID_STRIDE + batch_index`` — integers, because
MapOutputLostError round-trips map ids through JSON as ints — then
returns per-slot registrations for the driver's map-output tracker.

The conf shipped to workers is scrubbed of ``cluster.mode`` (a worker
must never recursively spawn a cluster) and ``test.faults`` (fault
injection is driven from the driver so a plan fires exactly once per
cluster, not once per process).
"""
from __future__ import annotations

import json
import os
import pickle
import sys
import threading

#: composite map-id encoding: map_id = cpid * stride + map batch index.
#: One child partition producing >= a million batches would collide;
#: batch coalescing keeps real counts orders of magnitude below this.
MAP_ID_STRIDE = 1_000_000

#: stdout marker the driver scans for; everything else on the worker's
#: stdout/stderr is passthrough logging
READY_PREFIX = "CLUSTER_WORKER_READY "

_SCRUBBED_KEYS = ("spark.rapids.cluster.mode", "spark.rapids.test.faults",
                  # workers ship spans back over RPC instead of exporting
                  # their own files — the driver's single export IS the
                  # cluster trace (obs/trace.py stamp_for_shipping)
                  "spark.rapids.obs.trace.dir")

#: per-RPC-message span shipping bound (newest win): heartbeats and
#: fragment replies stay small even under span storms
_MAX_SHIP_EVENTS = 2000


def scrub_worker_conf(settings: dict) -> dict:
    out = dict(settings)
    for k in _SCRUBBED_KEYS:
        out.pop(k, None)
    return out


class WorkerRuntime:
    """Everything one worker process owns; also constructible in-process
    for tests (fragment execution without paying subprocess startup)."""

    def __init__(self, worker_id: str, driver_addr=None,
                 settings: dict | None = None):
        from spark_rapids_tpu.cluster import (HEARTBEAT_INTERVAL,
                                              RPC_COMPRESSION_CODEC,
                                              RPC_TIMEOUT)
        from spark_rapids_tpu.cluster.rpc import RpcServer
        from spark_rapids_tpu.conf import TpuConf
        from spark_rapids_tpu.shuffle.local import LocalShuffleTransport
        from spark_rapids_tpu.shuffle.tcp import TcpShuffleServer
        self.worker_id = worker_id
        self.driver = tuple(driver_addr) if driver_addr else None
        self.conf = TpuConf(scrub_worker_conf(settings or {}))
        self._hb_interval = HEARTBEAT_INTERVAL.get(self.conf.settings)
        self.store = LocalShuffleTransport(self.conf, ctx=None)
        self.shuffle_server = TcpShuffleServer(self.store)
        self._stop = threading.Event()
        self._runtime_ready = False
        self._runtime_lock = threading.Lock()
        # graceful drain: once draining, new fragments are rejected with
        # a structured reply (the driver re-pools them on survivors) and
        # the driver polls _active down to zero before migrating slots
        self._draining = False
        self._active_lock = threading.Lock()
        self._active_fragments = 0
        # driver-loss linger: after stdin EOF (driver gone) the worker
        # can keep its RPC + shuffle servers alive for a grace window so
        # a recovered driver re-attaches; dispatch is paused meanwhile
        self._linger_lock = threading.Lock()
        self._lingering = False
        self._linger_timer: threading.Timer | None = None
        self._reattach_epoch = 0
        self.metrics = {"fragments_run": 0, "fragment_failures": 0,
                        "map_batches_written": 0,
                        "fragments_rejected_draining": 0,
                        "map_outputs_imported": 0,
                        "write_fragments_run": 0,
                        "write_tasks_staged": 0,
                        "write_fragment_failures": 0,
                        "linger_entered": 0, "linger_expired": 0,
                        "driver_reattached": 0, "shuffles_aliased": 0}
        # tracers of fragments currently executing: the heartbeat drains
        # them mid-run so a long map stage streams spans to the driver
        # instead of batching them all on completion
        self._tracer_lock = threading.Lock()
        self._live_tracers: list = []
        # heartbeat snapshots carry the process registry; folding this
        # runtime in gives the driver per-worker fragment counters
        from spark_rapids_tpu.obs.registry import get_registry
        get_registry().register_object_source("cluster.worker", self)
        self.rpc = RpcServer(
            {"ping": self._h_ping,
             "run_fragment": self._h_run_fragment,
             "run_write_fragment": self._h_run_write_fragment,
             "release_shuffle": self._h_release_shuffle,
             "drain": self._h_drain,
             "migrate_slots": self._h_migrate_slots,
             "reconnect": self._h_reconnect,
             "alias_shuffle": self._h_alias_shuffle,
             "shutdown": self._h_shutdown},
            timeout=RPC_TIMEOUT.get(self.conf.settings),
            codec_name=RPC_COMPRESSION_CODEC.get(self.conf.settings))
        self._hb_thread: threading.Thread | None = None

    # -- handlers -------------------------------------------------------
    def _h_ping(self, payload: dict, blob: bytes):
        return ({"worker_id": self.worker_id, "pid": os.getpid()}, b"")

    def _h_release_shuffle(self, payload: dict, blob: bytes):
        freed = self.store.release_shuffle(payload["shuffle_id"])
        return ({"freed": freed}, b"")

    def _h_shutdown(self, payload: dict, blob: bytes):
        self._stop.set()
        return ({"ok": True}, b"")

    def _h_drain(self, payload: dict, blob: bytes):
        """Enter (or poll) draining: stop accepting fragments and report
        how many are still executing.  Idempotent — the driver calls it
        repeatedly until ``active`` reaches zero."""
        self._draining = True
        with self._active_lock:
            active = self._active_fragments
        return ({"ok": True, "draining": True, "active": active}, b"")

    def _h_migrate_slots(self, payload: dict, blob: bytes):
        """Adopt a retiring peer's map-output slots: pull each run's
        serialized frames over the shuffle plane and import them into
        the local store under the driver-bumped epochs, then return the
        same per-slot registration records a fragment reply carries so
        the driver's tracker re-points atomically."""
        from spark_rapids_tpu.shuffle.errors import ShuffleFetchError
        from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
        sid = payload["shuffle_id"]
        source = tuple(payload["source"])
        imported: set[int] = set()
        pids: set[int] = set()
        try:
            for run in payload["runs"]:
                pid = int(run["pid"])
                mids = [int(m) for m in run["map_ids"]]
                rows = [int(r) for r in run["rows"]]
                epochs = [int(e) for e in run["epochs"]]
                frames = list(fetch_remote_with_retry(
                    source, sid, pid, lo=int(run["lo"]),
                    hi=int(run["hi"]), device=False, conf=self.conf,
                    raw=True))
                if len(frames) != len(mids):
                    return ({"error_kind": "migrate_fetch",
                             "error": f"migration run for shuffle {sid} "
                                      f"part {pid} returned "
                                      f"{len(frames)} frames, expected "
                                      f"{len(mids)}"}, b"")
                for mid, r, ep, raw in zip(mids, rows, epochs, frames):
                    self.store.import_serialized(sid, mid, pid, raw,
                                                 rows=r, epoch=ep)
                    imported.add(mid)
                    pids.add(pid)
                    self.metrics["map_outputs_imported"] += 1
        except ShuffleFetchError as e:
            return ({"error_kind": "migrate_fetch", "error": str(e)}, b"")
        entries = []
        for pid in sorted(pids):
            for wslot, (mid, size, rows, ep) in enumerate(
                    self.store.slots_for(sid, pid)):
                if mid in imported:
                    entries.append([mid, pid, wslot, size, rows, ep])
        return ({"ok": True, "entries": entries,
                 "shuffle": list(self.shuffle_server.address),
                 "imported": len(imported)}, b"")

    # -- driver-loss linger / re-attach ---------------------------------
    def begin_linger(self, grace: float) -> None:
        """Driver gone (stdin EOF): pause dispatch but keep the RPC and
        shuffle servers up for ``grace`` seconds so a recovered driver
        can RECONNECT and resume against the surviving map outputs.
        Past the grace the worker self-terminates — the linger window,
        not process lifetime, bounds orphan risk."""
        with self._linger_lock:
            if self._lingering or self._stop.is_set():
                return
            self._lingering = True
            self.metrics["linger_entered"] += 1
            self._linger_timer = threading.Timer(grace, self._linger_expired)
            self._linger_timer.daemon = True
            self._linger_timer.start()

    def _linger_expired(self) -> None:
        with self._linger_lock:
            if not self._lingering:
                return  # a reconnect raced the timer and won
            self.metrics["linger_expired"] += 1
        self._stop.set()

    def _h_reconnect(self, payload: dict, blob: bytes):
        """RECONNECT handshake from a recovered driver: cancel the
        linger deadline, re-route heartbeats to the new driver address,
        adopt its journal epoch, and reply with a full inventory of the
        map-output slots this worker still holds so the driver can
        reconcile them against the journaled tracker."""
        with self._linger_lock:
            if self._linger_timer is not None:
                self._linger_timer.cancel()
                self._linger_timer = None
            self._lingering = False
            self.driver = tuple(payload["driver"])
            self._reattach_epoch = int(payload.get("epoch", 0))
            self.metrics["driver_reattached"] += 1
        return ({"worker_id": self.worker_id, "pid": os.getpid(),
                 "rpc": list(self.rpc.address),
                 "shuffle": list(self.shuffle_server.address),
                 "epoch": self._reattach_epoch,
                 "inventory": self.store.shuffle_inventory()}, b"")

    def _h_alias_shuffle(self, payload: dict, blob: bytes):
        """Re-key a held shuffle's slots under a new shuffle id: a
        recovered driver's replanned query carries a fresh (per-process)
        shuffle id for the same exchange, and claiming the journaled
        outputs means renaming them in every holder's store."""
        moved = self.store.alias_shuffle(payload["old"], payload["new"])
        self.metrics["shuffles_aliased"] += 1
        return ({"ok": True, "moved": moved}, b"")

    def _ensure_runtime(self) -> None:
        # first fragment pays JAX/runtime init, keeping READY fast
        with self._runtime_lock:
            if not self._runtime_ready:
                from spark_rapids_tpu.runtime import ensure_runtime
                ensure_runtime(self.conf)
                self._runtime_ready = True

    def _h_run_fragment(self, payload: dict, blob: bytes):
        """Execute one map-side fragment: drain the assigned child
        partitions of the shipped exchange clone and write the
        partitioned pieces into the local store.  Structured failure
        payloads (never error frames) let the driver distinguish a
        peer's data loss — which routes into lineage recovery — from
        this worker's own fault.  A draining worker rejects the call
        structurally so the driver re-pools the partitions on survivors
        without treating the rejection as data loss."""
        if self._draining or self._lingering:
            # a lingering worker rejects exactly like a draining one:
            # its map outputs stay servable but no new work lands until
            # a driver completes the RECONNECT handshake
            self.metrics["fragments_rejected_draining"] += 1
            return ({"error_kind": "draining",
                     "error": f"worker {self.worker_id} is "
                              f"{'lingering' if self._lingering else 'draining'}"},
                    b"")
        with self._active_lock:
            self._active_fragments += 1
        try:
            return self._run_fragment(payload, blob)
        finally:
            with self._active_lock:
                self._active_fragments -= 1

    def _run_fragment(self, payload: dict, blob: bytes):
        from spark_rapids_tpu.cluster.exec import WorkerFetchFailed
        from spark_rapids_tpu.conf import TpuConf
        from spark_rapids_tpu.exec.core import ExecCtx
        from spark_rapids_tpu.shuffle.errors import MapOutputLostError
        self._ensure_runtime()
        spec = pickle.loads(blob)
        exchange = spec["exchange"]
        n = int(spec["num_parts"])
        cpids = [int(c) for c in spec["cpids"]]
        epochs = {int(k): int(v)
                  for k, v in (spec.get("epochs") or {}).items()}
        sid = exchange.shuffle_id
        conf = TpuConf(scrub_worker_conf(spec.get("conf") or
                                         self.conf.settings))
        child = exchange.children[0]
        self.metrics["fragments_run"] += 1
        hdr = spec.get("trace") or None
        tracer = None
        try:
            with ExecCtx(backend="device", conf=conf) as ctx:
                if hdr:
                    # the driver's query/trace ids win: every span this
                    # fragment records lands under the ORIGINATING query
                    ctx.cache["query_id"] = hdr["query_id"]
                tracer = ctx.tracer
                if tracer is not None:
                    if hdr and hdr.get("trace_id"):
                        tracer.trace_id = hdr["trace_id"]
                    with self._tracer_lock:
                        self._live_tracers.append(tracer)
                with ctx.trace_span("worker.fragment", "cluster",
                                    worker_id=self.worker_id,
                                    shuffle_id=sid, cpids=list(cpids)):
                    for cpid in cpids:
                        for k, b in enumerate(
                                child.partition_iter(ctx, cpid)):
                            enc = cpid * MAP_ID_STRIDE + k
                            exchange._write_map_batch(
                                ctx, self.store, enc, b, False, n,
                                epoch=epochs.get(enc))
                            self.metrics["map_batches_written"] += 1
        except WorkerFetchFailed as e:
            self.metrics["fragment_failures"] += 1
            return ({"error": str(e), "error_kind": "peer_fetch",
                     "peer": list(e.address),
                     "lost_sid": e.shuffle_id,
                     **self._spans_field(tracer)}, b"")
        except MapOutputLostError as e:
            self.metrics["fragment_failures"] += 1
            return ({"error": str(e), "error_kind": "map_lost",
                     "lost_sid": e.shuffle_id, "part": e.part_id,
                     "lost": {str(k): v for k, v in e.lost.items()},
                     "observed_empty": e.observed_empty,
                     **self._spans_field(tracer)}, b"")
        finally:
            if tracer is not None:
                with self._tracer_lock:
                    try:
                        self._live_tracers.remove(tracer)
                    except ValueError:
                        pass
        wanted = set(cpids)
        entries = []
        for pid in range(n):
            for wslot, (mid, size, rows, ep) in enumerate(
                    self.store.slots_for(sid, pid)):
                if mid // MAP_ID_STRIDE in wanted:
                    entries.append([mid, pid, wslot, size, rows, ep])
        return ({"ok": True, "entries": entries,
                 "shuffle": list(self.shuffle_server.address),
                 "attempt": spec.get("attempt", 0),
                 **self._spans_field(tracer)}, b"")

    def _h_run_write_fragment(self, payload: dict, blob: bytes):
        """Execute one WRITE fragment: run the shipped plan subtree's
        assigned child partitions and stage each task's files into its
        private attempt directory under the job's ``_staging`` tree,
        replying with one manifest per task for the driver's commit
        coordinator to arbitrate.  Nothing here touches the final
        directory — a worker death mid-write leaves only staging
        garbage.  Draining workers reject structurally, like
        ``run_fragment``."""
        if self._draining or self._lingering:
            self.metrics["fragments_rejected_draining"] += 1
            return ({"error_kind": "draining",
                     "error": f"worker {self.worker_id} is "
                              f"{'lingering' if self._lingering else 'draining'}"},
                    b"")
        with self._active_lock:
            self._active_fragments += 1
        try:
            return self._run_write_fragment(payload, blob)
        finally:
            with self._active_lock:
                self._active_fragments -= 1

    def _run_write_fragment(self, payload: dict, blob: bytes):
        from spark_rapids_tpu.cluster.exec import WorkerFetchFailed
        from spark_rapids_tpu.conf import TpuConf
        from spark_rapids_tpu.exec.core import ExecCtx
        from spark_rapids_tpu.io.writer import (staging_attempt_dir,
                                                write_task_attempt)
        from spark_rapids_tpu.shuffle.errors import MapOutputLostError
        self._ensure_runtime()
        spec = pickle.loads(blob)
        plan = spec["plan"]
        w = spec["write"]
        cpids = [int(c) for c in spec["cpids"]]
        attempts = {int(k): int(v) for k, v in spec["attempts"].items()}
        conf = TpuConf(scrub_worker_conf(spec.get("conf") or
                                         self.conf.settings))
        self.metrics["write_fragments_run"] += 1
        hdr = spec.get("trace") or None
        tracer = None
        manifests: list[dict] = []
        try:
            with ExecCtx(backend="device", conf=conf) as ctx:
                if hdr:
                    ctx.cache["query_id"] = hdr["query_id"]
                tracer = ctx.tracer
                if tracer is not None:
                    if hdr and hdr.get("trace_id"):
                        tracer.trace_id = hdr["trace_id"]
                    with self._tracer_lock:
                        self._live_tracers.append(tracer)
                with ctx.trace_span("worker.write_fragment", "cluster",
                                    worker_id=self.worker_id,
                                    job=w["job_id"], cpids=list(cpids)):
                    for cpid in cpids:
                        attempt = attempts[cpid]
                        adir = staging_attempt_dir(
                            w["path"], w["job_id"], cpid, attempt)
                        # faults=None: fault plans are driver-side only
                        # (scrub_worker_conf strips them from the spec)
                        manifests.append(write_task_attempt(
                            plan, ctx, cpid, adir, w["fmt"],
                            w["partition_by"], w["options"],
                            job_id=w["job_id"], attempt=attempt,
                            worker=self.worker_id))
                        self.metrics["write_tasks_staged"] += 1
        except WorkerFetchFailed as e:
            self.metrics["write_fragment_failures"] += 1
            return ({"error": str(e), "error_kind": "peer_fetch",
                     "peer": list(e.address),
                     "lost_sid": e.shuffle_id,
                     **self._spans_field(tracer)}, b"")
        except MapOutputLostError as e:
            self.metrics["write_fragment_failures"] += 1
            return ({"error": str(e), "error_kind": "map_lost",
                     "lost_sid": e.shuffle_id, "part": e.part_id,
                     "lost": {str(k): v for k, v in e.lost.items()},
                     "observed_empty": e.observed_empty,
                     **self._spans_field(tracer)}, b"")
        except OSError as e:
            # the staging write itself failed (disk, quota): nothing
            # visible happened; the driver re-pools under a new attempt
            self.metrics["write_fragment_failures"] += 1
            return ({"error": str(e), "error_kind": "write_failed",
                     **self._spans_field(tracer)}, b"")
        finally:
            if tracer is not None:
                with self._tracer_lock:
                    try:
                        self._live_tracers.remove(tracer)
                    except ValueError:
                        pass
        return ({"ok": True, "manifests": manifests,
                 **self._spans_field(tracer)}, b"")

    def _spans_field(self, tracer) -> dict:
        """Drain one fragment tracer into a reply-payload field (empty
        dict when tracing is off — the obs package is untouched)."""
        if tracer is None:
            return {}
        from spark_rapids_tpu.obs.trace import stamp_for_shipping
        evs = stamp_for_shipping(tracer.drain_events(),
                                 tracer._wall_origin, os.getpid())
        if not evs:
            return {}
        return {"spans": {"pid": os.getpid(),
                          "events": evs[-_MAX_SHIP_EVENTS:]}}

    def _drain_live_spans(self) -> "dict | None":
        """Heartbeat payload: whatever the in-flight fragments have
        buffered since the last beat (exactly-once shipping — drain
        pops)."""
        with self._tracer_lock:
            tracers = list(self._live_tracers)
        if not tracers:
            return None
        from spark_rapids_tpu.obs.trace import stamp_for_shipping
        evs: list = []
        for t in tracers:
            evs.extend(stamp_for_shipping(t.drain_events(),
                                          t._wall_origin, os.getpid()))
        if not evs:
            return None
        return {"pid": os.getpid(), "events": evs[-_MAX_SHIP_EVENTS:]}

    # -- liveness -------------------------------------------------------
    def start_heartbeat(self) -> None:
        if self.driver is None:
            return
        self._hb_thread = threading.Thread(target=self._hb_loop,
                                           daemon=True,
                                           name="tpu-cluster-heartbeat")
        self._hb_thread.start()

    def _hb_loop(self) -> None:
        from spark_rapids_tpu.cluster import REATTACH_GRACE
        from spark_rapids_tpu.cluster.rpc import rpc_call
        from spark_rapids_tpu.obs.registry import get_registry
        # a RE-ATTACHED worker has no stdin pipe to the new driver, so a
        # second driver loss is detected by heartbeat silence instead:
        # grace seconds of consecutive failed beats re-enter linger
        grace = REATTACH_GRACE.get(self.conf.settings)
        misses = 0
        while not self._stop.wait(self._hb_interval):
            try:
                payload = {"worker_id": self.worker_id,
                           "pid": os.getpid(),
                           "metrics": get_registry().snapshot()}
                spans = self._drain_live_spans()
                if spans is not None:
                    payload["spans"] = spans
                # cost-attribution shipping: only when the profiler /
                # meter are already live in THIS process (sys.modules
                # gate — a disabled worker never imports them here)
                import sys as _sys
                if "spark_rapids_tpu.obs.metering" in _sys.modules:
                    from spark_rapids_tpu.obs.metering import get_meter
                    delta = get_meter().drain_delta()
                    if delta is not None:
                        payload["metering"] = delta
                if "spark_rapids_tpu.obs.profile" in _sys.modules:
                    from spark_rapids_tpu.obs.profile import \
                        drain_hbm_for_shipping
                    hbm = drain_hbm_for_shipping()
                    if hbm:
                        payload["profile_hbm"] = hbm
                rpc_call(self.driver, "heartbeat", payload,
                         conf=self.conf, retries=0, timeout=5.0)
                misses = 0
            except (ConnectionError, OSError):
                # driver unreachable: keep trying — the driver's timeout
                # is the authority on whether this worker is dead
                misses += 1
                if (grace > 0 and self._reattach_epoch > 0
                        and not self._lingering
                        and misses * self._hb_interval >= grace):
                    self.begin_linger(grace)

    def wait(self) -> None:
        self._stop.wait()

    def close(self) -> None:
        self._stop.set()
        self.rpc.close()
        self.shuffle_server.close()
        self.store.close()


def main() -> int:
    line = sys.stdin.readline()
    if not line:
        print("cluster worker: no config line on stdin", file=sys.stderr)
        return 2
    cfg = json.loads(line)
    rt = WorkerRuntime(cfg["worker_id"], cfg.get("driver"),
                       cfg.get("conf") or {})
    print(READY_PREFIX + json.dumps(
        {"worker_id": rt.worker_id, "pid": os.getpid(),
         "rpc": list(rt.rpc.address),
         "shuffle": list(rt.shuffle_server.address)}), flush=True)
    rt.start_heartbeat()
    # orphan reaper: the driver holds our stdin pipe open for its whole
    # life, so EOF here means the driver process is GONE (even SIGKILL,
    # which skips its shutdown RPCs).  With reattachGraceSeconds > 0 the
    # worker LINGERS instead of exiting — dispatch paused, shuffle
    # outputs servable — so a recovered driver can RECONNECT; past the
    # grace it self-terminates.  Grace 0 (default) is the pre-journal
    # behavior: exit immediately, never orphan.
    from spark_rapids_tpu.cluster import REATTACH_GRACE
    grace = REATTACH_GRACE.get(rt.conf.settings)

    def _watch_stdin() -> None:
        while sys.stdin.readline():
            pass
        if grace > 0:
            rt.begin_linger(grace)
        else:
            rt._stop.set()
    threading.Thread(target=_watch_stdin, daemon=True,
                     name="tpu-cluster-stdin").start()
    rt.wait()
    rt.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
