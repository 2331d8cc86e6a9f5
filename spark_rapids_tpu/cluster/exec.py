"""Driver-side cluster shuffle execution: fragment cloning, scheduling,
and the distributed map-output tracker.

``cluster_do_shuffle`` intercepts a cluster-tagged
ShuffleExchangeExec's device materialization (the hook sits at the top
of ``_do_shuffle_device``): instead of draining the child in-process,
it clones the exchange's subtree into a self-contained, picklable
FRAGMENT — upstream cluster shuffles become
:class:`WorkerShuffleReaderExec` leaves that stream peers' map output
over the DCN shuffle plane, broadcasts become pre-materialized
:class:`StaticBroadcastExec` payloads — and ships one fragment per
worker over the control plane (cluster/rpc.py).  Workers execute their
assigned child partitions and register the resulting map-output slots
back into a :class:`ClusterMapOutputTracker`, the driver's duck-typed
ShuffleTransport for that shuffle (reference: MapStatus registration
into MapOutputTracker; the tracker doubles as the reduce-side fetch
client the way RapidsCachingReader does).

Fault tolerance composes with the existing lineage machinery
(exec/recovery.py) rather than duplicating it: a dead worker surfaces
as a terminal fetch failure -> the tracker names every map output that
died with it in one MapOutputLostError -> ``_recover`` invalidates and
calls :class:`ClusterLineage`.recompute, which REASSIGNS the lost
child partitions to surviving workers and registers the fresh slots.
Anything the cluster path cannot express (non-deterministic
partitionings, unpicklable operators, upstream shuffles that fell back
in-process) falls back to the classic in-process materialization —
same rows, one process.
"""
from __future__ import annotations

import copy
import hashlib
import itertools
import pickle
import re
import sys
import threading
import time
from dataclasses import dataclass
from typing import Any, Iterator

from spark_rapids_tpu import types as T
from spark_rapids_tpu.cluster import (SPECULATION_ENABLED,
                                      SPECULATION_MIN_RUNTIME,
                                      SPECULATION_MULTIPLIER)
from spark_rapids_tpu.cluster.worker import MAP_ID_STRIDE, scrub_worker_conf
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.faults import crash_point
from spark_rapids_tpu.obs.registry import get_registry
from spark_rapids_tpu.shuffle.errors import (MapOutputLostError,
                                             ShuffleFetchError)

__all__ = ["WorkerShuffleReaderExec", "StaticBroadcastExec",
           "ClusterMapOutputTracker", "ClusterLineage",
           "cluster_do_shuffle", "WorkerFetchFailed", "ClusterExecError"]

#: node __dict__ keys holding lazily-built jit wrappers; they close over
#: runtime state and would poison fragment pickling — the worker's first
#: execution rebuilds them from the same compile-cache keys
_JIT_ATTR = re.compile(r"jit")


class ClusterExecError(RuntimeError):
    """Cluster scheduling failed in a way recovery cannot absorb (e.g.
    every worker died)."""


class WorkerFetchFailed(Exception):
    """A fragment's read from a peer worker's shuffle server failed
    terminally: the worker reports the peer to the driver, which marks
    it dead and routes the upstream shuffle into lineage recovery."""

    def __init__(self, address, shuffle_id, detail: str = ""):
        self.address = tuple(address)
        self.shuffle_id = shuffle_id
        super().__init__(
            f"fetch from worker {self.address[0]}:{self.address[1]} for "
            f"shuffle {shuffle_id} failed terminally"
            + (f": {detail}" if detail else ""))


class WorkerShuffleReaderExec(PlanNode):
    """Leaf that streams an upstream cluster shuffle's reduce
    partitions from the workers that hold them, through the retrying
    fetch (shuffle/retry.py), with a slot-ranged run list per output
    partition.

    ``groups[pid]`` is a list of ``(address, fetch_pid, lo, hi)`` runs:
    fetch slots [lo, hi) of the peer's reduce partition ``fetch_pid``.
    AQE coalesce/skew-split groups computed driver-side flatten into
    the same run shape.  ``_src`` records ``(shuffle_id, groups_spec)``
    so the driver can rebuild the runs from the live tracker after a
    recovery relocated slots (cluster/exec.py _refresh_readers)."""

    def __init__(self, shuffle_id, schema: T.Schema, groups,
                 src=None):
        super().__init__([])
        self.shuffle_id = shuffle_id
        self._schema = schema
        self.groups = [list(g) for g in groups]
        self._src = src

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return len(self.groups)

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
        for address, fpid, lo, hi in self.groups[pid]:
            try:
                yield from fetch_remote_with_retry(
                    tuple(address), self.shuffle_id, fpid, lo=lo, hi=hi,
                    device=ctx.is_device, conf=ctx.conf,
                    lifecycle=ctx.lifecycle)
            except MapOutputLostError:
                raise
            except ShuffleFetchError as e:
                raise WorkerFetchFailed(address, self.shuffle_id,
                                        str(e)) from e

    def node_desc(self) -> str:
        return (f"WorkerShuffleReaderExec[shuffle="
                f"{str(self.shuffle_id)[:12]}, groups={len(self.groups)}]")


class StaticBroadcastExec(PlanNode):
    """Broadcast side pre-materialized ON THE DRIVER and shipped to
    workers as one serialized batch — the fragment-side analog of the
    reference's torrent-broadcast build side (GpuBroadcastExchangeExec
    collects on the driver and executors rebuild the device table from
    the broadcast blob)."""

    def __init__(self, data: bytes, schema: T.Schema):
        super().__init__([])
        self._data = data
        self._schema = schema

    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return 1

    def materialize(self, ctx: ExecCtx):
        from spark_rapids_tpu.shuffle.serializer import deserialize_batch
        return ctx.cached(("static_broadcast", id(self), ctx.backend),
                          lambda: deserialize_batch(
                              self._data, device=ctx.is_device))

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        yield self.materialize(ctx)

    def node_desc(self) -> str:
        return f"StaticBroadcastExec[{len(self._data)}B]"


class _Entry:
    """One registered map-output slot: where one (map batch, reduce
    partition) piece lives in the cluster."""

    __slots__ = ("map_id", "worker_id", "wslot", "size", "rows",
                 "epoch", "lost")

    def __init__(self, map_id: int, worker_id: str, wslot: int,
                 size: int, rows: int, epoch: int):
        self.map_id = map_id
        self.worker_id = worker_id
        self.wslot = wslot
        self.size = size
        self.rows = rows
        self.epoch = epoch
        self.lost = False


class ClusterMapOutputTracker:
    """Driver-side map-output directory + reduce-fetch client for ONE
    cluster shuffle; duck-types the ShuffleTransport SPI so the
    recovery loop (recovering_fetch/_recover), the AQE reader's
    statistics reads, and ExecCtx.close all work on it unchanged.

    Entries per reduce partition are kept sorted by composite map id
    ``cpid * MAP_ID_STRIDE + k`` — the same (child partition, batch)
    lexicographic order the single-process path's flat map indices
    produce — so the merged fetch stream is batch-for-batch identical
    to one process (the exactness argument behind
    tests/test_cluster.py's equality with the single-process rows)."""

    def __init__(self, cluster, ctx: ExecCtx, shuffle_id, num_parts: int):
        from spark_rapids_tpu.faults import FaultRegistry
        self.cluster = cluster
        self.ctx = ctx
        self.shuffle_id = shuffle_id
        self.num_parts = num_parts
        self._lock = threading.Lock()
        self._entries: list[list[_Entry]] = [[] for _ in range(num_parts)]
        self._epochs: dict[int, int] = {}
        # worker_id -> shuffle-plane address (recorded at registration)
        self._shuffle_addr: dict[str, tuple] = {}
        self._faults = ctx.cached(("fault_registry",),
                                  lambda: FaultRegistry.from_conf(ctx.conf))
        self._closed = False
        # write-ahead cluster journal (cluster/journal.py) when the
        # driver has one: registrations, invalidations, and the close
        # are journaled so a restarted driver can resume this shuffle
        self._journal = None
        # the driver weakly tracks live trackers so a graceful drain
        # can migrate a retiring worker's slots (elastic membership)
        reg_tracker = getattr(cluster, "register_tracker", None)
        if callable(reg_tracker):
            reg_tracker(self)

    # -- registration (dispatch rounds) ---------------------------------
    def register(self, worker_id: str, shuffle_addr, entries) -> None:
        """Fold one fragment reply's slot list in: a (pid, map_id) pair
        already present (a recovery recompute) is replaced in place so
        slot ORDER survives relocation; new pairs append and the
        partition re-sorts by map id.

        Commit is FIRST-WRITER-WINS per epoch: a slot already live at
        this epoch is never replaced, so a speculative duplicate (or a
        drain straggler) re-offering the same map output is discarded —
        the exactly-once guarantee behind speculation and migration."""
        with self._lock:
            self._shuffle_addr[worker_id] = tuple(shuffle_addr)
            dirty = set()
            for mid, pid, wslot, size, rows, epoch in entries:
                mid, pid = int(mid), int(pid)
                cur = self._epochs.get(mid, 0)
                if epoch < cur:
                    continue  # straggler from a pre-recovery attempt
                row = self._entries[pid]
                old = next((e for e in row if e.map_id == mid), None)
                if old is not None and not old.lost \
                        and int(epoch) <= old.epoch:
                    get_registry().inc(
                        "cluster.stale_registrations_discarded")
                    continue  # first writer already committed
                self._epochs[mid] = int(epoch)
                if old is not None:
                    old.worker_id = worker_id
                    old.wslot = int(wslot)
                    old.size = int(size)
                    old.rows = int(rows)
                    old.epoch = int(epoch)
                    old.lost = False
                else:
                    row.append(_Entry(mid, worker_id, int(wslot),
                                      int(size), int(rows), int(epoch)))
                    dirty.add(pid)
            for pid in dirty:
                self._entries[pid].sort(key=lambda e: e.map_id)
        if self._journal is not None and entries:
            self._journal.append(
                "map_register", sid=str(self.shuffle_id), wid=worker_id,
                shuffle=list(shuffle_addr),
                entries=[[int(m), int(p), int(w), int(s), int(r), int(e)]
                         for m, p, w, s, r, e in entries])

    def entries_owned_by(self, worker_id: str) -> dict[int, int]:
        """Live map ids (with current epochs) whose slots sit on the
        given worker — the loss payload when that worker dies."""
        with self._lock:
            out: dict[int, int] = {}
            for row in self._entries:
                for e in row:
                    if e.worker_id == worker_id and not e.lost:
                        out[e.map_id] = e.epoch
            return out

    def mark_worker_lost(self, worker_id: str) -> dict[int, int]:
        lost = self.entries_owned_by(worker_id)
        with self._lock:
            for row in self._entries:
                for e in row:
                    if e.worker_id == worker_id:
                        e.lost = True
        return lost

    # -- graceful-drain migration ---------------------------------------
    def begin_migration(self, worker_id: str, faults=None):
        """Plan the retiring worker's live slots as contiguous fetch
        runs, each slot tagged with its NEXT epoch: the copies the
        drain registers commit at that bumped epoch (register advances
        ``_epochs`` on success), so a straggling write from the old
        attempt — or a late speculative duplicate — is epoch-stale and
        discarded.  The tracker's OWN epoch map is NOT advanced here: a
        run that fails to migrate must still look lost at its old epoch
        so lineage recovery accepts the loss report.  Returns ``(runs,
        dropped)`` where each run is one ``migrate_slots`` RPC payload
        and ``dropped`` counts slots withheld by
        ``cluster.migrate.drop``.  A drop withholds the ENTIRE map
        output, not just the one slot: epochs are tracked per map_id,
        so migrating a map's other slots at epoch+1 while one slot
        stays lost at the old epoch would make that slot's loss report
        look stale forever (recovery filters on ``map_epoch <= lost
        epoch``) and the reduce would spin without recomputing.
        Withheld maps stay on the retiring worker at their old epoch
        and route through lineage recovery instead."""
        runs: list[dict] = []
        dropped = 0
        dropped_mids: set[int] = set()
        with self._lock:
            if faults is not None:
                for pid, row in enumerate(self._entries):
                    for e in row:
                        if e.worker_id != worker_id or e.lost:
                            continue
                        if e.map_id not in dropped_mids and faults.check(
                                "cluster.migrate.drop",
                                shuffle=self.shuffle_id, part=pid,
                                map=e.map_id) is not None:
                            dropped_mids.add(e.map_id)
            for pid, row in enumerate(self._entries):
                keep = []
                for e in row:
                    if e.worker_id != worker_id or e.lost:
                        continue
                    if e.map_id in dropped_mids:
                        dropped += 1
                        continue
                    keep.append(e)
                # contiguous source-slot ranges fetch as one stream each
                i, n = 0, len(keep)
                while i < n:
                    j = i + 1
                    while j < n and keep[j].wslot == keep[j - 1].wslot + 1:
                        j += 1
                    seg = keep[i:j]
                    runs.append({"pid": pid, "lo": seg[0].wslot,
                                 "hi": seg[-1].wslot + 1,
                                 "map_ids": [e.map_id for e in seg],
                                 "rows": [e.rows for e in seg],
                                 "epochs": [
                                     self._epochs.get(e.map_id, 0) + 1
                                     for e in seg]})
                    i = j
        return runs, dropped

    # -- ShuffleTransport SPI -------------------------------------------
    def write_partition(self, shuffle_id, map_id, part_id, batch,
                        epoch=None) -> None:
        raise RuntimeError(
            "ClusterMapOutputTracker is a read-side directory; map "
            "writes happen in the workers (ClusterLineage.recompute "
            "re-dispatches fragments instead of writing locally)")

    def map_epoch(self, shuffle_id, map_id: int) -> int:
        with self._lock:
            return self._epochs.get(map_id, 0)

    def map_output_present(self, shuffle_id, part_id: int,
                           map_id: int) -> bool:
        with self._lock:
            return any(e.map_id == map_id and not e.lost
                       for e in self._entries[part_id])

    def invalidate_map_outputs(self, shuffle_id,
                               map_ids) -> dict[int, int]:
        wanted = set(int(m) for m in map_ids)
        with self._lock:
            new_epochs = {m: self._epochs.get(m, 0) + 1 for m in wanted}
            self._epochs.update(new_epochs)
            for row in self._entries:
                for e in row:
                    if e.map_id in wanted:
                        e.lost = True
                        e.epoch = new_epochs[e.map_id]
        if self._journal is not None and new_epochs:
            self._journal.append(
                "map_invalidate", sid=str(self.shuffle_id),
                epochs={str(m): e for m, e in new_epochs.items()})
        return new_epochs

    def partition_sizes(self, shuffle_id) -> dict[int, int]:
        with self._lock:
            return {pid: sum(e.size for e in row if not e.lost)
                    for pid, row in enumerate(self._entries) if row}

    def partition_rows(self, shuffle_id) -> dict[int, int]:
        with self._lock:
            return {pid: sum(e.rows for e in row if not e.lost)
                    for pid, row in enumerate(self._entries) if row}

    def batch_sizes(self, shuffle_id, part_id: int) -> list[int]:
        with self._lock:
            return [e.size for e in self._entries[part_id]]

    def fetch_partition(self, shuffle_id, part_id: int, lo: int = 0,
                        hi: int | None = None) -> Iterator:
        """Stream slots [lo, hi) of one reduce partition from the
        workers holding them, in map-id order.  A worker whose fetch
        fails terminally is marked dead and ALL its map outputs for
        this shuffle surface in one MapOutputLostError, so one recovery
        round relocates everything it held (reference: one
        FetchFailed fails the stage once per lost executor, not once
        per missing block)."""
        crash_point(self._faults, "shuffle_read",
                    shuffle=str(shuffle_id)[:12], part=part_id)
        if self._faults is not None:
            with self._lock:
                snap = list(self._entries[part_id])[lo:hi]
            if snap:
                owner = snap[0].worker_id
                act = self._faults.check("cluster.worker.dead",
                                         shuffle=shuffle_id,
                                         part=part_id, worker=owner)
                if act is not None and len(self.cluster.live_workers()) > 1:
                    # SIGKILL the owner of the first requested slot —
                    # the fetch below then fails for real and the
                    # DETECTION + recovery machinery runs unfaked
                    self.cluster.kill_worker(owner)
        delivered = 0
        while True:
            self.ctx.check_cancel()
            with self._lock:
                snap = list(self._entries[part_id])[lo:hi]
            snap = snap[delivered:]
            lost = {e.map_id: e.epoch for e in snap if e.lost}
            if lost:
                raise MapOutputLostError(
                    shuffle_id, part_id, lost,
                    detail="slots invalidated pending recompute")
            if not snap:
                return
            resume = False
            for worker_id, wlo, whi in _runs(snap):
                addr = self._shuffle_addr[worker_id]
                try:
                    for batch in self._fetch_run(addr, part_id, wlo, whi):
                        yield batch
                        delivered += 1
                except MapOutputLostError:
                    raise
                except ShuffleFetchError as e:
                    # a graceful drain may have RELOCATED the remaining
                    # slots while this reader streamed: if nothing
                    # undelivered still lives on the failed worker,
                    # resume from the new owners instead of declaring a
                    # loss (the planned-scale-down copy, not a recompute)
                    with self._lock:
                        cur = list(self._entries[part_id])[lo:hi]
                    undelivered = cur[delivered:]
                    if undelivered and not any(
                            x.worker_id == worker_id and not x.lost
                            for x in undelivered):
                        get_registry().inc("cluster.migrated_refetches")
                        resume = True
                        break
                    handle = self.cluster.worker_by_id(worker_id)
                    if handle is not None:
                        self.cluster.mark_worker_lost(
                            worker_id, f"fetch failed: {e}")
                    all_lost = self.mark_worker_lost(worker_id)
                    if not all_lost:
                        raise
                    raise MapOutputLostError(
                        shuffle_id, part_id, all_lost,
                        detail=f"worker {worker_id} died mid-fetch: {e}"
                    ) from e
            if not resume:
                return

    def _fetch_run(self, addr, part_id, wlo, whi) -> Iterator:
        from spark_rapids_tpu.shuffle.retry import fetch_remote_with_retry
        ctx = self.ctx
        tracer = ctx.tracer
        trace = tracer.trace_header() if tracer is not None else None
        yield from fetch_remote_with_retry(
            addr, self.shuffle_id, part_id, lo=wlo, hi=whi,
            device=ctx.is_device, conf=ctx.conf, tracer=tracer,
            trace=trace, lifecycle=ctx.lifecycle)

    # -- downstream fragment support ------------------------------------
    def reader_groups(self, groups_spec=None):
        """(groups, locality) for a WorkerShuffleReaderExec consuming
        this shuffle.  ``groups_spec`` is the AQE reader's list of
        ``[(pid, lo, hi), ...]`` slices, or None for the identity
        mapping (one group per reduce partition).  ``locality[gi]`` maps
        worker_id -> bytes served, feeding locality-aware scheduling."""
        if groups_spec is None:
            groups_spec = [[(pid, 0, None)] for pid in
                           range(self.num_parts)]
        groups, locality = [], []
        with self._lock:
            for spec in groups_spec:
                runs, loc = [], {}
                for pid, lo, hi in spec:
                    snap = list(self._entries[pid])[lo:hi]
                    for worker_id, wlo, whi in _runs(snap):
                        runs.append((self._shuffle_addr[worker_id],
                                     pid, wlo, whi))
                    for e in snap:
                        loc[e.worker_id] = loc.get(e.worker_id, 0) + e.size
                groups.append(runs)
                locality.append(loc)
        return groups, locality

    def close(self) -> None:
        """Best-effort release of this shuffle's slots on every live
        worker (query teardown: ExecCtx.close closes every cached
        transport, this one included)."""
        if self._closed:
            return
        self._closed = True
        if self._journal is not None:
            # a closed shuffle is not resumable: drop it from the
            # journaled state so compaction forgets it
            self._journal.append("shuffle_close",
                                 sid=str(self.shuffle_id))
        from spark_rapids_tpu.cluster.rpc import rpc_call
        with self._lock:
            workers = list(self._shuffle_addr)
        for wid in workers:
            handle = self.cluster.worker_by_id(wid)
            if handle is None or not handle.alive:
                continue
            try:
                rpc_call(handle.rpc_addr, "release_shuffle",
                         {"shuffle_id": self.shuffle_id},
                         conf=self.ctx.conf, retries=0, timeout=5.0)
            except (ConnectionError, OSError):
                pass


def _runs(entries) -> Iterator[tuple]:
    """Group an ordered entry slice into per-worker contiguous-slot
    fetch runs ``(worker_id, wlo, whi)``."""
    i, n = 0, len(entries)
    while i < n:
        j = i + 1
        while (j < n and entries[j].worker_id == entries[i].worker_id
               and entries[j].wslot == entries[j - 1].wslot + 1):
            j += 1
        yield (entries[i].worker_id, entries[i].wslot,
               entries[j - 1].wslot + 1)
        i = j


@dataclass
class ClusterLineage:
    """Lineage handle for a cluster shuffle: recovery's ``recompute``
    re-dispatches the lost child partitions' fragments onto SURVIVING
    workers (reassignment) instead of re-draining locally — the
    DAGScheduler's resubmit-on-another-executor behavior."""

    exchange_clone: Any      # picklable fragment template
    cluster: Any             # ClusterDriver
    tracker: ClusterMapOutputTracker
    num_parts: int
    frag_conf: dict
    conf_fp: str | None = None

    def recompute(self, ctx: ExecCtx, transport,
                  epochs: dict[int, int]) -> int:
        if self.conf_fp is not None:
            from spark_rapids_tpu.exec.recovery import conf_fingerprint
            now = conf_fingerprint(ctx.conf)
            if now != self.conf_fp:
                raise RuntimeError(
                    f"cluster shuffle {self.tracker.shuffle_id}: conf "
                    f"changed since the map stage ran "
                    f"({self.conf_fp[:12]} -> {now[:12]}); lineage "
                    "recomputation would not be deterministic")
        lost_cpids = sorted({m // MAP_ID_STRIDE for m in epochs})
        _dispatch_fragments(self.cluster, ctx, self.tracker,
                            self.exchange_clone, self.num_parts,
                            lost_cpids, self.frag_conf, epochs=epochs)
        reg = get_registry()
        reg.inc("stage_recomputes")
        reg.inc("map_outputs_recomputed", len(epochs))
        return len(epochs)


# ---------------------------------------------------------------------------
# fragment cloning
# ---------------------------------------------------------------------------

def _clone_subtree(root, ctx: ExecCtx):
    """Clone a plan subtree into a picklable fragment body.

    Upstream CLUSTER shuffles materialize now (recursively, via
    ``_shuffled`` -> this module again) and become
    WorkerShuffleReaderExec leaves; broadcasts materialize driver-side
    into StaticBroadcastExec blobs; stage boundaries resolve to their
    adaptive replacement.  Returns (None, reason) when the subtree
    cannot run in a worker (a non-clusterable device exchange, or an
    upstream that itself fell back in-process) — the caller falls back
    to the in-process path.  Shared by the shuffle map-side clone
    (:func:`_clone_fragment`) and write fragments
    (:func:`dispatch_write_fragments`)."""
    from spark_rapids_tpu.exec.exchange import (AdaptiveShuffleReaderExec,
                                                BroadcastExchangeExec,
                                                ShuffleExchangeExec)
    from spark_rapids_tpu.exec.stage_boundary import StageBoundaryExec
    from spark_rapids_tpu.shuffle.serializer import serialize_batch
    memo: dict[int, Any] = {}
    poison: list[str] = []

    def reader_from(tr, src_sid, schema, groups_spec):
        groups, locality = tr.reader_groups(groups_spec)
        node = WorkerShuffleReaderExec(src_sid, schema, groups,
                                       src=(src_sid, groups_spec))
        node._cluster_locality = locality
        return node

    def walk(node):
        got = memo.get(id(node))
        if got is not None:
            return got
        if isinstance(node, StageBoundaryExec):
            out = walk(node._resolved(ctx))
            memo[id(node)] = out
            return out
        if isinstance(node, AdaptiveShuffleReaderExec) and \
                getattr(node.children[0], "_cluster_ok", False):
            ex = node.children[0]
            tr = ex._shuffled(ctx)  # stage barrier (recursive cluster run)
            if not isinstance(tr, ClusterMapOutputTracker):
                poison.append(f"upstream shuffle "
                              f"{str(ex.shuffle_id)[:12]} ran in-process")
                out = node
            else:
                out = reader_from(tr, ex.shuffle_id, node.output_schema,
                                  node._groups(ctx))
            memo[id(node)] = out
            return out
        if isinstance(node, ShuffleExchangeExec):
            if not getattr(node, "_cluster_ok", False):
                poison.append(f"non-clusterable exchange "
                              f"{node.node_desc()}")
                memo[id(node)] = node
                return node
            tr = node._shuffled(ctx)
            if not isinstance(tr, ClusterMapOutputTracker):
                poison.append(f"upstream shuffle "
                              f"{str(node.shuffle_id)[:12]} ran "
                              "in-process")
                memo[id(node)] = node
                return node
            out = reader_from(tr, node.shuffle_id, node.output_schema,
                              None)
            memo[id(node)] = out
            return out
        if isinstance(node, BroadcastExchangeExec):
            b = node.materialize(ctx)
            out = StaticBroadcastExec(serialize_batch(b),
                                      node.output_schema)
            memo[id(node)] = out
            return out
        if not node.children:
            memo[id(node)] = node
            return node
        c = copy.copy(node)
        # lazily-built jit wrappers close over the original node and do
        # not pickle; the worker rebuilds them (same compile-cache keys)
        for k in [k for k in vars(c) if _JIT_ATTR.search(k)]:
            c.__dict__.pop(k, None)
        c.children = tuple(walk(ch) for ch in node.children)
        memo[id(node)] = c
        return c

    walked = walk(root)
    if poison:
        return None, "; ".join(poison[:3])
    return walked, None


def _clone_fragment(exchange, ctx: ExecCtx):
    """Clone the exchange + child subtree into a picklable map fragment
    (see :func:`_clone_subtree` for the walk semantics)."""
    walked, reason = _clone_subtree(exchange.children[0], ctx)
    if walked is None:
        return None, reason
    clone = copy.copy(exchange)
    clone._shuffle_id = exchange.shuffle_id  # pin: id(n) never crosses
    clone.children = (walked,)
    return clone, None


def _readers(node, out=None) -> list:
    if out is None:
        out = []
    if isinstance(node, WorkerShuffleReaderExec):
        out.append(node)
    for c in node.children:
        _readers(c, out)
    return out


def _refresh_readers(clone, ctx: ExecCtx) -> None:
    """Rebuild every reader leaf's run list from the CURRENT upstream
    tracker state: a recovery may have relocated slots since the clone
    was built, and a re-dispatched fragment must read from where the
    data lives now."""
    for rd in _readers(clone):
        if rd._src is None:
            continue
        sid, groups_spec = rd._src
        tr = ctx.cache.get(("shuffle", sid, ctx.backend))
        if isinstance(tr, ClusterMapOutputTracker):
            groups, locality = tr.reader_groups(groups_spec)
            rd.groups = [list(g) for g in groups]
            rd._cluster_locality = locality


# ---------------------------------------------------------------------------
# scheduling + dispatch
# ---------------------------------------------------------------------------

def _locality(clone, ncpids: int) -> list[dict]:
    """Per child partition: worker_id -> upstream bytes already local.
    Sums every reader leaf's contribution; empty dicts when the
    fragment reads only base tables."""
    score: list[dict] = [dict() for _ in range(ncpids)]
    for rd in _readers(clone):
        loc = getattr(rd, "_cluster_locality", None)
        if not loc:
            continue
        for cpid in range(min(ncpids, len(loc))):
            for wid, nbytes in loc[cpid].items():
                score[cpid][wid] = score[cpid].get(wid, 0) + nbytes
    return score


def _assign_cpids(pending, live, score) -> dict[str, list[int]]:
    """Locality-first assignment: each child partition goes to the live
    worker already holding the most of its upstream bytes, tiebreak
    least-loaded (reference: DAGScheduler preferred locations from
    MapOutputTracker, then round-robin)."""
    reg = get_registry()
    load = {h.worker_id: 0 for h in live}
    assign: dict[str, list[int]] = {h.worker_id: [] for h in live}
    for cpid in sorted(pending):
        sc = score[cpid] if cpid < len(score) else {}
        best = min(live, key=lambda h: (-sc.get(h.worker_id, 0),
                                        load[h.worker_id], h.worker_id))
        if sc.get(best.worker_id, 0) > 0:
            reg.inc("cluster.locality_assignments")
        assign[best.worker_id].append(cpid)
        load[best.worker_id] += 1
    return {w: cps for w, cps in assign.items() if cps}


def _dispatch_fragments(cluster, ctx: ExecCtx, tracker, clone,
                        num_parts: int, cpids, frag_conf: dict,
                        epochs: dict[int, int] | None = None) -> None:
    """Run map fragments for the given child partitions over the live
    workers, retrying on surviving workers when one dies mid-round and
    cascading peer-loss reports into upstream lineage recovery.  All
    resulting slots are registered into ``tracker`` before returning
    (the stage barrier)."""
    from concurrent.futures import ThreadPoolExecutor
    from spark_rapids_tpu.cluster.rpc import RpcError, rpc_call
    reg = get_registry()
    journal = getattr(tracker, "_journal", None)
    speculate = SPECULATION_ENABLED.get(ctx.conf.settings)
    pending = sorted(int(c) for c in cpids)
    max_rounds = max(4, 2 * len(cluster.workers()) + 2)
    rounds = 0
    # every dispatch (retry round, speculative duplicate) carries a
    # distinct attempt id, echoed in the worker's reply — duplicate
    # attempts of one fragment are distinguishable at commit time
    attempt_seq = itertools.count()
    while pending:
        ctx.check_cancel()
        rounds += 1
        crash_point(tracker._faults, "dispatch", round=rounds,
                    shuffle=str(tracker.shuffle_id)[:12])
        if rounds > max_rounds:
            raise ClusterExecError(
                f"shuffle {str(tracker.shuffle_id)[:12]}: fragment "
                f"dispatch did not converge after {rounds - 1} rounds "
                f"({len(pending)} partitions still unplaced)")
        live = cluster.schedulable_workers()
        if not live:
            raise ClusterExecError(
                f"shuffle {str(tracker.shuffle_id)[:12]}: no live "
                "workers left to run map fragments")
        _refresh_readers(clone, ctx)
        assign = _assign_cpids(pending, live, _locality(clone,
                                                        max(pending) + 1))
        handles = {h.worker_id: h for h in live}

        tracer = ctx.tracer

        def run_one(wid: str, cps: list[int]):
            if tracker._faults is not None:
                act = tracker._faults.check(
                    "cluster.worker.slow", worker=wid,
                    shuffle=tracker.shuffle_id)
                if act is not None:
                    # a straggling executor, modelled driver-side so
                    # speculation's duplicate has a real head start
                    time.sleep(act.param("seconds", 2.0))
                act = tracker._faults.check(
                    "cluster.worker.flaky", worker=wid,
                    shuffle=tracker.shuffle_id)
                if act is not None:
                    raise RpcError(
                        f"injected fault: flaky worker {wid}")
            spec = {"exchange": clone, "num_parts": num_parts,
                    "cpids": cps, "conf": frag_conf,
                    "attempt": next(attempt_seq)}
            if tracer is not None:
                # propagate the query/trace ids: the worker's fragment
                # spans land under THIS query and ship back in the reply
                spec["trace"] = tracer.trace_header()
            if epochs:
                spec["epochs"] = {m: e for m, e in epochs.items()
                                  if m // MAP_ID_STRIDE in set(cps)}
            blob = pickle.dumps(spec, protocol=pickle.HIGHEST_PROTOCOL)
            reg.inc("cluster.fragments_dispatched")
            handle = handles.get(wid) or cluster.worker_by_id(wid)
            return rpc_call(handle.rpc_addr, "run_fragment",
                            {"shuffle_id": str(tracker.shuffle_id)},
                            blob=blob, conf=ctx.conf,
                            faults=tracker._faults)[0]

        next_pending: list[int] = []
        if speculate:
            _dispatch_round_speculative(cluster, ctx, tracker, tracer,
                                        assign, run_one, next_pending)
        else:
            results: dict[str, Any] = {}
            with ThreadPoolExecutor(max_workers=len(assign)) as pool:
                futs = {wid: pool.submit(run_one, wid, cps)
                        for wid, cps in assign.items()}
                for wid, fut in futs.items():
                    try:
                        results[wid] = fut.result()
                    except (RpcError, ConnectionError, OSError) as e:
                        results[wid] = e
            for wid, cps in assign.items():
                _consume_result(cluster, ctx, tracker, tracer, wid, cps,
                                results[wid], next_pending)
        round_pending = pending
        pending = sorted(next_pending)
        if journal is not None:
            # the dispatch frontier is journaled per round so a
            # restarted driver resumes from the last completed
            # partitions instead of re-running the whole stage
            newly_done = sorted(set(round_pending) - set(pending))
            if newly_done:
                journal.append("frontier",
                               sid=str(tracker.shuffle_id),
                               done=newly_done)


def _consume_result(cluster, ctx: ExecCtx, tracker, tracer, wid: str,
                    cps: list, res, next_pending: list,
                    register=None) -> None:
    """Fold one fragment attempt's outcome into the round: register a
    success, re-pool a structured failure (after driving upstream
    recovery), and pass a transport failure through the cluster's
    failure verdict (lost / quarantined / tolerated — all re-pool).

    ``register`` overrides what a success commits: shuffle fragments
    register map slots into ``tracker`` (the default); write fragments
    register task-attempt manifests with the job's commit coordinator.
    Either target applies its own first-writer-wins guard, so feeding
    it a duplicate attempt is always safe."""
    if isinstance(res, Exception):
        # control plane unreachable or flaky: the verdict decides
        # whether the worker is gone or just benched; either way its
        # partitions go back in the pool
        cluster.record_worker_failure(wid, f"run_fragment RPC: {res}")
        next_pending.extend(cps)
        return
    spans = res.get("spans")
    if tracer is not None and spans:
        # merge the worker's spans (success OR structured
        # failure) onto the driver timeline, one labelled lane
        # per worker pid
        tracer.ensure_lane(tracer.pid, "driver")
        tracer.ensure_lane(int(spans["pid"]),
                           f"cluster worker {wid}")
        tracer.ingest_wall(spans.get("events") or [])
    kind = res.get("error_kind")
    if kind == "draining":
        # a planned removal raced this dispatch: nobody died, the
        # partitions simply move to the survivors next round
        get_registry().inc("cluster.fragments_rejected_draining")
        next_pending.extend(cps)
        return
    if kind == "write_failed":
        # the worker's write attempt itself failed (I/O error while
        # staging): nothing visible happened — count a failure verdict
        # and re-pool so the next round retries under a fresh attempt id
        get_registry().inc("cluster.write_fragment_failures")
        cluster.record_worker_failure(
            wid, f"write fragment: {res.get('error')}")
        next_pending.extend(cps)
        return
    if kind:
        _handle_fragment_loss(cluster, ctx, res)
        next_pending.extend(cps)
        return
    cluster.note_worker_success(wid)
    if register is not None:
        register(wid, res)
    else:
        tracker.register(wid, res["shuffle"], res["entries"])


def _dispatch_round_speculative(cluster, ctx: ExecCtx, tracker, tracer,
                                assign, run_one, next_pending,
                                register=None) -> None:
    """One dispatch round with straggler speculation: every assignment
    runs as before, but a single attempt whose wall time exceeds
    ``speculation.multiplier`` × the round's running median gets a
    DUPLICATE on another schedulable worker; the first completed
    attempt per assignment wins and commits (the tracker's
    first-writer-wins epoch check rejects the loser's slots — the
    exactly-once guarantee).  Losers still running when the round
    completes are abandoned to finish in the background."""
    from concurrent.futures import ThreadPoolExecutor
    from spark_rapids_tpu.cluster.rpc import RpcError
    reg = get_registry()
    s = ctx.conf.settings
    mult = SPECULATION_MULTIPLIER.get(s)
    min_rt = SPECULATION_MIN_RUNTIME.get(s)
    pool = ThreadPoolExecutor(
        max_workers=2 * len(assign) + 1,
        thread_name_prefix="tpu-cluster-speculate")

    def attempt(wid, cps):
        def call():
            try:
                return run_one(wid, cps)
            except (RpcError, ConnectionError, OSError) as e:
                return e
        return (wid, pool.submit(call), time.monotonic())

    # key -> list of live attempts; first completion wins the key
    attempts = {tuple(cps): [attempt(wid, cps)]
                for wid, cps in assign.items()}
    owner = {tuple(cps): wid for wid, cps in assign.items()}
    walls: list[float] = []
    done_keys: set = set()
    try:
        while len(done_keys) < len(attempts):
            ctx.check_cancel()
            time.sleep(0.02)
            now = time.monotonic()
            for key, atts in attempts.items():
                if key in done_keys:
                    continue
                finished = [(w, f, t0) for (w, f, t0) in atts
                            if f.done()]
                winner = next(
                    ((w, f, t0) for (w, f, t0) in finished
                     if not isinstance(f.result(), Exception)
                     and not f.result().get("error_kind")), None)
                if winner is None and len(finished) == len(atts):
                    # every attempt failed: consume one failure so the
                    # partitions re-pool (and the loss is handled)
                    w, f, t0 = finished[-1]
                    _consume_result(cluster, ctx, tracker, tracer, w,
                                    list(key), f.result(), next_pending,
                                    register=register)
                    done_keys.add(key)
                    continue
                if winner is None:
                    # still running: speculate when the sole attempt
                    # has outlived the round's typical fragment
                    if len(atts) == 1 and walls:
                        import statistics
                        med = statistics.median(walls)
                        elapsed = now - atts[0][2]
                        if elapsed > max(min_rt, mult * med):
                            cand = [h for h in
                                    cluster.schedulable_workers()
                                    if h.worker_id not in
                                    {w for (w, _, _) in atts}]
                            if cand:
                                tgt = cand[0].worker_id
                                atts.append(attempt(tgt, list(key)))
                                reg.inc("speculative_launched")
                                print(f"cluster: speculating "
                                      f"{list(key)} of "
                                      f"{owner[key]} on {tgt}",
                                      file=sys.stderr)
                    continue
                w, f, t0 = winner
                wall = now - t0
                walls.append(wall)
                reg.observe("cluster.fragment.wall_seconds", wall)
                _consume_result(cluster, ctx, tracker, tracer, w,
                                list(key), f.result(), next_pending,
                                register=register)
                if len(atts) > 1:
                    # a duplicate existed: exactly one attempt's work
                    # is wasted (the loser's commit is epoch-rejected)
                    reg.inc("speculative_wasted", len(atts) - 1)
                    for (lw, lf, _) in atts:
                        if lf is f or not lf.done():
                            continue
                        lres = lf.result()
                        if not isinstance(lres, Exception) \
                                and not lres.get("error_kind"):
                            # commit the already-finished loser too:
                            # first-writer-wins discards its slots
                            # (write path: its manifests)
                            if register is not None:
                                register(lw, lres)
                            else:
                                tracker.register(lw, lres["shuffle"],
                                                 lres["entries"])
                done_keys.add(key)
    finally:
        # abandon still-running losers; their late replies are never
        # consumed and their slots are epoch-stale by construction
        pool.shutdown(wait=False)


def _handle_fragment_loss(cluster, ctx: ExecCtx, res: dict) -> None:
    """A fragment failed because UPSTREAM data disappeared: mark the
    dead peer, then drive the upstream shuffle's tracker through the
    standard recovery path so its slots are recomputed before the
    fragment retries."""
    from spark_rapids_tpu.exec import recovery
    sid = res.get("lost_sid")
    up = ctx.cache.get(("shuffle", sid, ctx.backend))
    if res.get("error_kind") == "peer_fetch":
        peer = tuple(res.get("peer") or ())
        handle = cluster.worker_by_shuffle_addr(peer)
        if handle is not None:
            cluster.mark_worker_lost(handle.worker_id,
                                     "peer fetch failed in fragment")
        if not isinstance(up, ClusterMapOutputTracker):
            raise ClusterExecError(
                f"fragment lost upstream shuffle {str(sid)[:12]} served "
                f"by {peer}, and no cluster tracker exists to recover it")
        lost = up.mark_worker_lost(handle.worker_id) if handle is not None \
            else {}
        if not lost:
            return  # already recovered by a concurrent reader
        err = MapOutputLostError(sid, -1, lost,
                                 detail="worker lost (reported by peer)")
    else:  # "map_lost": the peer's own store reported structured loss
        lost = {int(k): int(v)
                for k, v in (res.get("lost") or {}).items()}
        if not isinstance(up, ClusterMapOutputTracker) or not lost:
            raise ClusterExecError(
                f"fragment reported lost map outputs for shuffle "
                f"{str(sid)[:12]} but no cluster tracker exists")
        err = MapOutputLostError(sid, int(res.get("part", -1)), lost,
                                 detail="reported by fragment",
                                 observed_empty=bool(
                                     res.get("observed_empty")))
    recovery._recover(ctx, up, err)


# ---------------------------------------------------------------------------
# entry point (hooked from ShuffleExchangeExec._do_shuffle_device)
# ---------------------------------------------------------------------------

#: collapse object ids and other hex runs out of node descriptions:
#: shuffle/plan ids embed ``id(node)``, which never survives a driver
#: restart, so resume matching must hash the fragment's SHAPE instead
_UNSTABLE_HEX = re.compile(r"0x[0-9a-fA-F]+|[0-9a-f]{8,}")


def _stable_fragment_fp(clone) -> str:
    """Restart-stable identity of a map fragment: a digest over the
    clone subtree's node types, hex-scrubbed descriptions, and output
    schemas.  Two plans of the same query in different driver
    processes produce the same fingerprint even though their shuffle
    ids differ — the key the journal uses to hand a recovered
    shuffle's surviving map outputs to the resumed query."""
    h = hashlib.sha1()

    def walk(node):
        h.update(type(node).__name__.encode())
        h.update(_UNSTABLE_HEX.sub("#", node.node_desc()).encode())
        h.update(repr(node.output_schema).encode())
        h.update(b"(")
        for c in getattr(node, "children", None) or ():
            walk(c)
        h.update(b")")

    walk(clone)
    return h.hexdigest()


def cluster_do_shuffle(cluster, exchange, ctx: ExecCtx, child):
    """Materialize one cluster-tagged exchange's map side across the
    worker pool.  Returns the registered ClusterMapOutputTracker, or
    None to signal the caller to fall back to the classic in-process
    path (no live workers, unpicklable fragment, or a poisoned
    subtree)."""
    from spark_rapids_tpu.exec.recovery import conf_fingerprint
    reg = get_registry()
    if not cluster.live_workers():
        reg.inc("cluster.fallback_inprocess")
        return None
    n = exchange.partitioning.num_partitions
    sid = exchange.shuffle_id
    ncpids = child.num_partitions(ctx)
    clone, reason = _clone_fragment(exchange, ctx)
    if clone is None:
        reg.inc("cluster.fallback_inprocess")
        ctx.trace_event("cluster.fallback", "cluster",
                        shuffle=str(sid)[:12], reason=reason)
        return None
    frag_conf = scrub_worker_conf(dict(ctx.conf.settings))
    try:
        pickle.dumps(clone, protocol=pickle.HIGHEST_PROTOCOL)
    # enginelint: disable=RL001 (fallback to the in-process path is the handled outcome; the counter + trace event record it)
    except Exception:  # noqa: BLE001 - any unpicklable node falls back
        reg.inc("cluster.fragment_unpicklable")
        reg.inc("cluster.fallback_inprocess")
        ctx.trace_event("cluster.fallback", "cluster",
                        shuffle=str(sid)[:12],
                        reason="fragment not picklable")
        return None
    tracker = ClusterMapOutputTracker(cluster, ctx, sid, n)
    pending = list(range(ncpids))
    resume_epochs = None
    journal = getattr(cluster, "journal", None)
    if journal is not None:
        fp = _stable_fragment_fp(clone)
        jconf_fp = conf_fingerprint(frag_conf)
        # a recovered driver may hold this exact fragment's surviving
        # map outputs under the OLD shuffle id: claim them (workers
        # re-key their slots to the new id) before opening the new
        # journal record, then seed the tracker and shrink the
        # dispatch frontier to what was actually lost
        claim = None
        claimer = getattr(cluster, "claim_resume", None)
        if callable(claimer):
            claim = claimer(fp, str(sid), n, ncpids, jconf_fp)
        journal.append("shuffle_open", sid=str(sid), fp=fp,
                       num_parts=n, ncpids=ncpids, conf_fp=jconf_fp)
        tracker._journal = journal
        if claim is not None:
            tracker._epochs.update({int(m): int(e) for m, e
                                    in claim["epochs"].items()})
            seeded = 0
            for wid, ents in claim["entries"].items():
                tracker.register(wid, tuple(claim["addrs"][wid]), ents)
                seeded += len(ents)
            done = set(int(c) for c in claim["done"])
            if done:
                journal.append("frontier", sid=str(sid),
                               done=sorted(done))
            pending = [c for c in pending if c not in done]
            resume_epochs = {int(m): int(e) for m, e
                             in claim["epochs"].items()} or None
            reg.inc("cluster.map_outputs_resumed", seeded)
            ctx.trace_event("cluster.resume", "cluster",
                            shuffle=str(sid)[:12], seeded=seeded,
                            done=len(done),
                            recomputing=len(pending))
            lc = getattr(ctx, "lifecycle", None)
            if lc is not None and hasattr(lc, "annotations"):
                lc.annotations.setdefault("cluster.resumed", []).append(
                    {"shuffle": str(sid)[:12], "map_outputs": seeded,
                     "partitions_done": len(done),
                     "partitions_recomputing": len(pending)})
    with ctx.trace_span("cluster.map_stage", "cluster",
                        shuffle=str(sid)[:12], partitions=ncpids,
                        workers=len(cluster.live_workers())):
        _dispatch_fragments(cluster, ctx, tracker, clone, n,
                            pending, frag_conf, epochs=resume_epochs)
    tracer = ctx.tracer
    if tracer is not None:
        # spans a long fragment streamed back on heartbeats MID-run
        # (the completion reply only carries what was left unshipped)
        for ev in cluster.drain_query_spans(ctx.query_id):
            pid = ev.get("pid")
            if isinstance(pid, int):
                h = cluster.worker_by_pid(pid)
                tracer.ensure_lane(pid, f"cluster worker "
                                        f"{h.worker_id if h else pid}")
            tracer.ingest_wall([ev])
    ctx.register_lineage(sid, ClusterLineage(
        exchange_clone=clone, cluster=cluster, tracker=tracker,
        num_parts=n, frag_conf=frag_conf,
        conf_fp=getattr(exchange, "_conf_fp",
                        conf_fingerprint(ctx.conf))))
    reg.inc("cluster.shuffles_clustered")
    return tracker


# ---------------------------------------------------------------------------
# write fragments (hooked from exec/write_exec.run_write_job)
# ---------------------------------------------------------------------------

def dispatch_write_fragments(cluster, ctx: ExecCtx, coordinator,
                             write_node, tasks) -> bool:
    """Run a write job's tasks as cluster write fragments: each worker
    writes its assigned child partitions into private staging dirs under
    the job's ``_staging`` tree and ships back one manifest per task,
    which the driver-side ``coordinator`` arbitrates first-writer-wins.

    Rounds mirror :func:`_dispatch_fragments` — failures/draining
    replies re-pool onto survivors, upstream map loss drives lineage
    recovery, and straggler speculation may run duplicate attempts
    (each under its own attempt id; the coordinator discards the
    loser's manifests).  A task is only considered placed once the
    coordinator holds a winning manifest for it, so a dropped commit
    message re-dispatches the task under a fresh attempt.

    Returns False to signal the in-process fallback (no live workers,
    unpicklable or poisoned fragment body); the caller then runs the
    same attempt/commit protocol on the driver."""
    from concurrent.futures import ThreadPoolExecutor
    from spark_rapids_tpu.cluster.rpc import RpcError, rpc_call
    reg = get_registry()
    if not cluster.live_workers():
        reg.inc("cluster.write_fallback_inprocess")
        return False
    walked, reason = _clone_subtree(write_node.children[0], ctx)
    if walked is None:
        reg.inc("cluster.write_fallback_inprocess")
        ctx.trace_event("cluster.write_fallback", "cluster",
                        job=coordinator.job_id, reason=reason)
        return False
    try:
        pickle.dumps(walked, protocol=pickle.HIGHEST_PROTOCOL)
    # enginelint: disable=RL001 (fallback to the in-process write path is the handled outcome; the counter + trace event record it)
    except Exception:  # noqa: BLE001 - any unpicklable node falls back
        reg.inc("cluster.fragment_unpicklable")
        reg.inc("cluster.write_fallback_inprocess")
        ctx.trace_event("cluster.write_fallback", "cluster",
                        job=coordinator.job_id,
                        reason="fragment not picklable")
        return False
    cluster.register_write_coordinator(coordinator)
    faults = coordinator.faults
    frag_conf = scrub_worker_conf(dict(ctx.conf.settings))
    speculate = SPECULATION_ENABLED.get(ctx.conf.settings)
    wspec = {"path": coordinator.path, "fmt": write_node.fmt,
             "partition_by": list(write_node.partition_by),
             "options": dict(write_node.options),
             "job_id": coordinator.job_id}
    tracer = ctx.tracer
    tasks = sorted(int(t) for t in tasks)
    pending = list(tasks)
    max_rounds = max(4, 2 * len(cluster.workers()) + 2)
    rounds = 0
    with ctx.trace_span("cluster.write_stage", "cluster",
                        job=coordinator.job_id, tasks=len(tasks),
                        workers=len(cluster.live_workers())):
        while pending:
            ctx.check_cancel()
            rounds += 1
            if rounds > max_rounds:
                raise ClusterExecError(
                    f"write job {coordinator.job_id}: fragment dispatch "
                    f"did not converge after {rounds - 1} rounds "
                    f"({len(pending)} tasks without a committed attempt)")
            live = cluster.schedulable_workers()
            if not live:
                raise ClusterExecError(
                    f"write job {coordinator.job_id}: no live workers "
                    "left to run write fragments")
            _refresh_readers(walked, ctx)
            assign = _assign_cpids(pending, live,
                                   _locality(walked, max(pending) + 1))
            handles = {h.worker_id: h for h in live}

            def run_one(wid: str, cps: list[int]):
                if faults is not None:
                    act = faults.check("cluster.worker.slow", worker=wid,
                                       job=coordinator.job_id)
                    if act is not None:
                        time.sleep(act.param("seconds", 2.0))
                    act = faults.check("cluster.worker.flaky", worker=wid,
                                       job=coordinator.job_id)
                    if act is not None:
                        raise RpcError(
                            f"injected fault: flaky worker {wid}")
                    act = faults.check("cluster.worker.dead", worker=wid,
                                       job=coordinator.job_id)
                    if act is not None and len(cluster.live_workers()) > 1:
                        # kill the worker PROCESS shortly after dispatch
                        # so it dies mid-write: its partial attempt dirs
                        # stay in staging, never visible
                        t = threading.Timer(act.param("seconds", 0.15),
                                            cluster.kill_worker,
                                            args=[wid])
                        t.daemon = True
                        t.start()
                attempts = {int(c): coordinator.next_attempt(int(c))
                            for c in cps}
                spec = {"plan": walked, "write": wspec, "cpids": cps,
                        "attempts": attempts, "conf": frag_conf}
                if tracer is not None:
                    spec["trace"] = tracer.trace_header()
                blob = pickle.dumps(spec,
                                    protocol=pickle.HIGHEST_PROTOCOL)
                reg.inc("cluster.write_fragments_dispatched")
                handle = handles.get(wid) or cluster.worker_by_id(wid)
                return rpc_call(handle.rpc_addr, "run_write_fragment",
                                {"job_id": coordinator.job_id},
                                blob=blob, conf=ctx.conf,
                                faults=faults)[0]

            def register(wid: str, res: dict) -> None:
                for m in res.get("manifests") or ():
                    coordinator.register(m)

            next_pending: list[int] = []
            if speculate:
                _dispatch_round_speculative(cluster, ctx, None, tracer,
                                            assign, run_one, next_pending,
                                            register=register)
            else:
                results: dict[str, Any] = {}
                with ThreadPoolExecutor(max_workers=len(assign)) as pool:
                    futs = {wid: pool.submit(run_one, wid, cps)
                            for wid, cps in assign.items()}
                    for wid, fut in futs.items():
                        try:
                            results[wid] = fut.result()
                        except (RpcError, ConnectionError, OSError) as e:
                            results[wid] = e
                for wid, cps in assign.items():
                    _consume_result(cluster, ctx, None, tracer, wid, cps,
                                    results[wid], next_pending,
                                    register=register)
            # re-pool from the coordinator, the single source of truth:
            # a task stays pending until a manifest actually WON —
            # covering both failed attempts and commit messages the
            # io.write.commit.drop point swallowed
            pending = coordinator.missing(tasks)
    return True
