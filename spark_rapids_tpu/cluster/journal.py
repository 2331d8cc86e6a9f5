"""Write-ahead cluster journal: the driver's durable memory.

Every layer below the driver already self-heals (fetch retry, lineage
recompute, drain/quarantine/migrate, exactly-once write commits), but
the state that COORDINATES them — worker membership, the map-output
tracker's registrations, write-commit decisions, and each query's
dispatch frontier — lived only in the driver process.  This module
journals exactly that state so ``ClusterDriver.recover()`` can rebuild
a crashed driver and resume queries against lingering workers instead
of resetting the cluster (reference: spark.deploy.recoveryMode's
FILESYSTEM persistence engine, applied to the shuffle/write control
plane rather than app submission).

Disk discipline (same rules as obs/history.py):

* ``journal.log`` is append-only, one CRC-framed record per line
  (``<crc32 hex8> <json>\\n``).  Appends go through GROUP-COMMIT
  fsync: concurrent writers buffer under a lock, the first one through
  the I/O gate flushes and fsyncs the whole accumulated batch, and the
  rest observe durability without paying their own fsync.
* A torn tail (crash mid-write) is healed at open: the log is
  truncated back to the end of the last intact record.  A CRC-corrupt
  record mid-file stops replay at the last good record — everything
  after it is counted in ``journal_truncated_records``, never
  half-applied.
* Past ``spark.rapids.cluster.journal.maxBytes`` the log is
  snapshot-compacted: the fully replayed state is written to
  ``journal.snapshot`` (tmp + fsync + rename) and the log restarts
  empty.  Record application is idempotent by construction (the same
  first-writer-wins epoch rules as the live tracker), so
  replay(snapshot + tail) == replay(full log) even if a crash lands
  between the snapshot rename and the log truncate.

Fault points: ``cluster.journal.torn`` truncates the freshly appended
tail mid-record (a simulated crash inside the write syscall);
``cluster.journal.fsync.fail`` makes the fsync raise — the failure is
absorbed, counted (``journal_fsync_failures``), and the journal
degrades to flush-only rather than failing the query.

Dependency discipline: stdlib + obs.registry only (faults is injected
by the driver), and the module is imported ONLY by cluster-mode
drivers with the journal enabled — single-process sessions never load
it (tests/test_driver_recovery.py::test_journal_disabled_is_inert and
the journal case of
tests/test_telemetry.py::test_disabled_path_never_imports).
"""
from __future__ import annotations

import json
import os
import threading
import zlib

from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["ClusterJournal", "JournalState"]

LOG_NAME = "journal.log"
SNAPSHOT_NAME = "journal.snapshot"

#: composite map id stride (mirrors cluster/worker.py MAP_ID_STRIDE;
#: duplicated as a literal so this module stays import-light)
_STRIDE = 1_000_000


def _frame(rec: dict) -> bytes:
    payload = json.dumps(rec, separators=(",", ":"),
                         sort_keys=True).encode("utf-8")
    return b"%08x %s\n" % (zlib.crc32(payload) & 0xFFFFFFFF, payload)


def _parse(line: bytes) -> "dict | None":
    """One framed line -> record, or None when the frame is corrupt
    (bad CRC, bad json, missing separator)."""
    if not line.endswith(b"\n"):
        return None
    body = line[:-1]
    sep = body.find(b" ")
    if sep != 8:
        return None
    try:
        want = int(body[:8], 16)
    except ValueError:
        return None
    payload = body[9:]
    if zlib.crc32(payload) & 0xFFFFFFFF != want:
        return None
    try:
        rec = json.loads(payload)
    except ValueError:
        return None
    return rec if isinstance(rec, dict) else None


class JournalState:
    """The replayed journal: everything a recovering driver cannot
    recompute.  ``apply`` is idempotent — re-applying a record already
    folded in (a compaction race, a duplicated group-commit batch)
    changes nothing, which is what makes snapshot + tail replay exact.
    """

    def __init__(self):
        self.epoch = 0
        #: wid -> {"pid", "rpc", "shuffle", "status": alive|gone}
        self.workers: dict = {}
        #: sid -> {"fp", "num_parts", "ncpids", "conf_fp",
        #:         "addrs": {wid: [h, p]},
        #:         "entries": {(pid, mid): [wid, wslot, size, rows, epoch]},
        #:         "epochs": {mid: epoch}, "done": set(cpids)}
        self.shuffles: dict = {}
        #: job_id -> {"path", "fmt", "winners": {task: manifest},
        #:            "commit": {"renames", "manifest"} | None,
        #:            "committed", "aborted"}
        self.write_jobs: dict = {}
        #: records dropped at the torn/corrupt tail of the last replay
        self.truncated_records = 0

    # -- record application ---------------------------------------------
    def apply(self, rec: dict) -> None:
        k = rec.get("k")
        fn = getattr(self, f"_ap_{k}", None)
        if fn is not None:
            fn(rec)

    def _ap_driver_start(self, r):
        self.epoch = max(self.epoch, int(r.get("epoch", 0)))

    def _ap_worker_ready(self, r):
        self.workers[r["wid"]] = {
            "pid": r.get("pid"), "rpc": r.get("rpc"),
            "shuffle": r.get("shuffle"), "status": "alive"}

    def _ap_worker_gone(self, r):
        w = self.workers.get(r["wid"])
        if w is not None:
            w["status"] = "gone"

    def _ap_shuffle_open(self, r):
        sid = r["sid"]
        if sid not in self.shuffles:
            self.shuffles[sid] = {
                "fp": r.get("fp"), "num_parts": int(r.get("num_parts", 0)),
                "ncpids": int(r.get("ncpids", 0)),
                "conf_fp": r.get("conf_fp"), "addrs": {},
                "entries": {}, "epochs": {}, "done": set()}

    def _ap_map_register(self, r):
        st = self.shuffles.get(r["sid"])
        if st is None:
            return
        wid = r["wid"]
        st["addrs"][wid] = list(r.get("shuffle") or ())
        for mid, pid, wslot, size, rows, epoch in r.get("entries") or ():
            mid, pid, epoch = int(mid), int(pid), int(epoch)
            if epoch < st["epochs"].get(mid, 0):
                continue  # straggler from a pre-invalidation attempt
            old = st["entries"].get((pid, mid))
            if old is not None and epoch <= old[4]:
                continue  # first writer already committed
            st["epochs"][mid] = epoch
            st["entries"][(pid, mid)] = [wid, int(wslot), int(size),
                                         int(rows), epoch]

    def _ap_map_invalidate(self, r):
        st = self.shuffles.get(r["sid"])
        if st is None:
            return
        for mid, epoch in (r.get("epochs") or {}).items():
            mid, epoch = int(mid), int(epoch)
            if epoch < st["epochs"].get(mid, 0):
                continue
            st["epochs"][mid] = epoch
            for key in [key for key in st["entries"] if key[1] == mid]:
                del st["entries"][key]

    def _ap_frontier(self, r):
        st = self.shuffles.get(r["sid"])
        if st is not None:
            st["done"].update(int(c) for c in r.get("done") or ())

    def _ap_shuffle_close(self, r):
        self.shuffles.pop(r["sid"], None)

    def _ap_write_start(self, r):
        self.write_jobs.setdefault(r["job"], {
            "path": r.get("path"), "fmt": r.get("fmt"),
            "winners": {}, "commit": None,
            "committed": False, "aborted": False})

    def _ap_write_win(self, r):
        j = self.write_jobs.get(r["job"])
        if j is not None:
            j["winners"].setdefault(int(r["task"]), r.get("manifest"))

    def _ap_write_commit_begin(self, r):
        j = self.write_jobs.get(r["job"])
        if j is not None and j["commit"] is None:
            j["commit"] = {"renames": [list(p) for p in
                                       r.get("renames") or ()],
                           "manifest": r.get("manifest")}

    def _ap_write_commit_done(self, r):
        j = self.write_jobs.get(r["job"])
        if j is not None:
            j["committed"] = True

    def _ap_write_abort(self, r):
        j = self.write_jobs.get(r["job"])
        if j is not None:
            j["aborted"] = True

    # -- snapshot (de)serialization --------------------------------------
    def to_json(self) -> dict:
        shuffles = {}
        for sid, st in self.shuffles.items():
            shuffles[sid] = {
                "fp": st["fp"], "num_parts": st["num_parts"],
                "ncpids": st["ncpids"], "conf_fp": st["conf_fp"],
                "addrs": st["addrs"],
                "entries": [[pid, mid, *v]
                            for (pid, mid), v in st["entries"].items()],
                "epochs": {str(m): e for m, e in st["epochs"].items()},
                "done": sorted(st["done"])}
        # committed/aborted jobs carry no recovery obligation: drop them
        # at the compaction boundary so the snapshot stays bounded
        jobs = {job: j for job, j in self.write_jobs.items()
                if not (j["committed"] or j["aborted"])}
        return {"epoch": self.epoch, "workers": self.workers,
                "shuffles": shuffles,
                "write_jobs": {job: {**j, "winners": {
                    str(t): m for t, m in j["winners"].items()}}
                    for job, j in jobs.items()}}

    @classmethod
    def from_json(cls, doc: dict) -> "JournalState":
        st = cls()
        st.epoch = int(doc.get("epoch", 0))
        st.workers = dict(doc.get("workers") or {})
        for sid, s in (doc.get("shuffles") or {}).items():
            st.shuffles[sid] = {
                "fp": s.get("fp"), "num_parts": int(s.get("num_parts", 0)),
                "ncpids": int(s.get("ncpids", 0)),
                "conf_fp": s.get("conf_fp"),
                "addrs": dict(s.get("addrs") or {}),
                "entries": {(int(e[0]), int(e[1])):
                            [e[2], int(e[3]), int(e[4]), int(e[5]),
                             int(e[6])]
                            for e in s.get("entries") or ()},
                "epochs": {int(m): int(e) for m, e in
                           (s.get("epochs") or {}).items()},
                "done": set(int(c) for c in s.get("done") or ())}
        for job, j in (doc.get("write_jobs") or {}).items():
            st.write_jobs[job] = {
                "path": j.get("path"), "fmt": j.get("fmt"),
                "winners": {int(t): m for t, m in
                            (j.get("winners") or {}).items()},
                "commit": j.get("commit"),
                "committed": bool(j.get("committed")),
                "aborted": bool(j.get("aborted"))}
        return st

    # -- recovery views ---------------------------------------------------
    def shuffle_done_cpids(self, sid) -> set:
        """Child partitions of one shuffle whose dispatch the journal
        proves COMPLETE: in the journaled frontier AND every journaled
        map output of theirs still present (reconciliation may have
        dropped entries — those cpids must re-dispatch)."""
        st = self.shuffles.get(sid)
        if st is None:
            return set()
        have = {}
        for (pid, mid) in st["entries"]:
            have.setdefault(mid // _STRIDE, set()).add(mid)
        journaled = {}
        for mid in st["epochs"]:
            journaled.setdefault(mid // _STRIDE, set()).add(mid)
        out = set()
        for c in st["done"]:
            # a cpid with zero journaled maps produced no rows at all:
            # the frontier record alone proves it complete
            if journaled.get(c, set()) <= have.get(c, set()):
                out.add(c)
        return out


class ClusterJournal:
    """Append-side handle over one journal directory.  Thread-safe:
    dispatch threads, the tracker's registration path, and the write
    coordinator all append concurrently through the group-commit gate.
    """

    def __init__(self, journal_dir: str, max_bytes: int = 4 << 20,
                 faults=None):
        self.dir = journal_dir
        self.max_bytes = int(max_bytes)
        self._faults = faults
        os.makedirs(journal_dir, exist_ok=True)
        self._log_path = os.path.join(journal_dir, LOG_NAME)
        self._snap_path = os.path.join(journal_dir, SNAPSHOT_NAME)
        self.metrics = {"journal_appends": 0, "journal_fsyncs": 0,
                        "journal_group_commits": 0,
                        "journal_fsync_failures": 0,
                        "journal_snapshots": 0,
                        "journal_truncated_records": 0}
        self._heal_tail()
        self._fh = open(self._log_path, "ab")
        # group commit: _mu guards the buffer/sequence, _io the file.
        # The first appender through _io flushes EVERYTHING buffered so
        # far; appenders whose records it covered observe _durable and
        # return without touching the file.
        self._mu = threading.Lock()
        self._io = threading.Lock()
        self._buf: list[bytes] = []
        self._seq = 0
        self._durable = 0
        self._closed = False
        get_registry().register_object_source("cluster.journal", self)

    # -- append side ------------------------------------------------------
    def append(self, kind: str, **fields) -> None:
        self.append_many([{"k": kind, **fields}])

    def append_many(self, recs) -> None:
        """Durably append the records (one fsync covers every record
        buffered by the time the leader flushes — group commit)."""
        lines = [_frame(r) for r in recs]
        if not lines:
            return
        with self._mu:
            if self._closed:
                return
            self._buf.extend(lines)
            self._seq += len(lines)
            my = self._seq
            self.metrics["journal_appends"] += len(lines)
        while True:
            with self._mu:
                if self._durable >= my or self._closed:
                    return
            with self._io:
                with self._mu:
                    if self._durable >= my or self._closed:
                        return
                    buf, self._buf = self._buf, []
                    top = self._seq
                self._flush_locked(buf)
                with self._mu:
                    self._durable = max(self._durable, top)

    def _flush_locked(self, buf: list) -> None:
        """Write + fsync one group (caller holds ``_io``)."""
        data = b"".join(buf)
        self._fh.write(data)
        self._fh.flush()
        if self._faults is not None:
            act = self._faults.check("cluster.journal.torn")
            if act is not None:
                # a crash mid-write: keep only half of the last record
                # past the previously durable prefix, exactly the state
                # replay's torn-tail healing must absorb
                end = self._fh.tell()
                cut = end - max(1, len(buf[-1]) // 2)
                self._fh.truncate(cut)
                self._fh.seek(cut)
                get_registry().inc("cluster.journal.torn_injected")
        self.metrics["journal_group_commits"] += 1
        try:
            if self._faults is not None and \
                    self._faults.check("cluster.journal.fsync.fail") \
                    is not None:
                raise OSError("injected fault: cluster.journal.fsync.fail")
            os.fsync(self._fh.fileno())
            self.metrics["journal_fsyncs"] += 1
        except OSError:
            # a filesystem that cannot fsync journals at flush-only
            # durability rather than failing the query; the counter is
            # the operator's signal that crash recovery is weakened
            self.metrics["journal_fsync_failures"] += 1
            get_registry().inc("cluster.journal.fsync_failures")
        if self._fh.tell() > self.max_bytes:
            self._compact_locked()

    def _compact_locked(self) -> None:
        """Snapshot-compact under the size bound (caller holds ``_io``;
        the buffer may keep accruing meanwhile).  Crash-safe: the
        snapshot lands via tmp + fsync + rename BEFORE the log is
        truncated, and replay is idempotent, so a crash between the two
        replays snapshot + old log to the identical state."""
        state = self.replay(self.dir, count=False)
        tmp = self._snap_path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(_frame({"k": "snapshot", "state": state.to_json()}))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._snap_path)
        self._fh.truncate(0)
        self._fh.seek(0)
        self.metrics["journal_snapshots"] += 1
        get_registry().inc("cluster.journal.snapshots")

    def close(self) -> None:
        with self._mu:
            if self._closed:
                return
            buf, self._buf = self._buf, []
            self._closed = True
        with self._io:
            if buf:
                self._flush_locked(buf)
            try:
                self._fh.close()
            except OSError:
                pass
        get_registry().unregister_source("cluster.journal")

    # -- replay side ------------------------------------------------------
    def _heal_tail(self) -> None:
        """Truncate the log back to the end of its last INTACT record
        (a torn append, or a tail the torn fault cut mid-record).  Run
        before opening for append so new records never chain onto a
        corrupt line."""
        try:
            with open(self._log_path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            return
        good_end, dropped = _scan(raw)[1:]
        if good_end < len(raw):
            with open(self._log_path, "r+b") as f:
                f.truncate(good_end)
            self.metrics["journal_truncated_records"] += dropped
            get_registry().inc("cluster.journal.truncated_records",
                               dropped)

    @classmethod
    def replay(cls, journal_dir: str, count: bool = True) -> JournalState:
        """Rebuild the journaled state: snapshot first (when present),
        then every intact log record in order.  Replay STOPS at the
        first corrupt record — applying records past a corruption could
        interleave state from two torn writes — and the remainder is
        counted as truncated."""
        state = JournalState()
        snap_path = os.path.join(journal_dir, SNAPSHOT_NAME)
        try:
            with open(snap_path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            raw = b""
        if raw:
            recs, _, _ = _scan(raw)
            if recs and recs[0].get("k") == "snapshot":
                state = JournalState.from_json(recs[0].get("state") or {})
        try:
            with open(os.path.join(journal_dir, LOG_NAME), "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            raw = b""
        recs, _, dropped = _scan(raw)
        for rec in recs:
            state.apply(rec)
        state.truncated_records = dropped
        if dropped and count:
            get_registry().inc("cluster.journal.truncated_records",
                               dropped)
        return state


def _scan(raw: bytes):
    """Parse a framed byte stream -> (records, byte offset of the end
    of the last intact record, count of dropped trailing lines)."""
    recs: list[dict] = []
    pos = 0
    good_end = 0
    dropped = 0
    while pos < len(raw):
        nl = raw.find(b"\n", pos)
        if nl < 0:
            dropped += 1  # torn tail: no terminator
            break
        line = raw[pos:nl + 1]
        rec = _parse(line)
        if rec is None:
            # corrupt record: stop here — every complete line after it
            # is dropped too (replay must not skip-and-continue past a
            # corruption, order is the correctness contract)
            dropped += 1 + raw.count(b"\n", nl + 1)
            if not raw.endswith(b"\n"):
                dropped += 1
            break
        recs.append(rec)
        pos = nl + 1
        good_end = pos
    return recs, good_end, dropped
