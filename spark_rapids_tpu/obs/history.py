"""Persistent query history: the engine's Spark-history-server analog.

Every query that reaches a lifecycle terminal state appends ONE JSON
line to ``<dir>/query_history.jsonl`` — plan fingerprint, analyzed plan,
tenant, wall time, registry delta (counters + histogram movement),
cache/AQE decisions, and the failure taxonomy when it failed — so
post-hoc forensics ("what ran at 3am and why was p99 bad") survive the
process, the way the reference ecosystem leans on the Spark history
server + event log (PAPER.md §L3).

Durability/bounds: append is a single ``write()`` of one line on a
line-buffered handle under a lock; rotation past
``spark.rapids.obs.history.maxEntries`` keeps the newest entries by
rewriting to a temp file and ``os.replace`` (atomic on POSIX — readers
see the old or the new file, never a torn one).

Import discipline: the session gates on the raw conf string, so with
``spark.rapids.obs.history.dir`` unset this module is never imported
(tests/test_telemetry.py::test_disabled_path_never_imports).
``python -m tools.history`` reads the log with NO engine imports at all.
"""
from __future__ import annotations

import collections
import json
import os
import statistics
import threading
import time

from spark_rapids_tpu.conf import ConfEntry, register

__all__ = ["HISTORY_DIR", "HISTORY_MAX", "HistoryIndex", "QueryHistoryLog",
           "history_log", "read_entries", "read_history_tail",
           "HISTORY_FILE"]

HISTORY_DIR = register(ConfEntry(
    "spark.rapids.obs.history.dir", "",
    "When set, every query reaching a lifecycle terminal state appends "
    "one JSON line (plan fingerprint, analyzed plan, tenant, wall, "
    "registry delta, failure taxonomy) to <dir>/query_history.jsonl; "
    "inspect with `python -m tools.history`. Empty (default): no "
    "history, no overhead (the module is never imported)."))
HISTORY_MAX = register(ConfEntry(
    "spark.rapids.obs.history.maxEntries", 512,
    "History log rotation bound: once the log exceeds this many "
    "entries it is atomically rewritten keeping the newest ones.",
    conv=int))

HISTORY_FILE = "query_history.jsonl"


class QueryHistoryLog:
    """Append-only bounded JSONL log, safe for concurrent appenders in
    one process (lock) and for concurrent readers across processes
    (atomic rotation via ``os.replace``)."""

    def __init__(self, directory: str, max_entries: int = 512):
        self.dir = directory
        self.path = os.path.join(directory, HISTORY_FILE)
        self.max_entries = max(1, int(max_entries))
        self._lock = threading.Lock()
        self._count: int | None = None  # lazily counted on first append

    def _count_locked(self) -> int:
        if self._count is None:
            n = 0
            try:
                with open(self.path, "rb") as f:
                    for _ in f:
                        n += 1
            except FileNotFoundError:
                pass
            self._count = n
        return self._count

    def append(self, entry: dict) -> None:
        line = json.dumps(entry, sort_keys=True, default=str)
        with self._lock:
            os.makedirs(self.dir, exist_ok=True)
            self._count_locked()
            with open(self.path, "ab") as f:
                # a crash mid-append can leave a torn final line with no
                # newline; terminate it first so THIS entry stays parseable
                # (the reader already skips the torn fragment)
                if f.tell() > 0:
                    with open(self.path, "rb") as r:
                        r.seek(-1, os.SEEK_END)
                        if r.read(1) != b"\n":
                            f.write(b"\n")
                f.write(line.encode("utf-8") + b"\n")
                f.flush()
            self._count += 1
            if self._count > self.max_entries:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        with open(self.path, "r", encoding="utf-8") as f:
            lines = f.readlines()
        keep = lines[-self.max_entries:]
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.writelines(keep)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)
        self._count = len(keep)

    def entries(self, last: int | None = None) -> list[dict]:
        return read_entries(self.path, last=last)


def read_entries(path: str, last: int | None = None) -> list[dict]:
    """Parse the log, newest last; torn/garbage lines are skipped (a
    crash mid-append must not poison forensics of every other query).

    Rotation-tolerant: ``_rotate_locked`` swaps the file out with
    ``os.replace`` while readers may be mid-iteration.  The swap is
    atomic but a read that STRADDLES it returns a mix of a
    half-consumed old inode and nothing of the new one — so the inode
    is compared before and after the read, and a read whose file was
    replaced underneath it retries against the fresh file (bounded
    retries: under pathological rotation churn the last read wins,
    torn or not, rather than spinning)."""
    out: list[dict] = []
    for _attempt in range(4):
        try:
            st_before = os.stat(path)
        except OSError:
            return []
        out = []
        try:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            continue
        try:
            st_after = os.stat(path)
        except OSError:
            # rotated away (or the dir vanished) right after the read:
            # what was read is the newest complete view there was
            break
        if (st_after.st_ino, st_after.st_dev) == \
                (st_before.st_ino, st_before.st_dev):
            break
    return out if last is None else out[-last:]


def read_history_tail(directory: str, last: int = 16) -> list[dict]:
    """Bounded newest-entries summary for diag bundles: one compact
    dict per query, heavy fields (analyzed plan, registry delta)
    dropped."""
    tail = read_entries(os.path.join(directory, HISTORY_FILE), last=last)
    out = []
    for e in tail:
        out.append({k: e.get(k) for k in
                    ("query_id", "state", "tenant", "wall_s",
                     "submitted_unix_s", "plan_fingerprint", "error")
                    if e.get(k) is not None})
    return out


class HistoryIndex:
    """Bounded in-memory fingerprint → wall-time index over the
    history log, so plan routing is a dict lookup on the query path
    instead of a ``query_history.jsonl`` re-read per query.

    Two feeds: :meth:`note_entry` (the in-process fast path — the
    session indexes each entry as it appends it) and
    :meth:`refresh_from` (rebuild from the file when its identity
    changed — history written by OTHER processes sharing the
    directory, or a rotation).  ``refresh_from`` is stat-gated and
    rate-limited, and a rebuild REPLACES the index, so the two feeds
    never double-count an entry.  LRU-bounded on fingerprints and
    sample-bounded per fingerprint: a long-lived driver seeing
    unbounded distinct plans stays at a fixed footprint."""

    def __init__(self, max_fingerprints: int = 512,
                 max_samples: int = 32,
                 min_refresh_s: float = 1.0):
        self.max_fingerprints = max(1, int(max_fingerprints))
        self.max_samples = max(1, int(max_samples))
        self.min_refresh_s = float(min_refresh_s)
        self._lock = threading.Lock()
        # fp -> deque of (wall_s, mesh_devices, rows_processed,
        # device_seconds), LRU order; rows/device_s are 0 when the
        # entry predates the cost-attribution plane (PR 19)
        self._fps: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._file_id: "tuple | None" = None
        self._last_refresh: "float | None" = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._fps)

    def note_entry(self, entry: dict) -> None:
        """Index one history entry (only FINISHED runs teach the
        router — a failed or cancelled wall says nothing about the
        plan's true cost)."""
        with self._lock:
            self._note_locked(entry)

    def _note_locked(self, entry: dict) -> None:
        fp = entry.get("plan_fingerprint")
        if not fp or entry.get("state") != "FINISHED":
            return
        wall = entry.get("wall_s")
        if not isinstance(wall, (int, float)) or wall < 0:
            return
        try:
            mesh = int(entry.get("mesh_devices") or 1)
        except (TypeError, ValueError):
            mesh = 1
        try:
            rows = int(entry.get("rows_processed") or 0)
        except (TypeError, ValueError):
            rows = 0
        metering = entry.get("metering")
        try:
            dev = float((metering or {}).get("device_seconds") or 0.0)
        except (TypeError, ValueError):
            dev = 0.0
        dq = self._fps.get(fp)
        if dq is None:
            dq = self._fps[fp] = collections.deque(
                maxlen=self.max_samples)
        dq.append((float(wall), mesh, rows, dev))
        self._fps.move_to_end(fp)
        while len(self._fps) > self.max_fingerprints:
            self._fps.popitem(last=False)

    def refresh_from(self, path: str) -> bool:
        """Rebuild from the log file iff its identity (inode + size +
        mtime) moved since the last look, at most every
        ``min_refresh_s``.  Returns True when a rebuild happened."""
        now = time.monotonic()
        with self._lock:
            if self._last_refresh is not None and \
                    now - self._last_refresh < self.min_refresh_s:
                return False
            self._last_refresh = now
            try:
                st = os.stat(path)
                file_id = (st.st_ino, st.st_dev, st.st_size,
                           st.st_mtime_ns)
            except OSError:
                file_id = None
            if file_id == self._file_id:
                return False
            self._file_id = file_id
        entries = read_entries(path)  # outside the lock: file I/O
        with self._lock:
            self._fps.clear()
            for e in entries:
                self._note_locked(e)
        return True

    def lookup(self, fingerprint: str) -> "dict | None":
        """Observed-wall stats for one plan fingerprint, or None if it
        was never (successfully) seen: total samples, overall median
        wall, a per-mesh-shape breakdown, and — when the history
        carries cost-attribution data — median rows processed (the
        /queries progress denominator) and median metered
        device-seconds."""
        with self._lock:
            dq = self._fps.get(fingerprint)
            if not dq:
                return None
            self._fps.move_to_end(fingerprint)
            samples = list(dq)
        by_mesh: dict = {}
        for wall, mesh, _rows, _dev in samples:
            by_mesh.setdefault(mesh, []).append(wall)
        rows = [r for _w, _m, r, _d in samples if r > 0]
        devs = [d for _w, _m, _r, d in samples if d > 0]
        return {
            "samples": len(samples),
            "median_wall_s": statistics.median(
                w for w, _m, _r, _d in samples),
            "median_rows": statistics.median(rows) if rows else None,
            "median_device_s": statistics.median(devs) if devs else None,
            "by_mesh": {m: {"samples": len(ws),
                            "median_wall_s": statistics.median(ws)}
                        for m, ws in by_mesh.items()},
        }


_logs: dict[tuple[str, int], QueryHistoryLog] = {}
_logs_lock = threading.Lock()


def history_log(conf) -> "QueryHistoryLog | None":
    """Process-wide per-directory singleton (two sessions pointed at
    one dir share a lock and a rotation count)."""
    settings = getattr(conf, "settings", None) or {}
    d = HISTORY_DIR.get(settings)
    if not d:
        return None
    key = (os.path.abspath(d), HISTORY_MAX.get(settings))
    with _logs_lock:
        log = _logs.get(key)
        if log is None:
            log = _logs[key] = QueryHistoryLog(key[0], key[1])
        return log
