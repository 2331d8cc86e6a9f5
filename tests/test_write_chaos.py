"""Transactional write-plane chaos matrix: exactly-once partitioned
parquet commits under seeded faults (io/writer.py, exec/write_exec.py,
cluster/exec.py dispatch_write_fragments).

Every case asserts EXACT rows on read-back (pyarrow dataset with hive
partition inference — an oracle independent of the engine's scan) and
the zero-orphans invariant: after a committed job, every visible file
in the output directory is listed in ``_MANIFEST.json`` and no
``_staging`` tree remains.  Chaos cases additionally prove the fault
actually fired (``faults.injected.*`` delta > 0) so a renamed injection
point can never turn a case vacuous.

Storms covered: task death mid-write (``io.write.partial`` crash and
truncate actions), commit-message loss (``io.write.commit.drop``),
rename failure with retry and with exhaustion -> rollback
(``io.write.rename.fail``), OOM split-retry inside the write fragment
(``memory.oom``), cluster worker death mid-write
(``cluster.worker.dead``), duplicate speculative attempts, and a
graceful drain during the write (fencing).  Reference intent: Spark's
HadoopMapReduceCommitProtocol + OutputCommitCoordinator keep
speculative/failed task attempts from ever publishing partial output.
"""
import glob
import json
import os

import numpy as np
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("cat", T.StringType(), True),
    T.StructField("v", T.DoubleType(), True),
])


def _mkdata(n=2000, seed=7):
    rng = np.random.default_rng(seed)
    cats = ["a", "b", "c", "d"]
    return {"k": [int(x) for x in rng.integers(0, 10000, n)],
            "cat": [cats[i] for i in rng.integers(0, 4, n)],
            "v": [float(i) for i in range(n)]}


def _expected(data):
    return sorted(zip(data["k"], data["cat"], data["v"]), key=str)


def _readback(out):
    """Oracle read-back: pyarrow dataset with hive partition inference
    (default ignore_prefixes skips ``_``/``.`` paths, like the engine)."""
    import pyarrow.dataset as ds
    t = ds.dataset(out, format="parquet", partitioning="hive").to_table()
    cols = {n: t.column(n).to_pylist() for n in t.column_names}
    return sorted(zip(cols["k"], cols["cat"], cols["v"]), key=str)


def _assert_no_orphans(out):
    """Zero-orphans invariant: visible files == committed manifest,
    no staging tree left behind."""
    with open(os.path.join(out, "_MANIFEST.json")) as f:
        man = json.load(f)
    committed = {os.path.normpath(e["rel"]) for e in man["files"]}
    visible = set()
    for root, dirs, files in os.walk(out):
        dirs[:] = [d for d in dirs if not d.startswith(("_", "."))]
        for fn in files:
            if fn.startswith(("_", ".")):
                continue
            visible.add(os.path.normpath(
                os.path.relpath(os.path.join(root, fn), out)))
    assert visible == committed, \
        f"orphans: {visible - committed}, missing: {committed - visible}"
    assert not os.path.exists(os.path.join(out, "_staging")), \
        "staging tree survived a committed job"


def _write(s, data, out, partition_by=("cat",), partitions=4):
    df = s.from_pydict(data, SCHEMA, partitions=partitions,
                       rows_per_batch=256)
    return df.write_parquet(out, partition_by=list(partition_by))


# ---------------------------------------------------------------------------
# case 1: clean CTAS — baseline control for the whole matrix
# ---------------------------------------------------------------------------

def test_clean_ctas_exact_and_no_orphans(tmp_path):
    data = _mkdata()
    s = TpuSession({})
    out = str(tmp_path / "clean")
    stats = _write(s, data, out)
    assert stats.num_rows == len(data["k"])
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)
    # full CRC read-back verification against the committed manifest
    from spark_rapids_tpu.io.writer import verify_manifest
    man = verify_manifest(out, full=True)
    assert man["num_rows"] == len(data["k"])


# ---------------------------------------------------------------------------
# case 2/3: task attempt dies mid-write (crash / truncated file)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("action", ["crash", "truncate"])
def test_partial_write_retries_exact(tmp_path, action):
    data = _mkdata()
    s = TpuSession({"spark.rapids.test.faults":
                    f"io.write.partial:{action},times=1"})
    out = str(tmp_path / f"partial_{action}")
    before = get_registry().snapshot()
    _write(s, data, out)
    d = get_registry().delta(before)["counters"]
    assert d.get("faults.injected.io.write.partial", 0) == 1, d
    assert d.get("write.task_attempt_failures", 0) >= 1, d
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 4: the attempt's commit message never reaches the coordinator
# ---------------------------------------------------------------------------

def test_commit_message_drop_reattempts(tmp_path):
    data = _mkdata()
    s = TpuSession({"spark.rapids.test.faults":
                    "io.write.commit.drop:drop,times=1"})
    out = str(tmp_path / "drop")
    before = get_registry().snapshot()
    _write(s, data, out)
    d = get_registry().delta(before)["counters"]
    assert d.get("faults.injected.io.write.commit.drop", 0) == 1, d
    assert d.get("write.commit_msgs_dropped", 0) == 1, d
    # the task re-attempted and the retry won
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 5: transient rename failure — retried inside the commit
# ---------------------------------------------------------------------------

def test_rename_failure_retry_succeeds(tmp_path):
    data = _mkdata()
    s = TpuSession({"spark.rapids.test.faults":
                    "io.write.rename.fail:fail,times=1"})
    out = str(tmp_path / "renametry")
    before = get_registry().snapshot()
    _write(s, data, out)
    d = get_registry().delta(before)["counters"]
    assert d.get("faults.injected.io.write.rename.fail", 0) == 1, d
    assert d.get("write.rename_retries", 0) >= 1, d
    assert d.get("write.jobs_committed", 0) == 1, d
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 6: rename failure exhausts retries — full rollback, then a clean
# rerun over the SAME directory succeeds
# ---------------------------------------------------------------------------

def test_rename_exhaustion_rolls_back_then_clean_rerun(tmp_path):
    data = _mkdata()
    out = str(tmp_path / "rollback")
    s = TpuSession({"spark.rapids.test.faults":
                    "io.write.rename.fail:fail,times=0"})
    before = get_registry().snapshot()
    with pytest.raises(Exception, match="rename"):
        _write(s, data, out)
    d = get_registry().delta(before)["counters"]
    assert d.get("faults.injected.io.write.rename.fail", 0) >= 1, d
    assert d.get("write.jobs_commit_failed", 0) == 1, d
    assert d.get("write.jobs_aborted", 0) == 1, d
    # the directory is observed UNTOUCHED: no data files, no success
    # marker, no manifest, no staging leftovers (abort removed them)
    assert glob.glob(os.path.join(out, "**", "*.parquet"),
                     recursive=True) == []
    assert not os.path.exists(os.path.join(out, "_SUCCESS"))
    assert not os.path.exists(os.path.join(out, "_MANIFEST.json"))
    assert not os.path.exists(os.path.join(out, "_staging"))
    # a later clean job over the same path commits exactly
    s2 = TpuSession({})
    _write(s2, data, out)
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 7: OOM split-retry INSIDE the write fragment — the memory
# machinery splits and retries, the attempt still commits exactly once
# ---------------------------------------------------------------------------

def test_oom_split_retry_inside_write_fragment(tmp_path):
    data = _mkdata()
    s = TpuSession({"spark.rapids.test.faults":
                    "memory.oom:oom,op=agg_flush,times=1",
                    "spark.sql.shuffle.partitions": 4})
    df = s.from_pydict(data, SCHEMA, partitions=4, rows_per_batch=256)
    agg = df.group_by("cat").agg(Sum(col("v")).alias("sv"))
    want = sorted(agg.collect(), key=str)
    out = str(tmp_path / "oomwrite")
    before = get_registry().snapshot()
    agg.write_parquet(out)
    d = get_registry().delta(before)["counters"]
    assert d.get("faults.injected.memory.oom", 0) >= 1, d
    assert d.get("write.jobs_committed", 0) == 1, d
    import pyarrow.parquet as pq
    t = pq.read_table(out)
    got = sorted(zip(t.column("cat").to_pylist(),
                     t.column("sv").to_pylist()), key=str)
    assert got == want
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 8: crashed jobs leave only garbage-collectable staging — the
# next job's GC sweeps them
# ---------------------------------------------------------------------------

def test_stale_staging_gc_on_next_job(tmp_path):
    from spark_rapids_tpu.io.writer import staging_attempt_dir
    data = _mkdata()
    out = str(tmp_path / "gc")
    # plant a crashed job's leftover attempt dir (what a dead driver or
    # killed worker leaves behind: `_`-prefixed, invisible to scans)
    stale = staging_attempt_dir(out, "deadjob0", 0, 0)
    os.makedirs(stale)
    with open(os.path.join(stale, "part-00000-deadjob0-a00.parquet"),
              "wb") as f:
        f.write(b"partial")
    s = TpuSession({})
    before = get_registry().snapshot()
    _write(s, data, out)
    d = get_registry().delta(before)["counters"]
    assert d.get("write.staging_dirs_gced", 0) >= 1, d
    assert _readback(out) == _expected(data)
    _assert_no_orphans(out)


# ---------------------------------------------------------------------------
# case 9: duplicate attempts of one write task — exactly one committed
# manifest (the attempt-id satellite's regression)
# ---------------------------------------------------------------------------

def test_duplicate_attempt_single_commit(tmp_path):
    from spark_rapids_tpu.io.writer import WriteCommitCoordinator
    coord = WriteCommitCoordinator(str(tmp_path / "dup"), "parquet")
    a0 = coord.next_attempt(3)
    a1 = coord.next_attempt(3)
    assert (a0, a1) == (0, 1), "attempt ids must be distinguishable"
    before = get_registry().snapshot()
    won0 = coord.register({"task": 3, "attempt": a0, "worker": "w0",
                           "files": [], "partitions": []})
    won1 = coord.register({"task": 3, "attempt": a1, "worker": "w1",
                           "files": [], "partitions": []})
    assert won0 and not won1, "first writer must win, duplicate discarded"
    assert coord.winner(3)["attempt"] == a0
    d = get_registry().delta(before)["counters"]
    assert d.get("write.attempts_won", 0) == 1, d
    assert d.get("write.attempts_discarded", 0) == 1, d


# ---------------------------------------------------------------------------
# case 10: cluster worker killed mid-write — surviving worker re-runs
# the tasks, commit stays exact
# ---------------------------------------------------------------------------

def test_cluster_worker_death_mid_write(tmp_path):
    data = _mkdata(8000)
    s = TpuSession({
        "spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.cluster.heartbeat.intervalSeconds": "0.2",
        "spark.rapids.test.faults":
            "cluster.worker.dead:dead,worker=w1,seconds=0.02,times=1",
    })
    try:
        out = str(tmp_path / "wdead")
        before = get_registry().snapshot()
        _write(s, data, out, partitions=4)
        d = get_registry().delta(before)["counters"]
        assert d.get("faults.injected.cluster.worker.dead", 0) == 1, d
        assert d.get("cluster.write_fragments_dispatched", 0) >= 1, d
        assert d.get("write.jobs_committed", 0) == 1, d
        assert _readback(out) == _expected(data)
        _assert_no_orphans(out)
    finally:
        s.shutdown(drain=True)


# ---------------------------------------------------------------------------
# case 11: straggler speculation during the write — the duplicate
# attempt's manifests are discarded, exactly one winner per task
# ---------------------------------------------------------------------------

_STALL_S = 1234.5


def _hold_slow_worker_until_speculated(monkeypatch):
    """Make the ``cluster.worker.slow`` stall (the one sleep of
    ``_STALL_S``) last until the speculative copy has been launched,
    not for a length of time: whether 2 s outlasts
    ``multiplier`` x the median fragment depends on how loaded the host
    is, and on a loaded one the straggler used to wake first."""
    import time
    import types

    import spark_rapids_tpu.cluster.exec as cexec
    base = get_registry().snapshot()

    def sleep(seconds):
        if seconds != _STALL_S:
            return time.sleep(seconds)
        deadline = time.monotonic() + 120.0
        while not get_registry().delta(base)["counters"].get(
                "speculative_launched") and time.monotonic() < deadline:
            time.sleep(0.02)

    monkeypatch.setattr(cexec, "time", types.SimpleNamespace(
        **{**vars(time), "sleep": sleep}))


def test_speculative_duplicate_write_exact(tmp_path, monkeypatch):
    data = _mkdata(8000)
    _hold_slow_worker_until_speculated(monkeypatch)
    s = TpuSession({
        "spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.cluster.speculation.enabled": "true",
        "spark.rapids.cluster.speculation.multiplier": "2.0",
        "spark.rapids.cluster.speculation.minRuntimeSeconds": "0.2",
        "spark.rapids.test.faults":
            f"cluster.worker.slow:slow,seconds={_STALL_S},worker=w1,"
            "times=1",
    })
    try:
        out = str(tmp_path / "spec")
        before = get_registry().snapshot()
        _write(s, data, out, partitions=4)
        d = get_registry().delta(before)["counters"]
        assert d.get("faults.injected.cluster.worker.slow", 0) == 1, d
        assert d.get("speculative_launched", 0) >= 1, d
        # exactly one winning manifest per task: 4 child partitions
        assert d.get("write.attempts_won", 0) == 4, d
        assert _readback(out) == _expected(data)
        _assert_no_orphans(out)
    finally:
        s.shutdown(drain=True)


# ---------------------------------------------------------------------------
# case 12: graceful drain DURING the write — the drained worker is
# fenced out of the commit, survivors finish the job
# ---------------------------------------------------------------------------

def test_drain_during_write_fences_and_completes(tmp_path, monkeypatch):
    import spark_rapids_tpu.io.writer as writer
    data = _mkdata(8000)
    s = TpuSession({
        "spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.cluster.heartbeat.intervalSeconds": "0.2",
    })
    try:
        drv = s._cluster()
        fired: dict = {}
        orig = writer.WriteCommitCoordinator.register

        def hooked(self, manifest):
            # retire w1 synchronously at its FIRST commit registration:
            # the drain fences w1 in this coordinator, so this very
            # manifest must be rejected and the task re-dispatched
            if manifest.get("worker") == "w1" and not fired:
                fired["ok"] = True
                fired.update(drv.remove_worker("w1", drain=True))
            return orig(self, manifest)

        monkeypatch.setattr(writer.WriteCommitCoordinator, "register",
                            hooked)
        out = str(tmp_path / "drain")
        before = get_registry().snapshot()
        _write(s, data, out, partitions=4)
        assert fired.get("ok"), "drain never triggered mid-write"
        d = get_registry().delta(before)["counters"]
        assert d.get("cluster_workers_drained", 0) == 1, d
        assert d.get("write.attempts_fenced", 0) >= 1, d
        assert d.get("write.jobs_committed", 0) == 1, d
        assert _readback(out) == _expected(data)
        _assert_no_orphans(out)
        h = drv.worker_by_id("w1")
        assert h.retired and not h.alive
    finally:
        s.shutdown(drain=True)


# ---------------------------------------------------------------------------
# case 13: CTAS-then-read — a committed write invalidates result-cache
# entries that scanned the replaced files (stale hits are impossible)
# ---------------------------------------------------------------------------

def test_ctas_then_read_invalidates_result_cache(tmp_path):
    data1 = _mkdata(500, seed=1)
    data2 = _mkdata(500, seed=2)
    s = TpuSession({})
    out = str(tmp_path / "cachedir")
    _write(s, data1, out, partition_by=())
    first = sorted(s.read_parquet(out).collect(), key=str)
    assert len(first) == 500
    # read again so the result cache demonstrably holds the entry
    assert sorted(s.read_parquet(out).collect(), key=str) == first
    before = get_registry().snapshot()
    _write(s, data2, out, partition_by=())
    d = get_registry().delta(before)["counters"]
    assert d.get("result_cache_invalidated", 0) >= 1, d
    # fresh read sees the newly committed rows, never the stale cache
    again = sorted(s.read_parquet(out).collect(), key=str)
    k2 = set(data2["k"])
    assert any(r[0] in k2 for r in again)
    assert len(again) > len(first)  # append semantics: both jobs visible


# ---------------------------------------------------------------------------
# case 14: verifyCrcOnScan — post-commit corruption is detected at scan
# time instead of served as silently wrong rows
# ---------------------------------------------------------------------------

def test_verify_crc_on_scan_detects_corruption(tmp_path):
    from spark_rapids_tpu.io.writer import WriteIntegrityError
    data = _mkdata(500)
    s = TpuSession({"spark.rapids.io.write.verifyCrcOnScan": "true"})
    out = str(tmp_path / "crc")
    _write(s, data, out, partition_by=())
    assert sorted(s.read_parquet(out).collect(), key=str) == \
        sorted(zip(data["k"], data["cat"], data["v"]), key=str)
    # flip one byte of a committed file behind the manifest's back
    victim = glob.glob(os.path.join(out, "*.parquet"))[0]
    with open(victim, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(WriteIntegrityError, match="CRC32"):
        s.read_parquet(out).collect()
