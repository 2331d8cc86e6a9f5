"""Driver crash recovery: write-ahead journal + worker re-attach +
resumable queries (spark_rapids_tpu/cluster/{journal,driver}.py).

Each scenario runs a REAL driver process (a python subprocess building
a local[2] session with the journal on), SIGKILLs it at a seeded
``cluster.driver.crash`` point — mid-dispatch, mid-shuffle-read,
mid-write-commit, during a drain — and then recovers in THIS process
with ``ClusterDriver.recover(conf, journal_dir)``: the journal
replays, the orphaned workers (lingering on
``driver.reattachGraceSeconds``) RECONNECT with their map-output
inventories, and the re-run query must return exactly the oracle rows.
The resumable-query contract is asserted through the registry: map
outputs the journal proved complete are claimed
(``cluster.map_outputs_resumed``), never recomputed
(``map_outputs_recomputed`` == 0).  Interrupted write commits roll
forward to exactly one ``_SUCCESS`` with zero ``_staging`` residue.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs.registry import get_registry

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("v", T.LongType(), True),
])


def _mkdata(n=400, seed=7):
    rng = np.random.default_rng(seed)
    return {"k": [int(x) for x in rng.integers(0, 13, n)],
            "v": [int(x) for x in rng.integers(-1000, 1000, n)]}


def _oracle():
    s = TpuSession()
    df = s.from_pydict(_mkdata(), SCHEMA, partitions=4, rows_per_batch=64)
    want = sorted(df.group_by("k").agg(Sum(col("v")).alias("sv"),
                                       CountStar().alias("c")).collect())
    s.shutdown()
    return want


def _base_conf(journal_dir: str, grace: float = 60.0) -> dict:
    return {
        "spark.rapids.cluster.mode": "local[2]",
        "spark.rapids.cluster.journal.dir": journal_dir,
        "spark.rapids.cluster.driver.reattachGraceSeconds": str(grace),
    }


#: the driver-under-test: builds a session from argv conf, runs the
#: same deterministic group-by the oracle uses, and (mode-dependent)
#: collects, writes parquet, or drains a worker.  The seeded
#: cluster.driver.crash fault SIGKILLs it somewhere in the middle.
_DRIVER_SCRIPT = r"""
import json, sys, time
import numpy as np
from spark_rapids_tpu import TpuSession
from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.aggregates import CountStar, Sum
from spark_rapids_tpu.expr.core import col

conf = json.loads(sys.argv[1])
mode = sys.argv[2]
SCHEMA = T.Schema([T.StructField("k", T.IntegerType(), True),
                   T.StructField("v", T.LongType(), True)])
rng = np.random.default_rng(7)
data = {"k": [int(x) for x in rng.integers(0, 13, 400)],
        "v": [int(x) for x in rng.integers(-1000, 1000, 400)]}
s = TpuSession(conf)
df = s.from_pydict(data, SCHEMA, partitions=4, rows_per_batch=64)
agg = df.group_by("k").agg(Sum(col("v")).alias("sv"),
                           CountStar().alias("c"))
if mode == "write":
    agg.write_parquet(sys.argv[3])
elif mode == "drain":
    agg.collect()                 # a full query journals + completes
    s._cluster().remove_worker("w0")
elif mode == "sleep":
    agg.collect()
    print("QUERY_DONE", flush=True)
    time.sleep(120)
else:
    agg.collect()
s.shutdown()
print("CLEAN_EXIT", flush=True)
"""


def _run_driver(conf: dict, mode: str, *extra,
                timeout: float = 120.0) -> subprocess.CompletedProcess:
    # stderr goes to a real FILE, never a pipe: the workers inherit the
    # driver's stderr, so a captured pipe would keep run() blocked on
    # EOF until every LINGERING worker exits — long after the SIGKILL
    # this harness is built to observe.  A file has no reader to block
    # on and still preserves the diagnostics.
    with tempfile.TemporaryFile(mode="w+") as ef:
        proc = subprocess.run(
            [sys.executable, "-c", _DRIVER_SCRIPT, json.dumps(conf),
             mode, *extra],
            stdout=subprocess.PIPE, stderr=ef, text=True,
            timeout=timeout)
        ef.seek(0)
        proc.stderr = ef.read()
    return proc


def _journal_worker_pids(journal_dir: str) -> list:
    from spark_rapids_tpu.cluster.journal import ClusterJournal
    state = ClusterJournal.replay(journal_dir)
    return [w["pid"] for w in state.workers.values()
            if w.get("status") == "alive" and w.get("pid")]


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


def _kill_stragglers(pids) -> None:
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def _recover_and_rerun(journal_dir: str, conf: dict):
    """The recovery half of every crash scenario: rebuild the driver
    from the journal, attach it to a fresh session, re-run the oracle
    query, and return (driver, rows, registry counter delta)."""
    from spark_rapids_tpu.cluster.driver import ClusterDriver
    from spark_rapids_tpu.conf import TpuConf
    driver = ClusterDriver.recover(TpuConf(conf), journal_dir)
    s = TpuSession(conf).attach_cluster(driver)
    try:
        df = s.from_pydict(_mkdata(), SCHEMA, partitions=4,
                           rows_per_batch=64)
        before = get_registry().snapshot()
        rows = sorted(df.group_by("k").agg(
            Sum(col("v")).alias("sv"),
            CountStar().alias("c")).collect())
        delta = get_registry().delta(before)["counters"]
        info = dict(driver.recovery_info or {})
        return rows, delta, info
    finally:
        s.shutdown()


def _crash_scenario(tmp_path, point: str, want):
    journal_dir = str(tmp_path / "journal")
    conf = _base_conf(journal_dir)
    crashed = _run_driver(
        {**conf,
         "spark.rapids.test.faults":
             f"cluster.driver.crash:kill,point={point}"}, "query")
    assert crashed.returncode == -signal.SIGKILL, \
        f"driver survived {point}: rc={crashed.returncode} " \
        f"stderr={crashed.stderr[-2000:]}"
    assert "CLEAN_EXIT" not in crashed.stdout
    pids = _journal_worker_pids(journal_dir)
    try:
        rows, delta, info = _recover_and_rerun(journal_dir, conf)
        assert rows == want
        assert info["epoch"] == 2
        assert info["workers_reattached"] == 2, info
        assert info["workers_replaced"] == 0, info
        # zero recompute of journaled-complete map outputs
        assert delta.get("map_outputs_recomputed", 0) == 0, delta
        # the recovered driver's shutdown reaps the RE-ATTACHED workers
        # too (no pipe to them — the shutdown RPC + kill must suffice)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline \
                and any(_pid_alive(p) for p in pids):
            time.sleep(0.2)
        orphans = [p for p in pids if _pid_alive(p)]
        assert not orphans, f"orphan workers after shutdown: {orphans}"
        return delta, info
    finally:
        _kill_stragglers(pids)


# ---------------------------------------------------------------------------
# the four crash points
# ---------------------------------------------------------------------------

def test_crash_mid_dispatch_recovers_exact(tmp_path):
    """SIGKILL at the top of the first dispatch round: nothing but the
    shuffle_open is journaled, so the resumed query recomputes the
    stage cleanly — exact rows, both workers re-attached, epoch 2."""
    delta, info = _crash_scenario(tmp_path, "dispatch", _oracle())
    assert delta.get("cluster.fragments_dispatched", 0) >= 1, delta


def test_crash_mid_shuffle_read_resumes_without_recompute(tmp_path):
    """SIGKILL on the first reduce-side fetch: the map stage was fully
    dispatched AND journaled, so the resumed query must CLAIM every
    journaled map output from the lingering workers — the dispatch
    frontier is empty and nothing recomputes."""
    delta, info = _crash_scenario(tmp_path, "shuffle_read", _oracle())
    assert delta.get("cluster.shuffles_resumed", 0) >= 1, delta
    assert delta.get("cluster.map_outputs_resumed", 0) >= 4, delta
    # the whole map stage came from the claim: no fragment re-ran for
    # the resumed shuffle (the counter stays 0 because the one shuffle
    # in this plan resumed wholesale)
    assert delta.get("cluster.fragments_dispatched", 0) == 0, delta


def test_crash_mid_write_commit_rolls_forward(tmp_path):
    """SIGKILL right after the first staged-file rename of a write
    commit.  The rename plan hit the journal BEFORE any rename ran, so
    recovery rolls the commit FORWARD: exactly one _SUCCESS, a full
    manifest, zero _staging residue, no double-commit."""
    journal_dir = str(tmp_path / "journal")
    out = str(tmp_path / "out")
    conf = _base_conf(journal_dir)
    crashed = _run_driver(
        {**conf,
         "spark.rapids.test.faults":
             "cluster.driver.crash:kill,point=write.commit"},
        "write", out)
    assert crashed.returncode == -signal.SIGKILL, crashed.stderr[-2000:]
    pids = _journal_worker_pids(journal_dir)
    try:
        assert not os.path.exists(os.path.join(out, "_SUCCESS"))
        from spark_rapids_tpu.cluster.driver import ClusterDriver
        from spark_rapids_tpu.conf import TpuConf
        driver = ClusterDriver.recover(TpuConf(conf), journal_dir)
        try:
            info = driver.recovery_info
            assert info["write_rollforward"] == 1, info
            assert info["write_rollback"] == 0, info
        finally:
            driver.shutdown()
        success = [f for f in os.listdir(out) if f == "_SUCCESS"]
        assert len(success) == 1
        assert not os.path.exists(os.path.join(out, "_staging"))
        assert os.path.exists(os.path.join(out, "_MANIFEST.json"))
        # the rolled-forward directory serves the exact oracle rows
        s = TpuSession()
        got = sorted(tuple(r) for r in s.read_parquet(out).collect())
        s.shutdown()
        want = sorted(tuple(r) for r in _oracle())
        assert got == want
    finally:
        _kill_stragglers(pids)


def test_crash_during_drain_recovers_membership(tmp_path):
    """SIGKILL inside remove_worker after the drain fence went up: the
    half-drained worker was never told to exit, so BOTH workers linger
    and re-attach; the resumed cluster serves the query exactly."""
    journal_dir = str(tmp_path / "journal")
    conf = _base_conf(journal_dir)
    crashed = _run_driver(
        {**conf,
         "spark.rapids.test.faults":
             "cluster.driver.crash:kill,point=drain"}, "drain")
    assert crashed.returncode == -signal.SIGKILL, crashed.stderr[-2000:]
    pids = _journal_worker_pids(journal_dir)
    try:
        rows, delta, info = _recover_and_rerun(journal_dir, conf)
        assert rows == _oracle()
        assert info["workers_reattached"] == 2, info
        assert delta.get("map_outputs_recomputed", 0) == 0, delta
    finally:
        _kill_stragglers(pids)


# ---------------------------------------------------------------------------
# linger semantics
# ---------------------------------------------------------------------------

def test_linger_expiry_self_terminates(tmp_path):
    """With a short grace, orphaned workers serve their shuffle outputs
    for the window and then exit on their own — no daemon leak when no
    driver ever comes back."""
    journal_dir = str(tmp_path / "journal")
    conf = _base_conf(journal_dir, grace=2.0)
    proc = subprocess.Popen(
        [sys.executable, "-c", _DRIVER_SCRIPT, json.dumps(conf),
         "sleep"], stdout=subprocess.PIPE, text=True)
    try:
        line = ""
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if "QUERY_DONE" in line:
                break
        assert "QUERY_DONE" in line
        pids = _journal_worker_pids(journal_dir)
        assert len(pids) == 2 and all(_pid_alive(p) for p in pids)
        proc.kill()
        proc.wait(timeout=10)
        # workers notice the gone driver (stdin EOF), linger ~2s, exit
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline \
                and any(_pid_alive(p) for p in pids):
            time.sleep(0.2)
        leftovers = [p for p in pids if _pid_alive(p)]
        _kill_stragglers(leftovers)
        assert not leftovers, f"workers outlived linger: {leftovers}"
    finally:
        if proc.poll() is None:
            proc.kill()
        _kill_stragglers(_journal_worker_pids(journal_dir))


def test_zero_grace_workers_exit_with_driver(tmp_path):
    """reattachGraceSeconds=0 (the default) keeps the legacy contract:
    driver death takes the workers down immediately — no linger."""
    journal_dir = str(tmp_path / "journal")
    conf = _base_conf(journal_dir, grace=0.0)
    proc = subprocess.Popen(
        [sys.executable, "-c", _DRIVER_SCRIPT, json.dumps(conf),
         "sleep"], stdout=subprocess.PIPE, text=True)
    try:
        line = ""
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if "QUERY_DONE" in line:
                break
        assert "QUERY_DONE" in line
        pids = _journal_worker_pids(journal_dir)
        proc.kill()
        proc.wait(timeout=10)
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline \
                and any(_pid_alive(p) for p in pids):
            time.sleep(0.2)
        leftovers = [p for p in pids if _pid_alive(p)]
        _kill_stragglers(leftovers)
        assert not leftovers
    finally:
        if proc.poll() is None:
            proc.kill()
        _kill_stragglers(_journal_worker_pids(journal_dir))


# ---------------------------------------------------------------------------
# shutdown vs monitor-thread race (regression)
# ---------------------------------------------------------------------------

def test_shutdown_gates_late_death_verdicts():
    """A death verdict landing DURING shutdown must not start output
    migration against a worker the shutdown is already retiring: after
    shutdown, mark_worker_lost is a no-op, record_worker_failure
    tolerates, and remove_worker refuses outright."""
    from spark_rapids_tpu.cluster.driver import ClusterDriver
    from spark_rapids_tpu.conf import TpuConf
    driver = ClusterDriver(TpuConf(
        {"spark.rapids.cluster.mode": "local[1]",
         "spark.rapids.cluster.journal.enabled": "false"}))
    wid = driver.workers()[0].worker_id
    driver.shutdown()
    before = get_registry().snapshot()
    driver.mark_worker_lost(wid, "late verdict")
    assert driver.record_worker_failure(wid, "late verdict") == "tolerated"
    with pytest.raises(RuntimeError, match="shut down"):
        driver.remove_worker(wid)
    d = get_registry().delta(before)["counters"]
    assert d.get("cluster_workers_lost", 0) == 0, d
    assert d.get("map_outputs_migrated", 0) == 0, d


# ---------------------------------------------------------------------------
# recovery preconditions
# ---------------------------------------------------------------------------

def test_journal_disabled_is_inert(tmp_path, monkeypatch):
    """journal.enabled=false: a live driver never imports
    cluster/journal.py, never touches journal.dir, and plans the same
    (the default-conf half is
    tests/test_telemetry.py::test_disabled_path_never_imports[journal])."""
    from spark_rapids_tpu.cluster.driver import ClusterDriver
    from spark_rapids_tpu.conf import TpuConf
    monkeypatch.delitem(sys.modules, "spark_rapids_tpu.cluster.journal",
                        raising=False)
    jdir = tmp_path / "never-touched"
    off = {"spark.rapids.cluster.mode": "local[1]",
           "spark.rapids.cluster.journal.enabled": "false",
           "spark.rapids.cluster.journal.dir": str(jdir)}
    driver = ClusterDriver(TpuConf(off))

    def plan(conf):
        s = TpuSession(conf).attach_cluster(driver)
        df = s.from_pydict(_mkdata(), SCHEMA, partitions=4,
                           rows_per_batch=64)
        return df.group_by("k").agg(Sum(col("v")).alias("sv")).explain()
    try:
        assert driver.journal is None
        on = dict(off, **{"spark.rapids.cluster.journal.enabled": "true"})
        assert plan(off) == plan(on), "journal changed the plan"
        assert "spark_rapids_tpu.cluster.journal" not in sys.modules
        assert not jdir.exists(), "disabled journal still did I/O"
    finally:
        driver.shutdown()


def test_recover_requires_journal_dir():
    from spark_rapids_tpu.cluster.driver import ClusterDriver
    from spark_rapids_tpu.conf import TpuConf
    with pytest.raises(ValueError, match="journal"):
        ClusterDriver.recover(TpuConf(
            {"spark.rapids.cluster.mode": "local[2]"}))


def test_recover_replaces_dead_workers(tmp_path):
    """Recovery with NO surviving workers (grace 0: they died with the
    driver) spawns a fresh pool — workers_replaced == N, and queries
    run; the journaled map outputs reconcile away instead of wedging
    the claim path."""
    journal_dir = str(tmp_path / "journal")
    conf = _base_conf(journal_dir, grace=0.0)
    crashed = _run_driver(
        {**conf,
         "spark.rapids.test.faults":
             "cluster.driver.crash:kill,point=shuffle_read"}, "query")
    assert crashed.returncode == -signal.SIGKILL, crashed.stderr[-2000:]
    pids = _journal_worker_pids(journal_dir)
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline \
            and any(_pid_alive(p) for p in pids):
        time.sleep(0.2)
    _kill_stragglers(pids)
    rows, delta, info = _recover_and_rerun(journal_dir, conf)
    assert rows == _oracle()
    assert info["workers_reattached"] == 0, info
    assert info["workers_replaced"] == 2, info
    # nothing survived to claim; the journaled entries were dropped by
    # reconciliation and the stage recomputed from scratch
    assert info["entries_dropped"] >= 1, info
    assert delta.get("cluster.map_outputs_resumed", 0) == 0, delta
