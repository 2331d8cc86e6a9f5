"""Mesh-parallel exchange + distributed aggregation vs CPU oracle.

Mirrors the reference's transport-mock strategy (RapidsShuffleClientSuite:
protocol correctness without a network): here the 8-device CPU mesh stands
in for a TPU slice and results are checked against the single-threaded
host oracle.  The programs under test are the engine's own: a bare
``MeshExchangeExec`` / ``MeshAggregateExec`` over the shards, launched
through the one ``MeshLauncher`` (exec/mesh_exec.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.basic import LocalScanExec
from spark_rapids_tpu.exec.core import ExecCtx
from spark_rapids_tpu.exec.mesh_exec import (MeshAggregateExec,
                                             MeshExchangeExec)
from spark_rapids_tpu.expr.aggregates import Count, CountStar, Max, Min, Sum
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.host.batch import HostBatch
from spark_rapids_tpu.parallel.mesh_shuffle import partition_ids_for_keys

SCHEMA = T.Schema([
    T.StructField("k", T.IntegerType(), True),
    T.StructField("v", T.LongType(), True),
    T.StructField("f", T.DoubleType(), True),
])


def _make_shards(rng, p=8, n_per=50, nkeys=13):
    shards_h = []
    for _ in range(p):
        k = rng.integers(0, nkeys, n_per).astype(np.int32)
        v = rng.integers(-100, 100, n_per).astype(np.int64)
        f = rng.normal(size=n_per)
        kv = np.ones(n_per, bool)
        kv[rng.integers(0, n_per, 3)] = False  # some null keys
        hb = HostBatch.from_pydict(
            {"k": np.where(kv, k, 0), "v": v, "f": f}, SCHEMA)
        hb.columns[0].validity[:] = kv
        shards_h.append(hb)
    return shards_h


def _launched(node, ctx):
    """One batch per device: the outputs a bare terminal's launcher
    leaves under the terminal's cache key (the exchange's are tagged
    ``"mesh"``: no slice was lost, nothing degraded to host)."""
    out = node._outputs(ctx)
    if isinstance(node, MeshExchangeExec):
        kind, shards = out
        assert kind == "mesh"
        return list(shards)
    return [b for per_dev in out for b in per_dev]


def test_hash_exchange_routes_all_rows(rng):
    p = 8
    shards_h = _make_shards(rng, p=p)
    ex = MeshExchangeExec([col("k")], LocalScanExec(shards_h, SCHEMA, p), p)
    with ExecCtx(backend="device") as ctx:
        outs = _launched(ex, ctx)
    assert len(outs) == p
    total_in = sum(b.num_rows for b in shards_h)
    total_out = sum(b.host_num_rows() for b in outs)
    assert total_out == total_in
    # every row of one key lands on exactly one device, and the partition
    # choice matches the host-side murmur3 pmod
    def rk(r):
        return tuple((x is None, x) for x in r)
    all_in_rows = sorted(
        (r for hb in shards_h for r in hb.to_rows()), key=rk)
    all_out_rows = sorted(
        (r for b in outs for r in HostBatch.from_device(b).to_rows()), key=rk)
    assert all_in_rows == all_out_rows
    for d, b in enumerate(outs):
        hb = HostBatch.from_device(b)
        n = hb.num_rows
        if n == 0:
            continue
        pid = np.asarray(jax.device_get(
            partition_ids_for_keys(b, [0], p)))[:n]
        assert (pid == d).all()


def test_distributed_groupby_matches_oracle(rng):
    p = 8
    shards_h = _make_shards(rng, p=p)
    gb = MeshAggregateExec(
        [col("k")],
        [col("k"), Sum(col("v")).alias("sv"), Count(col("f")).alias("cf"),
         Min(col("v")).alias("mv"), Max(col("f")).alias("xf")],
        LocalScanExec(shards_h, SCHEMA, p), p)
    with ExecCtx(backend="device") as ctx:
        got = sorted(
            (r for b in _launched(gb, ctx)
             for r in HostBatch.from_device(b).to_rows()),
            key=lambda r: (r[0] is None, r[0]))

    # oracle: single-host groupby over the concatenated shards
    big = HostBatch.concat(shards_h)
    import collections
    acc = collections.defaultdict(lambda: [0, False, 0, None, None])
    ks = big.columns[0]
    vs = big.columns[1]
    fs = big.columns[2]
    for i in range(big.num_rows):
        key = int(ks.data[i]) if ks.validity[i] else None
        a = acc[key]
        if vs.validity[i]:
            a[0] += int(vs.data[i]); a[1] = True
            a[3] = int(vs.data[i]) if a[3] is None else min(a[3], int(vs.data[i]))
        if fs.validity[i]:
            a[2] += 1
            a[4] = float(fs.data[i]) if a[4] is None else max(a[4], float(fs.data[i]))
    want = sorted(((k, a[0] if a[1] else None, a[2], a[3], a[4])
                   for k, a in acc.items()),
                  key=lambda r: (r[0] is None, r[0]))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[1] == w[1] and g[2] == w[2] and g[3] == w[3]
        assert g[4] == pytest.approx(w[4])


def test_distributed_grand_aggregate(rng):
    p = 8
    shards_h = _make_shards(rng, p=p)
    gb = MeshAggregateExec(
        [], [Sum(col("v")).alias("sv"), CountStar().alias("n")],
        LocalScanExec(shards_h, SCHEMA, p), p)
    with ExecCtx(backend="device") as ctx:
        rows = [r for b in _launched(gb, ctx)
                for r in HostBatch.from_device(b).to_rows()]
    assert len(rows) == 1
    big = HostBatch.concat(shards_h)
    vs = big.columns[1]
    assert rows[0][0] == int(vs.data[vs.validity].sum())
    assert rows[0][1] == big.num_rows
