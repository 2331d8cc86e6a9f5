"""Micro-run for the chip (PR 44): the pieces of a mesh region's join body at
the mesh cell's per-device shape (TPC-DS q6 at SF1 over four chips: a
2^20-slot shard of ``store_sales`` holding 720k rows), each as a program of
its own on ONE chip: the prepared probe by address, the expanding gather at
2^20 output slots for the first join's columns and for the last's, the sort
path ``join_probe`` the body ran until PR 44, the search that its gather
plan made of the offsets, and the terminal aggregate's two steps on
``ca_state`` (``sorted_group_by`` against ``group_by_update``: the local
step over a shard's 2^20 slots, and the merge over the worst-case 4 x 2^20
slots its exchange hands on, which hold 4 x 51 partial rows).  Host clock
around ``block_until_ready``, medians of ``MICRO_REPS`` (7); one JSON fact
a line.  ``chiprun --timeout 1500 -- python3 scripts/micro_region_join.py``
(the last piece, the merge at 2^22 slots on a string key, compiled for over
four minutes on the chip in PR 44 and was cut at 600 s); a rehearsal
on XLA:CPU (never a measurement): ``JAX_PLATFORMS=cpu MICRO_SHIFT=8
MICRO_REPS=1`` makes every shape 2^8 times smaller."""
import json, os, statistics, sys, time
sys.path.insert(0, ".")
import numpy as np, pyarrow as pa
import jax, jax.numpy as jnp

SHIFT = int(os.environ.get("MICRO_SHIFT", "0"))   # rehearsal: smaller by 2^SHIFT
REPS = int(os.environ.get("MICRO_REPS", "7"))

def med(f, n=REPS):
    jax.block_until_ready(f()); out = []
    for _ in range(n):
        t = time.perf_counter(); jax.block_until_ready(f())
        out.append(time.perf_counter() - t)
    return round(statistics.median(out) * 1e3, 3)

def say(**kw): print(json.dumps(kw), flush=True)

say(device=jax.devices()[0].device_kind)
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.exec import joins as J
from spark_rapids_tpu.ops.join import (gather_join_output,
                                       join_indices_from_probe, join_probe)
from spark_rapids_tpu.ops.segmented import (AggSpec, group_by_update,
                                            sorted_group_by)

rng = np.random.default_rng(44)
CL = (1 << 20) >> SHIFT                  # a device's shard of store_sales
N = 720_000 >> SHIFT
NCUST, NADDR, NITEM = 100_000 >> SHIFT, 50_000 >> SHIFT, 18_000 >> SHIFT

def nulls(x, p):
    return pa.array(x, mask=rng.random(len(x)) < p)

def batch(cols, cap):
    return ColumnBatch.from_arrow(pa.record_batch(cols), capacity=cap)

stream = batch({
    "ss_sold_date_sk": nulls(rng.integers(2450816, 2452642, N).astype(np.int32), 0.02),
    "ss_item_sk": pa.array(rng.integers(1, NITEM + 1, N).astype(np.int32)),
    "ss_customer_sk": nulls(rng.integers(1, NCUST + 1, N).astype(np.int32), 0.02)}, CL)
days = batch({"d_date_sk": pa.array(np.arange(2451911, 2451942, dtype=np.int32))},
             round_capacity(31))
customer = batch({
    "c_customer_sk": pa.array(np.arange(1, NCUST + 1, dtype=np.int32)),
    "c_current_addr_sk": pa.array(rng.integers(1, NADDR + 1, NCUST).astype(np.int32))},
    round_capacity(NCUST))
STATES = np.array([f"{a}{b}" for a in "ABCDEFG" for b in "HIJKLMNO"][:51])
address = batch({
    "ca_address_sk": pa.array(np.arange(1, NADDR + 1, dtype=np.int32)),
    "ca_state": pa.array(STATES[rng.integers(0, 51, NADDR)])}, round_capacity(NADDR))

def one_join(label, lb, rb, lkey, out_cap=CL):
    """The new body's two pieces, and the old body's on the same shapes."""
    prep = J.prepare_fast_build(rb, (0,))
    kind, key = J.probe_selected(prep, (lkey,))
    probe = jax.jit(lambda l, p: J.probe_traced(kind, l, None, p, key, None, "inner"))
    arrays, total = probe(lb, prep)
    @jax.jit
    def gather(l, r, a):
        plan = join_indices_from_probe(l.capacity, a, "inner", out_cap)
        return gather_join_output(l, r, *plan, None, True)
    out = gather(lb, rb, arrays)
    sort_probe = jax.jit(lambda l, r: join_probe(l, r, (lkey,), (0,), "inner"))
    out_cnt = arrays[3]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(out_cnt)[:-1].astype(jnp.int32)])
    search = jax.jit(lambda o: jnp.searchsorted(
        o, jnp.arange(out_cap, dtype=jnp.int32), side="right"))
    say(join=label, probe=kind, stream_cols=lb.num_columns, build_cols=rb.num_columns,
        build_cap=rb.capacity, rows_out=int(total),
        prepared_probe_ms=med(lambda: probe(lb, prep)),
        expanding_gather_ms=med(lambda: gather(lb, rb, arrays)),
        old_sort_path_probe_ms=med(lambda: sort_probe(lb, rb)),
        old_plan_offsets_search_ms=med(lambda: search(offsets)))
    return out

# the first join (a month of days: keeps 1 row in 60) and, on a full shard of
# wider rows, the last two (every row kept: customer, then its address)
one_join("store_sales x date_dim", stream, days, 0)
wide = one_join("store_sales x customer", stream, customer, 2)
wide = ColumnBatch(wide.columns, wide.num_rows, None)
last = one_join("... x customer_address", wide, address, 4)

# the terminal's local step: count(*) by ca_state over the joined shard
keyed = ColumnBatch([last.columns[-1]], last.num_rows,
                    T.Schema([T.StructField("ca_state", T.StringType(), True)]))
specs = [AggSpec("count_star", 0)]
sort_branch = jax.jit(lambda b: sorted_group_by(b, [0], specs))
update = jax.jit(lambda b: group_by_update(b, [0], specs))
groups, dense = update(keyed)
say(aggregate="count(*) by ca_state", slots=keyed.capacity, rows=int(keyed.num_rows),
    groups=int(groups.num_rows), dense=bool(dense),
    sorted_group_by_ms=med(lambda: sort_branch(keyed)),
    group_by_update_ms=med(lambda: update(keyed)))

# the terminal's merge step: what the in-program exchange hands on is sized for
# the worst case, P x C slots, and holds P x 51 partial rows
MERGE = 4 * CL
parts = batch({"ca_state": pa.array(np.tile(STATES, 4)),
               "cnt": pa.array(rng.integers(1, 20_000, 4 * 51).astype(np.int64))}, MERGE)
merge_specs = [AggSpec("sum", 1)]
merge_sorted = jax.jit(lambda b: sorted_group_by(b, [0], merge_specs))
merge_update = jax.jit(lambda b: group_by_update(b, [0], merge_specs))
say(aggregate="merge of the exchanged partials", slots=MERGE, rows=int(parts.num_rows),
    groups=int(merge_sorted(parts).num_rows),
    sorted_group_by_ms=med(lambda: merge_sorted(parts)),
    group_by_update_ms=med(lambda: merge_update(parts)))
