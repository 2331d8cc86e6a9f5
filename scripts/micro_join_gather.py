"""Micro-run for the chip (PR 43): ``join_gather``'s two plans at q93's shape (a
2^20-slot stream batch of 5 columns left-joined on two packed keys to a
2.88M-row build of 4 columns in 2^22 slots), checked equal array for
array, and the pieces of what the aligned plan still pays.  Host clock
around ``block_until_ready``, medians of ``MICRO_REPS`` (7); one JSON
fact a line.  ``chiprun -- python3 scripts/micro_join_gather.py``; a
rehearsal on XLA:CPU (never a measurement): ``JAX_PLATFORMS=cpu
MICRO_SHIFT=8 MICRO_REPS=1`` makes every shape 2^8 times smaller."""
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
from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.exec import joins as J
from spark_rapids_tpu.ops import kernels as dk
from spark_rapids_tpu import types as T

rng = np.random.default_rng(43)
CL, CR = (1 << 20) >> SHIFT, (1 << 22) >> SHIFT
NS, NB = 28 * CL, 2_880_000 >> SHIFT
item = rng.integers(1, 56_921, NS).astype(np.int32)
ticket = rng.permutation(NS).astype(np.int64) + 1        # (item, ticket) unique
pick = rng.choice(NS, NB, replace=False)
def nulls(x, p):
    return pa.array(x, mask=rng.random(len(x)) < p)
build = pa.record_batch({
    "sr_item_sk": pa.array(item[pick]), "sr_ticket_number": pa.array(ticket[pick]),
    "sr_reason_sk": nulls(rng.integers(1, 36, NB).astype(np.int32), 0.02),
    "sr_return_quantity": nulls(rng.integers(1, 100, NB).astype(np.int32), 0.02)})
rb = ColumnBatch.from_arrow(build, capacity=CR)
def stream(lo, n=CL):
    s = slice(lo, lo + n)
    return ColumnBatch.from_arrow(pa.record_batch({
        "ss_item_sk": pa.array(item[s]), "ss_ticket_number": pa.array(ticket[s]),
        "ss_customer_sk": nulls(rng.integers(1, 500_000, n).astype(np.int32), 0.02),
        "ss_quantity": pa.array(rng.integers(1, 100, n).astype(np.int32)),
        "ss_sales_price": pa.array(np.round(rng.uniform(1, 200, n), 2))}), capacity=CL)
lb = stream(0)
prep = J.prepare_fast_build(rb, (0, 1))
say(build=type(prep).__name__, inner=type(prep.build).__name__)
kf = T.Schema(list(lb.schema.fields) + list(rb.schema.fields))

def both(lb, label):
    pa_, counts = J._jit_probe_fast(lb, prep, (0, 1), "left")
    total, aligned = (int(x) for x in np.asarray(counts))
    out_cap = round_capacity(total)
    run = lambda a: J._jit_gather(lb, rb, pa_, lb.capacity, "left", out_cap,
                                  True, kf, aligned=a)
    a, b = (jax.tree_util.tree_leaves(run(x)) for x in (False, True))
    same = all(np.array_equal(x, y, equal_nan=True) for x, y in zip(a, b))
    say(shape=label, total=total, aligned=aligned, out_cap=out_cap, same=same,
        matched=int((np.asarray(pa_[1]) > 0).sum()),
        probe_ms=med(lambda: J._jit_probe_fast(lb, prep, (0, 1), "left")),
        expanding_ms=med(lambda: run(False)), aligned_ms=med(lambda: run(True)))
    return pa_

pa_ = both(lb, "full batch")
both(stream(CL, int(CL * 0.38)), "38% of the slots: out_cap < cl")

# ---- the pieces of the aligned plan at the full batch
start, cnt, perm, out_cnt = pa_
last = perm.shape[0] - 1
ri_of = jax.jit(lambda perm, start: perm[jnp.clip(start, 0, last)])
ri = ri_of(perm, start); take = cnt > 0
stacks = jax.jit(lambda cols, idx, take: dk.gather_stacked(cols, idx, take))
front = jax.jit(lambda cols, take: dk.front_stacked(cols, take))
say(piece="perm[start] (int32)", ms=med(lambda: ri_of(perm, start)))
say(piece="build stacks: 4 columns by ri", ms=med(lambda: stacks(rb.columns, ri, take)))
one = jax.jit(lambda xs, ri: xs[ri])
for name, xs in (
        ("4 validity flags", jnp.stack([c.validity for c in rb.columns], axis=1)),
        ("3 int32", jnp.stack([rb.columns[i].data for i in (0, 2, 3)], axis=1)),
        ("1 int64", rb.columns[1].data[:, None])):
    say(piece="one stack by ri: " + name, ms=med(lambda: one(xs, ri)))
say(piece="stream front_stacked: 5 columns, no gather",
    ms=med(lambda: front(lb.columns, lb.row_mask())))
