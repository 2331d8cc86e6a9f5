"""Per-kernel on-chip microbenchmark: times the engine's core kernel
shapes standalone with a true device sync, so the q6 wall time can be
attributed to specific programs (VERDICT round-4 item 1).

Covers the primitives the TPC-DS execution path is built from, at the
scan batch capacity (4M):
  * lax.sort: i32 / (i32,u32) pair / s64 / f32 / f64 keys + payload
  * searchsorted: s64 and i32, 4M probes into 256K sorted keys
  * 1-D gather / scatter-set / segment_sum at 4M
  * s64 / f64 elementwise arithmetic vs 32-bit
  * cumsum i32/s64
Each item reports cold (compile+run) and warm-best-of-2 seconds.

Sync is forced by jax.device_get of one output element.

Usage: python scripts/kernel_bench.py [--cap 4194304] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cap", type=int, default=1 << 22)
    ap.add_argument("--build", type=int, default=1 << 18)
    ap.add_argument("--out", default=None)
    ap.add_argument("--platform", default=None,
                    help="cpu forces XLA:CPU (config.update after "
                         "import is authoritative)")
    args = ap.parse_args()

    import spark_rapids_tpu  # noqa: F401  (x64 config)
    import jax
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
    from spark_rapids_tpu.runtime import enable_compilation_cache
    enable_compilation_cache()
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    backend = jax.default_backend()
    print(f"backend: {backend}  cap: {args.cap}", flush=True)
    N, B = args.cap, args.build
    rng = np.random.default_rng(0)

    k64 = rng.integers(0, 1 << 20, N).astype(np.int64)
    k32 = k64.astype(np.int32)
    hi = (k64 >> 32).astype(np.int32)
    lo = (k64 & 0xFFFFFFFF).astype(np.uint32)
    f64 = rng.random(N)
    f32 = f64.astype(np.float32)
    iota = np.arange(N, dtype=np.int32)
    bkeys = np.sort(rng.integers(0, 1 << 20, B).astype(np.int64))
    idx = rng.integers(0, N, N).astype(np.int32)
    seg = np.sort(rng.integers(0, 64, N).astype(np.int32))

    results = []

    def timeit(label, fn, *arrs):
        f = jax.jit(fn)
        dargs = [jnp.asarray(a) for a in arrs]

        def sync(r):
            leaves = jax.tree_util.tree_leaves(r)
            x = leaves[0]
            return jax.device_get(x.ravel()[0] if x.ndim else x)

        t0 = time.perf_counter()
        sync(f(*dargs))
        cold = time.perf_counter() - t0
        ts = []
        for _ in range(2):
            t0 = time.perf_counter()
            sync(f(*dargs))
            ts.append(time.perf_counter() - t0)
        rec = {"label": label, "cold_s": round(cold, 3),
               "warm_s": round(min(ts), 4)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    timeit("sort_i32_payload", lambda k, i: lax.sort(
        [k, i], num_keys=1, is_stable=True), k32, iota)
    timeit("sort_i32pair_payload", lambda h, l, i: lax.sort(
        [h, l, i], num_keys=2, is_stable=True), hi, lo, iota)
    timeit("sort_s64_payload", lambda k, i: lax.sort(
        [k, i], num_keys=1, is_stable=True), k64, iota)
    timeit("sort_f32_payload", lambda k, i: lax.sort(
        [k, i], num_keys=1, is_stable=True), f32, iota)
    timeit("sort_f64_payload", lambda k, i: lax.sort(
        [k, i], num_keys=1, is_stable=True), f64, iota)
    timeit("searchsorted_s64_4Mx256K", lambda s, q: jnp.searchsorted(
        s, q), bkeys, k64)
    timeit("searchsorted_i32_4Mx256K", lambda s, q: jnp.searchsorted(
        s.astype(jnp.int32), q.astype(jnp.int32)), bkeys, k64)
    timeit("gather1d_i32", lambda d, i: d[i], k32, idx)
    timeit("gather1d_s64", lambda d, i: d[i], k64, idx)
    timeit("gather1d_f64", lambda d, i: d[i], f64, idx)
    timeit("scatter_set_i32", lambda d, i: jnp.zeros(
        N, jnp.int32).at[i].set(d, mode="drop"), k32, idx)
    timeit("segment_sum_i64_capseg", lambda d, s: jax.ops.segment_sum(
        d, s, num_segments=N), k64, seg)
    timeit("segment_sum_i64_64seg", lambda d, s: jax.ops.segment_sum(
        d, s, num_segments=64), k64, seg)
    timeit("cumsum_i32", lambda d: jnp.cumsum(d.astype(jnp.int32)), k32)
    timeit("cumsum_s64", lambda d: jnp.cumsum(d), k64)
    timeit("elemwise_s64", lambda a: (a * 3 + 7) ^ (a >> 5), k64)
    timeit("elemwise_i32", lambda a: (a * 3 + 7) ^ (a >> 5), k32)
    timeit("elemwise_f64", lambda a: a * 1.5 + a * a, f64)
    timeit("elemwise_f32", lambda a: a * 1.5 + a * a, f32)
    timeit("sum_f64", lambda a: jnp.sum(a), f64)
    timeit("where_cmp_s64", lambda a, b: jnp.where(a < b, a, b),
           k64, np.flip(k64).copy())

    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", f"kernel_bench_{backend}.json")
    with open(out, "w") as f:
        json.dump({"backend": backend, "cap": N, "results": results}, f,
                  indent=1)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
