#!/usr/bin/env python3
"""The chip's word on how a float64 reaches it (PR 46).

    chiprun -- python scripts/probe_double_decode.py

On the device, in one process:

* ``decode(encode(v)) == device_put(v)`` elementwise (and ``>=``,
  ``<=``) for every value the wire codec ships as scaled integers
  (``columnar/wirecodec.py``: ``encode_fixed`` -> ``_PackBuilder.build``
  -> ``jit_batch_unpack``): all hundredths 0..2 x 10^7, seeded wider
  hundredths with negative bases, whole numbers to 2^47;
* ``from_cents(n) == device_put(n / 100.0)`` over seeded ``n`` to 2^44;
* the operations the rebuild uses (float32 -> float64, the two sums,
  int64 -> float64) against the emulator tier-1 runs them under
  (``tests/chip_f64.py``), on the same inputs, bit for bit;
* for the record, what the formula before PR 46 gives at five
  hundredths (``5 * 0.01 >= 0.05``: false on the chip).

Every line is one JSON fact with its count of mismatches; the last is
``{"ok": ..., "device": ...}``.  Exits non-zero on any mismatch, and at
once where the platform is not the expected one.  ``run("cpu")`` is the
rehearsal (real float64: the emulator's lines are left out).
"""
from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "tests")]

CAP = 1 << 20


def _say(**fact) -> None:
    print(json.dumps(fact), flush=True)


def _shipped(values):
    """(how the column travelled, its decoded device array) through the
    pack builder and the unpack program, as a scan stages it."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import _PackBuilder
    pack = _PackBuilder(CAP, codec=True)
    pack.add_fixed(values, None)
    schema = T.Schema([T.StructField("v", T.DoubleType())])
    batch = pack.build(len(values), schema)
    desc = pack.col_specs[0][1]
    return (desc[0], desc[2] if desc[0] == "fbits" else None), \
        batch.columns[0].data


def _unequal(jnp, got, want_host) -> int:
    """Slots where the device does not find ``got`` equal to the
    ``device_put`` of the host's doubles under ==, >= and <=."""
    import jax
    want = jax.device_put(want_host)
    n = want_host.shape[0]
    got = got[:n]
    return int(jnp.sum(~((got == want) & (got >= want) & (got <= want))))


def _decode(jnp, name, cents_chunks, per_unit, bad) -> None:
    travelled, wrong, rows = set(), 0, 0
    for n in cents_chunks:
        v = n / float(per_unit)
        how, got = _shipped(v)
        travelled.add(how)
        wrong += _unequal(jnp, got, v)
        rows += len(v)
    kinds = sorted({h[0] for h in travelled})
    _say(check="decode", values=name, rows=rows, mismatches=wrong,
         travelled=kinds, bits=sorted({h[1] for h in travelled if h[1]}))
    if wrong or kinds != ["fbits"]:
        bad.append(f"decode {name}")


def run(expect_platform: str = "tpu") -> dict:
    import numpy as np
    import spark_rapids_tpu  # noqa: F401  (x64 on)
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import cents

    dev = jax.devices()[0]
    if dev.platform != expect_platform:
        raise RuntimeError(f"probe: platform is {dev.platform!r}, "
                           f"expected {expect_platform!r}")
    _say(phase="device", platform=dev.platform, kind=dev.device_kind)
    rng = np.random.default_rng(46)
    bad: list[str] = []

    # ---- the wire codec's rebuild, through the unpack program
    _decode(jnp, "hundredths 0..2e7",
            [np.arange(lo, min(lo + CAP, 20_000_001), dtype=np.int64)
             for lo in range(0, 20_000_001, CAP)], 100, bad)
    wide = []
    for bits in (8, 12, 16, 20, 24, 28, 32):
        for base in (-(1 << 43) + 5, -10**9, -37, 10**7, (1 << 43)):
            span = min(1 << bits, (1 << 44) - 1 - abs(base))
            n = base + rng.integers(0, span, CAP)
            n[0], n[1] = base, base + span - 1
            wide.append(n.astype(np.int64))
    _decode(jnp, "seeded hundredths, |n| < 2^44, negative bases",
            wide, 100, bad)
    _decode(jnp, "whole numbers, |n| < 2^47",
            [b + rng.integers(0, 1 << 32, CAP)
             for b in (0, -(1 << 47) + 1, (1 << 47) - (1 << 32), -5)],
            1, bad)

    # ---- from_cents, as the exact sums call it
    rebuild = jax.jit(lambda c: cents.from_cents(jnp, c))
    wrong = rows = 0
    for k in range(4):
        n = rng.integers(-(1 << 44) + 1, 1 << 44, CAP)
        n[:8] = [0, 1, -1, 5, (1 << 44) - 1, -(1 << 44) + 1,
                 100 << 24, (100 << 24) - 1]
        if k == 1:
            n = rng.integers(-10**9, 10**9, CAP)
        wrong += _unequal(jnp, rebuild(jax.device_put(n)), n / 100.0)
        rows += CAP
    _say(check="from_cents", values="seeded |n| < 2^44", rows=rows,
         mismatches=wrong)
    if wrong:
        bad.append("from_cents")

    # ---- the bug on record: the old formula at five hundredths
    old = jax.jit(lambda c: c.astype(jnp.float64) * 0.01 >= 0.05)
    new = jax.jit(lambda c: cents.from_cents(jnp, c) >= 0.05)
    five = jax.device_put(np.array([4, 5, 6, 7], np.int64))
    _say(check="on record", formula="n.astype(f64) * 0.01 >= 0.05",
         at=[4, 5, 6, 7], old=np.asarray(old(five)).tolist(),
         from_cents=np.asarray(new(five)).tolist())

    # ---- the emulator's operations against the chip's
    if expect_platform == "tpu":
        from chip_f64 import _Int64s, _Pair, _PairXP
        n = rng.integers(-(1 << 44) + 1, 1 << 44, CAP)
        hi, lo, rest = cents._thirds(np, n)
        f64 = jnp.float64

        def chip(fn, *args):
            return np.asarray(jax.jit(fn)(*map(jax.device_put, args)))

        def pair(x):
            return _Pair(x, np.zeros_like(x))

        checks = {
            "float32 -> float64": (
                chip(lambda a: a.astype(f64), hi), pair(hi).stored()),
            "hi + lo": (
                chip(lambda a, b: a.astype(f64) + b.astype(f64), hi, lo),
                (pair(hi) + pair(lo)).stored()),
            "(hi + lo) + rest": (
                chip(lambda a, b, c: a.astype(f64) + b.astype(f64)
                     + c.astype(f64), hi, lo, rest),
                (pair(hi) + pair(lo) + pair(rest)).stored()),
            "int64 -> float64, |n| < 2^47": (
                chip(lambda a: a.astype(f64), n * 8),
                _Int64s(n * 8).astype(_PairXP.float64).stored()),
        }
        x = rng.uniform(-1e6, 1e6, CAP) * rng.choice([1e-6, 1.0, 1e6], CAP)
        y = rng.uniform(-1e6, 1e6, CAP) * rng.choice([1e-6, 1.0, 1e6], CAP)
        checks["float64 + float64, any operands"] = (
            chip(lambda a, b: a + b, x, y),
            (_Pair.of(x) + _Pair.of(y)).stored())
        checks["float64 * float64, any operands"] = (
            chip(lambda a, b: a * b, x, y),
            (_Pair.of(x) * _Pair.of(y)).stored())
        for name, (got, want) in checks.items():
            wrong = int((got.view(np.int64) != want.view(np.int64)).sum())
            _say(check="emulator", op=name, rows=CAP, mismatches=wrong)
            # what the rebuild does not use is reported, not required
            if wrong and "any operands" not in name:
                bad.append(f"emulator {name}")

    result = {"ok": not bad, "failed": bad,
              "device": {"platform": dev.platform, "kind": dev.device_kind}}
    _say(**result)
    return result


if __name__ == "__main__":
    sys.exit(0 if run()["ok"] else 1)
