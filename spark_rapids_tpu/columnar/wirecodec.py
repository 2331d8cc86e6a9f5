"""Wire codec: encoded host->device transfers, decoded on device.

The reference ships compressed tables over its transports and
decompresses ON the GPU (nvcomp seam, TableCompressionCodec.scala:41,
GpuCompressedColumnVector.java) because PCIe/IB bandwidth — not kernel
time — bounds scan-heavy queries.  The TPU analog has the same shape:
host->device bandwidth bounds a scan, so every column is encoded
host-side into compact integer streams and decoded INSIDE the single
jitted unpack program that already materializes a packed batch
(columnar/batch.py _PackBuilder) — the decode fuses with the
slice/reshape pass and costs no extra dispatch or host round trip.

Encodings (chosen per column per batch, host-side, O(n) numpy passes):

* ints / dates / timestamps / bools — frame-of-reference + bit-packing:
  ship ``capacity * b / 32`` uint32 words where ``b`` is the rung of
  ``_BIT_BUCKETS`` that holds ``bit_length(max - min)``, decode
  ``(bits + min) * div``; an optional integral divisor (1e3/1e6)
  catches second-aligned timestamps.
* float64 — when every value is a whole number, or the double nearest
  a whole number of hundredths (money is cents: ``n / 100.0 == v``
  bitwise), ship the FOR/bit-packed integers ``n``.  The decode
  (:func:`rebuild_double`) gives back THE double the host held, in the
  device's own arithmetic: the chip computes float64 on f32 pairs of
  about 48 bits, where ``n * 0.01`` is not the pair ``n / 100.0`` turns
  into for half of all ``n`` (``l_discount >= 0.05`` lost every row at
  0.05 until PR 46), so hundredths are rebuilt from integers by
  ``ops/cents.from_cents`` and whole numbers by one conversion.  A
  column's doubles are the same doubles whichever way a batch of them
  travelled.
* strings — pyarrow dictionary encoding when it pays: ship the (small)
  dictionary byte-matrix plus bit-packed indices; decode selects among a
  dictionary of at most ``_DICT_SELECT_MAX_ROWS`` rows and gathers from
  a larger one (the one place the data decides an index).
* validity — all-valid columns ship NOTHING (decode compares against
  num_rows); others ship 1 bit/row.

Bit widths are the rungs of ``_BIT_BUCKETS`` (1, 2, 4, 8, 12, ... 32):
a 17-bit key column ships 20 bits, so that a batch whose value range
crosses a bit boundary does not compile a fresh unpack program.

The bit layout is PLANAR, and host packer and device decode are one
format (:func:`pack_bits_host`, :func:`_unpack_bits_device`): values are
grouped in blocks of ``lcm(b, 32)`` bits that no value straddles, the
words of all blocks are shipped plane by plane (word 0 of every block,
then word 1, ...), and block ``k`` holds slots ``k, k + nblocks,
k + 2 * nblocks, ...``.  Slot ``j * nblocks + k`` is then the same shift
of the same one or two planes for every ``k``: the decode is static
slices, shifts, masks and one concatenate — no index computed from an
``arange``, so no gather (10 ns a row a column on the chip, PERF.md
Findings PR 39).
"""
from __future__ import annotations

from math import gcd

import numpy as np

__all__ = ["encode_fixed", "encode_lengths", "maybe_dict_arrow",
           "pack_bits_host", "decode_data", "decode_dict",
           "decode_validity", "dict_selects", "bits_needed",
           "rebuild_double"]

#: integral divisors probed for int64 columns (timestamp micros that are
#: second- or milli-aligned shrink below the 32-bit FOR window)
_INT_DIVISORS = (1_000_000, 1_000)
#: what a float64 column may be whole numbers of: ``(per unit, limit)``
#: — units first (a quantity: fewer bits, one conversion to decode),
#: then hundredths (money is cents).  A value ``v`` is shipped as the
#: integer ``n`` only where ``n / per_unit == v`` bit for bit and
#: ``|n| < limit``, the domain over which :func:`rebuild_double` gives
#: ``v`` back exactly on the chip (an int64 converts to an f32 pair
#: exactly below 2^48; ``ops/cents.SUM_LIMIT``)
_FLOAT_UNITS = ((1, 1 << 48), (100, 1 << 44))


#: rows of a float64 column a unit is first tried on
_PROBE_ROWS = 256


#: bit widths are BUCKETED: the unpack program's structure (and the
#: encoded leaf sizes feeding every later leaf's offset) depend on the
#: width, so free widths would compile a fresh program whenever a
#: batch's value range crosses a bit boundary — these rungs keep the
#: variant count bounded while staying within ~15% of minimal bits
_BIT_BUCKETS = (1, 2, 4, 8, 12, 16, 20, 24, 28, 32)

#: a dictionary of at most this many (padded) rows is decoded by a chain
#: of selects, a larger one by a gather (measured, PERF.md Findings PR 39)
_DICT_SELECT_MAX_ROWS = 64


def bits_needed(rng: int) -> int:
    """Bucketed bits to hold values in [0, rng]."""
    raw = max(1, int(rng).bit_length())
    for b in _BIT_BUCKETS:
        if raw <= b:
            return b
    return raw


def _layout(cap: int, bits: int) -> tuple[int, int, int]:
    """``(g, wpb, nblocks)`` of a ``cap``-slot stream of ``bits``-bit
    values: a block is ``lcm(bits, 32)`` bits = ``g`` values = ``wpb``
    words, so no value straddles a block; ``nblocks`` blocks hold the
    slots.  For a power-of-two ``cap`` >= 8 and a width of
    ``_BIT_BUCKETS`` that is ``cap * bits / 32`` words, no padding."""
    lcm = bits * 32 // gcd(bits, 32)
    g = lcm // bits
    return g, lcm // 32, -(-cap // g)


def pack_bits_host(vals: np.ndarray, bits: int, cap: int) -> np.ndarray:
    """Pack ``vals`` (non-negative, < 2**bits, any int dtype) into
    ``cap`` slots of ``bits`` bits, returned as uint32 words; slots
    beyond ``len(vals)`` are zero bits.

    The layout is PLANAR, because the device decodes it by static
    slices and shifts (:func:`_unpack_bits_device`): the stream is
    ``wpb`` word planes of ``nblocks`` words, block ``k`` is word ``k``
    of every plane, and it holds the values ``k, k + nblocks,
    k + 2 * nblocks, ...`` little-endian at bit ``j * bits``.  So value
    ``j * nblocks + k`` is a fixed shift of one or two planes whatever
    ``k`` is, and each of the ``g`` passes here is a shift/or over one
    contiguous row: O(n) temporaries whatever the width."""
    g, wpb, nblocks = _layout(cap, bits)
    padded = np.zeros(g * nblocks, np.uint32)
    padded[:vals.shape[0]] = vals
    rows = padded.reshape(g, nblocks)
    acc = np.zeros((wpb, nblocks), np.uint32)
    for j in range(g):
        wi, sh = divmod(j * bits, 32)
        acc[wi] |= rows[j] << np.uint32(sh)
        if sh + bits > 32:
            acc[wi + 1] |= rows[j] >> np.uint32(32 - sh)
    return acc.reshape(-1)


def _unpack_bits_device(words, cap: int, bits: int):
    """uint32[cap] of ``bits``-bit values from :func:`pack_bits_host`'s
    words (traced; runs inside the batch unpack program).  Every index
    is static: plane ``p`` is a slice, value row ``j`` a shift of one or
    two planes, the result their concatenation — no gather."""
    import jax
    import jax.numpy as jnp
    g, wpb, nblocks = _layout(cap, bits)
    planes = [jax.lax.slice(words, (p * nblocks,), ((p + 1) * nblocks,))
              for p in range(wpb)]
    rows = []
    for j in range(g):
        wi, sh = divmod(j * bits, 32)
        v = planes[wi] >> jnp.uint32(sh) if sh else planes[wi]
        if sh + bits > 32:
            v = v | (planes[wi + 1] << jnp.uint32(32 - sh))
        if sh + bits != 32:
            v = v & jnp.uint32((1 << bits) - 1)
        rows.append(v)
    return jnp.concatenate(rows)[:cap]


# ---------------------------------------------------------------------------
# Host-side encoding decisions
# ---------------------------------------------------------------------------

def _valid_minmax(data: np.ndarray, validity: np.ndarray | None):
    """(vmin, vmax) over valid slots; None when no valid values."""
    if validity is not None and not validity.all():
        if not validity.any():
            return None
        data = data[validity]
    if data.size == 0:
        return None
    return data.min(), data.max()


def encode_fixed(data: np.ndarray, validity: np.ndarray | None, cap: int,
                 add_leaf, add_i64):
    """Encode one fixed-width column's data leaf.

    ``data`` is the UNPADDED host array (null slots already zeroed).
    ``add_leaf(arr)`` registers a host buffer and returns its leaf index;
    ``add_i64`` registers a dynamic decode param (the FOR base) and
    returns its param index.  Divisors/scales come from tiny fixed menus
    so they ride the spec as STATIC program constants.  Returns the
    data_desc spec tuple.
    """
    dt = data.dtype
    out_dtype = dt.str

    def raw():
        full = np.zeros((cap,) + data.shape[1:], dtype=dt)
        full[:data.shape[0]] = data
        return ("raw", add_leaf(full))

    if dt.kind == "b":
        return ("bits", add_leaf(pack_bits_host(
            data.astype(np.uint8), 1, cap)), 1, out_dtype, add_i64(0), 1)
    if dt.kind in "iu":
        mm = _valid_minmax(data.astype(np.int64, copy=False), validity)
        if mm is None:
            return ("bits", add_leaf(pack_bits_host(
                np.zeros(0, np.uint32), 1, cap)), 1, out_dtype,
                add_i64(0), 1)
        vmin, vmax = int(mm[0]), int(mm[1])
        div = 1
        if dt.itemsize == 8 and vmax - vmin >= (1 << 32):
            for d in _INT_DIVISORS:
                q, r = np.divmod(data.astype(np.int64, copy=False), d)
                if not r.any() and (vmax - vmin) // d < (1 << 32):
                    data, vmin, vmax, div = q, vmin // d, vmax // d, d
                    break
            else:
                return raw()
        rng = vmax - vmin
        if rng >= (1 << 32):
            return raw()
        bits = bits_needed(rng)
        if bits >= dt.itemsize * 8 and div == 1:
            return raw()
        enc = (data.astype(np.int64, copy=False) - vmin).astype(np.uint32)
        if validity is not None and not validity.all():
            enc = np.where(validity, enc, 0)
        return ("bits", add_leaf(pack_bits_host(enc, bits, cap)), bits,
                out_dtype, add_i64(vmin), div)
    if dt.kind == "f" and dt.itemsize == 8:
        v = data
        # -0.0 round-trips to +0.0 through the integer path; the values
        # compare equal but format differently ("-0" vs "0") in the
        # differential harness — ship raw when any negative zero exists
        zeros = v == 0
        if zeros.any() and np.signbit(v[zeros]).any():
            return raw()
        with np.errstate(invalid="ignore", over="ignore"):
            for per_unit, limit in _FLOAT_UNITS:
                # told from its first rows where it is not (a price is
                # not whole dollars): a failed probe costs no pass
                head = v[:_PROBE_ROWS]
                if not (np.rint(head * per_unit) / per_unit == head).all():
                    continue
                ints = np.rint(v * per_unit)
                mm = _valid_minmax(ints, validity)
                vmin, vmax = (0.0, 0.0) if mm is None else mm
                if not (np.isfinite(vmin) and np.isfinite(vmax)):
                    break  # NaN/inf present: ship raw
                if vmax - vmin >= (1 << 32) or max(-vmin, vmax) >= limit:
                    continue
                if not (ints / per_unit == v).all():
                    continue  # not whole numbers of this unit
                bits = bits_needed(int(vmax - vmin))
                enc = (ints - vmin).astype(np.uint32)
                if validity is not None and not validity.all():
                    enc = np.where(validity, enc, 0)
                return ("fbits", add_leaf(pack_bits_host(enc, bits, cap)),
                        bits, out_dtype, add_i64(int(vmin)), per_unit)
        return raw()
    return raw()


def encode_lengths(lengths: np.ndarray, cap: int, max_len: int,
                   add_leaf, add_i64):
    """Length vectors are in [0, max_len]: always bit-packable."""
    bits = bits_needed(max(int(max_len), 1))
    return ("bits", add_leaf(pack_bits_host(
        lengths.astype(np.uint32), bits, cap)), bits, "<i4",
        add_i64(0), 1)


def maybe_dict_arrow(arr, n: int):
    """Try pyarrow dictionary encoding for a string array; returns
    (indices int32[n] with nulls->0, dictionary pa.Array) when the
    encoded form is materially smaller, else None."""
    if n < 4096:
        return None
    import pyarrow.compute as pc
    # a column of all but distinct values (comments, names) is told from
    # its first n/64 rows: encoding every row only to throw the
    # dictionary away hashes each byte of a fact-sized batch.  A column
    # uniform over k <= n/8 values shows at most 88 % distinct there.
    if n >= 1 << 16 and \
            pc.count_distinct(arr.slice(0, n // 64)).as_py() > 0.95 * (n // 64):
        return None
    try:
        enc = arr.dictionary_encode()
    # enginelint: disable=RL001 (dictionary codec is best-effort; un-encodable arrays ship raw)
    except Exception:  # noqa: BLE001 - codec is best-effort
        return None
    k = len(enc.dictionary)
    if k == 0 or k > max(256, n // 8):
        return None
    idx = enc.indices
    if idx.null_count:
        idx = pc.fill_null(idx, 0)
    return np.asarray(idx, dtype=np.int64).astype(np.int32), enc.dictionary


# ---------------------------------------------------------------------------
# Device-side decode (traced helpers called from the unpack program)
# ---------------------------------------------------------------------------

def decode_validity(desc, leaf, cap: int, nr):
    """bool[cap] from a validity desc — ("av",) derives the mask from
    the row count, ("vbits", i) unpacks 1 bit/row; ``leaf`` resolves
    leaf indices to traced arrays, ``nr`` is the traced row count."""
    import jax.numpy as jnp
    if desc[0] == "av":
        return jnp.arange(cap, dtype=jnp.int32) < nr
    return _unpack_bits_device(leaf(desc[1]), cap, 1) != 0


def dict_selects(rows: int) -> bool:
    """Whether a dictionary of ``rows`` (padded) rows is decoded by
    compare-and-select rather than by a gather: static, read from the
    dictionary leaf's own shape."""
    return rows <= _DICT_SELECT_MAX_ROWS


def decode_dict(mat, dlens, idx):
    """(bytes[cap, w], lengths[cap]) of a dictionary string column from
    its padded dictionary and decoded indices.  A gather costs about
    10 ns an index on the chip whatever the dictionary's size, a select
    pass over the rows far less for a few of them (PERF.md Findings
    PR 39), so a small dictionary is a chain of selects."""
    import jax.numpy as jnp
    if not dict_selects(mat.shape[0]):
        return mat[idx], dlens[idx]
    data = jnp.zeros((idx.shape[0],) + mat.shape[1:], mat.dtype)
    lens = jnp.zeros(idx.shape, dlens.dtype)
    for r in range(mat.shape[0]):
        hit = idx == r
        data = jnp.where(hit[:, None], mat[r][None, :], data)
        lens = jnp.where(hit, dlens[r], lens)
    return data, lens


def rebuild_double(xp, raw, base, per_unit: int):
    """The float64 column of an ``fbits`` leaf: ``raw`` (uint32, the
    unpacked bits) + ``base`` (int64) whole numbers of ``1 / per_unit``,
    as the doubles the host held.  ``xp`` as in ``ops/cents.py``
    (``jax.numpy`` in the unpack program; the tests run the same code
    under the chip's pair arithmetic, tests/chip_f64.py)."""
    n = raw.astype(xp.int64) + base
    if per_unit == 1:
        return n.astype(xp.float64)
    from spark_rapids_tpu.ops.cents import from_cents
    return from_cents(xp, n)


def decode_data(desc, leaf, i64p, cap: int):
    """Traced decode of a data/lengths desc to its full-capacity array
    (padding/null slots NOT yet zeroed — the caller masks by validity).
    Divisors/units are static program constants; only the FOR base is
    dynamic (read from the i64 params vector)."""
    import jax.numpy as jnp
    kind = desc[0]
    if kind == "raw":
        return leaf(desc[1])
    if kind == "rows":  # a matrix shipped in row chunks
        return jnp.concatenate([leaf(i) for i in desc[1]], axis=0)
    _, li, bits, out_dtype, pbase, factor = desc
    raw = _unpack_bits_device(leaf(li), cap, bits)
    dt = np.dtype(out_dtype)
    if kind == "fbits":
        return rebuild_double(jnp, raw, i64p[pbase], factor)
    if dt.kind == "b":
        return raw != 0
    val = (raw.astype(jnp.int64) + i64p[pbase]) * factor
    return val.astype(dt.str)
