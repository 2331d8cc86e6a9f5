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
  ship ``ceil(n*b/32)`` uint32 words where ``b = bit_length(max-min)``,
  decode ``(bits + min) * div``; an optional integral divisor (1e3/1e6)
  catches second-aligned timestamps.
* float64 — when exactly representable as scaled integers (money is
  cents: ``rint(v/s)*s == v`` bitwise for s in {1, 0.01}), ship the
  FOR/bit-packed integers and decode ``(bits + base) * s``.
* strings — pyarrow dictionary encoding when it pays: ship the (small)
  dictionary byte-matrix plus bit-packed indices; decode is one gather.
* validity — all-valid columns ship NOTHING (decode compares against
  num_rows); others ship 1 bit/row.

Bit widths are arbitrary (1..32, values may straddle word boundaries),
not power-of-two buckets: a 17-bit key column ships 17 bits, not 32.
"""
from __future__ import annotations

import numpy as np

__all__ = ["encode_fixed", "encode_lengths", "maybe_dict_arrow",
           "pack_bits_host", "decode_data", "decode_validity",
           "bits_needed"]

_FAST_BITS = {8: np.uint8, 16: np.uint16, 32: np.uint32}

#: integral divisors probed for int64 columns (timestamp micros that are
#: second- or milli-aligned shrink below the 32-bit FOR window)
_INT_DIVISORS = (1_000_000, 1_000)
#: scales probed for float64 columns (money = cents first, then whole)
_FLOAT_SCALES = (0.01, 1.0)


#: bit widths are BUCKETED: the unpack program's structure (and the
#: encoded leaf sizes feeding every later leaf's offset) depend on the
#: width, so free widths would compile a fresh program whenever a
#: batch's value range crosses a bit boundary — these rungs keep the
#: variant count bounded while staying within ~15% of minimal bits
_BIT_BUCKETS = (1, 2, 4, 8, 12, 16, 20, 24, 28, 32)


def bits_needed(rng: int) -> int:
    """Bucketed bits to hold values in [0, rng]."""
    raw = max(1, int(rng).bit_length())
    for b in _BIT_BUCKETS:
        if raw <= b:
            return b
    return raw


def pack_bits_host(vals: np.ndarray, bits: int, cap: int) -> np.ndarray:
    """Pack ``vals`` (non-negative, < 2**bits, any int dtype) into a
    little-endian bit stream of ``cap`` slots, returned as uint32 words.
    Slots beyond ``len(vals)`` are zero bits."""
    n = vals.shape[0]
    nwords = (cap * bits + 31) // 32
    if bits in _FAST_BITS:
        per = 32 // bits
        buf = np.zeros(nwords * per, dtype=_FAST_BITS[bits])
        buf[:n] = vals.astype(_FAST_BITS[bits])
        return buf.view(np.uint32)
    # Word-level shift/or accumulation.  The previous formulation built
    # an n x bits uint8 bit-matrix plus a 32-aligned bit stream (~n*bits
    # bytes each — ~120 MB of host staging per 4M-row 24-bit column
    # before the arrays even reached packbits).  Values are laid out in
    # BLOCKS of lcm(bits, 32): g = lcm/bits values fill exactly
    # wpb = lcm/32 words, value j of a block starting at bit j*bits —
    # and because g*bits == wpb*32, no value ever spills across a block
    # boundary, so each of the g column passes is a pure vectorized
    # shift/or over the block rows with no scatter and no carries.
    # Peak temporaries are O(n) bytes (padded input + one uint64 column
    # + the uint64 accumulator), independent of the bit width.
    from math import gcd
    lcm = bits * 32 // gcd(bits, 32)
    g = lcm // bits             # values per block
    wpb = lcm // 32             # words per block
    nblocks = (nwords + wpb - 1) // wpb
    padded = np.zeros(nblocks * g, dtype=vals.dtype)
    padded[:n] = vals
    blocks = padded.reshape(nblocks, g)
    acc = np.zeros((nblocks, wpb + 1), np.uint64)
    for j in range(g):
        off = j * bits
        wi, sh = off // 32, np.uint64(off % 32)
        contrib = blocks[:, j].astype(np.uint64) << sh
        acc[:, wi] |= contrib & np.uint64(0xFFFFFFFF)
        acc[:, wi + 1] |= contrib >> np.uint64(32)
    return acc[:, :wpb].reshape(-1)[:nwords].astype(np.uint32)


def _unpack_bits_device(words, cap: int, bits: int):
    """uint32[cap] of ``bits``-bit values from the packed word stream
    (traced; runs inside the batch unpack program)."""
    import jax.numpy as jnp
    mask = jnp.uint32((1 << bits) - 1) if bits < 32 else jnp.uint32(0xFFFFFFFF)
    i = jnp.arange(cap, dtype=jnp.uint32)
    if bits in _FAST_BITS:
        per = 32 // bits
        w = words[(i // per).astype(jnp.int32)]
        sh = (i % per) * jnp.uint32(bits)
        return (w >> sh) & mask
    nwords = words.shape[0]
    o = i * jnp.uint32(bits)
    wi = (o >> 5).astype(jnp.int32)
    sh = o & jnp.uint32(31)
    lo = words[wi] >> sh
    hi = words[jnp.minimum(wi + 1, nwords - 1)]
    # (32 - sh) & 31 keeps the shift defined when sh == 0; the where
    # discards that lane anyway
    spill = jnp.where(sh > 0, hi << ((jnp.uint32(32) - sh) & jnp.uint32(31)),
                      jnp.uint32(0))
    return (lo | spill) & mask


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
        for scale in _FLOAT_SCALES:
            with np.errstate(invalid="ignore", over="ignore"):
                ints = np.rint(v / scale)
            if not np.isfinite(ints).all():
                break  # NaN/inf present: ship raw
            if not (ints * scale == v).all():
                continue  # not exactly representable at this scale
            mm = _valid_minmax(ints, validity)
            vmin = 0 if mm is None else int(mm[0])
            vmax = 0 if mm is None else int(mm[1])
            rng = vmax - vmin
            if rng >= (1 << 32):
                continue
            bits = bits_needed(rng)
            if bits > 32:
                continue
            enc = (ints.astype(np.int64) - vmin).astype(np.uint32)
            if validity is not None and not validity.all():
                enc = np.where(validity, enc, 0)
            return ("fbits", add_leaf(pack_bits_host(enc, bits, cap)),
                    bits, out_dtype, add_i64(vmin), scale)
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


def decode_data(desc, leaf, i64p, cap: int):
    """Traced decode of a data/lengths desc to its full-capacity array
    (padding/null slots NOT yet zeroed — the caller masks by validity).
    Divisors/scales are static program constants; only the FOR base is
    dynamic (read from the i64 params vector)."""
    import jax.numpy as jnp
    kind = desc[0]
    if kind == "raw":
        return leaf(desc[1])
    _, li, bits, out_dtype, pbase, factor = desc
    raw = _unpack_bits_device(leaf(li), cap, bits)
    dt = np.dtype(out_dtype)
    if kind == "fbits":
        return ((raw.astype(jnp.float64) + i64p[pbase].astype(jnp.float64))
                * factor).astype(dt.str)
    if dt.kind == "b":
        return raw != 0
    val = (raw.astype(jnp.int64) + i64p[pbase]) * factor
    return val.astype(dt.str)
