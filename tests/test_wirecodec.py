"""Wire-codec differential tests: every encoding must round-trip
host->encode->device-decode->host bit-exactly against the raw path.

Reference test model: the compression codec round-trip tests over the
shuffle path (TableCompressionCodec, SURVEY §4); here the codec rides
the scan/backend-switch H2D path, so the round trip is
pyarrow.RecordBatch -> ColumnBatch(codec) -> to_arrow."""
import numpy as np
import pyarrow as pa
import pytest

from chip_f64 import _Pair, _PairXP, _on_chip
from spark_rapids_tpu.columnar import wirecodec as wc
from spark_rapids_tpu.columnar.batch import ColumnBatch


def roundtrip(rb):
    got = ColumnBatch.from_arrow(rb, codec=True).to_arrow()
    want = ColumnBatch.from_arrow(rb, codec=False).to_arrow()
    assert got.schema == want.schema
    for i, name in enumerate(rb.schema.names):
        gl, wl = got.column(i).to_pylist(), want.column(i).to_pylist()
        assert len(gl) == len(wl), name
        for g, w in zip(gl, wl):
            if isinstance(g, float) and isinstance(w, float) \
                    and np.isnan(g) and np.isnan(w):
                continue
            assert g == w, (name, g, w)
    return got


def test_pack_bits_host_all_widths():
    rng = np.random.default_rng(0)
    for bits in range(1, 33):
        n = 1000
        vals = rng.integers(0, 1 << bits, size=n, dtype=np.uint64) \
            .astype(np.uint32)
        words = wc.pack_bits_host(vals, bits, 1024)
        assert words.dtype == np.uint32
        assert words.size == (1024 * bits + 31) // 32
        # decode on host via the same bit math the device uses: block k
        # is word k of each plane, value j of it sits at bit j * bits
        g, wpb, nblocks = wc._layout(1024, bits)
        blocks = np.ascontiguousarray(words.reshape(wpb, nblocks).T)
        stream = np.unpackbits(blocks.view(np.uint8), axis=1,
                               bitorder="little")
        got = np.zeros((nblocks, g), np.uint32)
        for b in range(bits):
            got |= stream[:, b::bits].astype(np.uint32) << np.uint32(b)
        np.testing.assert_array_equal(got.T.reshape(-1)[:n], vals)


@pytest.mark.parametrize("dtype,lo,hi", [
    (np.int32, 0, 100), (np.int32, -5, 300000), (np.int64, 0, 17),
    (np.int64, -2**40, -2**40 + 1000), (np.int8, -128, 127),
    (np.int64, -2**62, 2**62),  # range too wide: raw path
])
def test_int_columns(dtype, lo, hi):
    rng = np.random.default_rng(1)
    vals = rng.integers(lo, hi, size=2000, dtype=np.int64).astype(dtype)
    mask = rng.random(2000) < 0.1
    arr = pa.array(np.ma.masked_array(vals, mask))
    roundtrip(pa.record_batch([arr], names=["c"]))


def test_timestamp_micros_divisor():
    # second-aligned micros: range > 2^32 but divisor 1e6 shrinks it
    rng = np.random.default_rng(2)
    secs = rng.integers(1_500_000_000, 1_600_000_000, size=4096)
    micros = secs * 1_000_000
    got = {}
    desc = wc.encode_fixed(
        micros, None, 4096,
        lambda a: got.setdefault("leaf", a) is None and 0 or 0,
        lambda v: got.setdefault("i64", []).append(v) or len(got["i64"]) - 1)
    assert desc[0] == "bits"
    assert desc[5] == 1_000_000  # static divisor recovered
    arr = pa.array(micros, type=pa.int64())
    roundtrip(pa.record_batch([arr], names=["ts"]))


def test_money_doubles_cents():
    rng = np.random.default_rng(3)
    cents = rng.integers(0, 3_000_000, size=4096)
    vals = cents / 100.0
    # precondition of the cents path: each the double nearest its cents
    assert (np.rint(vals * 100) / 100 == vals).all()
    arr = pa.array(vals)
    roundtrip(pa.record_batch([arr], names=["price"]))


def test_doubles_raw_fallbacks():
    cases = {
        "arbitrary": np.array([1.23456789, np.pi, -0.125]),
        "nan": np.array([1.0, np.nan, 2.0]),
        "inf": np.array([np.inf, -np.inf, 0.0]),
        "negzero": np.array([-0.0, 1.0, 2.0]),
    }
    for name, vals in cases.items():
        rb = pa.record_batch([pa.array(vals)], names=[name])
        got = roundtrip(rb)
        back = np.asarray(got.column(0), dtype=np.float64)
        if name == "negzero":
            assert np.signbit(back[0]), "raw path must preserve -0.0"


def test_bool_and_validity_bitpack():
    rng = np.random.default_rng(4)
    vals = rng.random(5000) < 0.5
    mask = rng.random(5000) < 0.3
    arr = pa.array(np.ma.masked_array(vals, mask))
    roundtrip(pa.record_batch([arr], names=["b"]))
    # all-null column
    arr2 = pa.array([None] * 100, type=pa.int32())
    roundtrip(pa.record_batch([arr2], names=["n"]))


def test_dict_strings():
    rng = np.random.default_rng(5)
    cats = ["Books", "Electronics", "Home & Garden", "Música", ""]
    vals = [cats[i] for i in rng.integers(0, len(cats), size=8192)]
    vals[17] = None
    arr = pa.array(vals, type=pa.string())
    rb = pa.record_batch([arr], names=["cat"])
    # dictionary path must actually engage at this cardinality
    assert wc.maybe_dict_arrow(arr, len(arr)) is not None
    roundtrip(rb)


def test_high_cardinality_strings_stay_raw():
    vals = [f"unique-{i}" for i in range(8192)]
    arr = pa.array(vals, type=pa.string())
    assert wc.maybe_dict_arrow(arr, len(arr)) is None
    roundtrip(pa.record_batch([arr], names=["s"]))


def test_empty_and_single_row():
    for vals in ([], [42]):
        arr = pa.array(vals, type=pa.int64())
        roundtrip(pa.record_batch([arr], names=["x"]))


def test_mixed_schema_roundtrip():
    rng = np.random.default_rng(6)
    n = 4096
    rb = pa.record_batch([
        pa.array(rng.integers(0, 2**17, n, dtype=np.int64)),
        pa.array((rng.integers(0, 10**6, n) * 0.01)),
        pa.array(rng.random(n)),          # arbitrary doubles: raw
        pa.array(["ab", "cd", "ef", None] * (n // 4), type=pa.string()),
        pa.array(rng.random(n) < 0.5),
    ], names=["k", "price", "noise", "tag", "flag"])
    roundtrip(rb)


def _pack_bits_reference(vals, bits, cap):
    """The n x bits bit-matrix formulation of the planar layout, kept
    here as the oracle for the word-level shift/or packer: slot
    ``j * nblocks + k`` is value ``j`` of block ``k``, a block's bits are
    its values' bits little-endian back to back, and word ``p`` of block
    ``k`` is word ``k`` of plane ``p``."""
    n = vals.shape[0]
    g, wpb, nblocks = wc._layout(cap, bits)
    u = np.zeros(g * nblocks, np.uint32)
    u[:n] = vals
    bm = ((u[:, None] >> np.arange(bits, dtype=np.uint32)[None, :]) & 1) \
        .astype(np.uint8)
    blocks = bm.reshape(g, nblocks, bits).transpose(1, 0, 2) \
        .reshape(nblocks, g * bits)
    words = np.ascontiguousarray(
        np.packbits(blocks, axis=1, bitorder="little")).view(np.uint32)
    return np.ascontiguousarray(words.T).reshape(-1)


@pytest.mark.parametrize("bits", [1, 2, 3, 5, 7, 11, 12, 13, 17, 20, 24, 31])
def test_pack_bits_word_accumulation_matches_bit_matrix(rng, bits):
    """The word-level shift/or packer is bit-for-bit identical to the
    bit-matrix formulation for every width and ragged length."""
    for n in (0, 1, 7, 31, 32, 33, 1000, 4097):
        cap = max(n, 1)
        vals = rng.integers(0, 1 << bits, n, dtype=np.uint64)
        got = wc.pack_bits_host(vals, bits, cap)
        want = _pack_bits_reference(vals, bits, cap)
        assert got.dtype == np.uint32
        _, wpb, nblocks = wc._layout(cap, bits)
        assert got.size == wpb * nblocks
        assert np.array_equal(got, want), (bits, n)


def test_pack_bits_peak_memory_is_linear():
    """Peak temporaries must stay O(n) bytes, not O(n*bits): the old
    bit-matrix spiked ~n*bits*2 bytes of uint8 staging (~120 MB for a
    4M-row 24-bit column)."""
    import tracemalloc
    bits, n = 24, 1 << 20
    vals = np.random.default_rng(0).integers(
        0, 1 << bits, n, dtype=np.uint64)
    tracemalloc.start()
    tracemalloc.reset_peak()
    out = wc.pack_bits_host(vals, bits, n)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # a matrix formulation alone: n*bits ~ 25 MB of uint8 plus the
    # 32-aligned stream copy; the packer's budget is a few n*4-byte
    # temporaries.  40 MB bounds it with slack while failing the
    # matrix (~50+ MB).
    assert peak < 40 << 20, f"peak {peak >> 20} MB"
    assert out.nbytes == ((n * bits + 31) // 32) * 4


# ---------------------------------------------------------------------------
# The whole format, through _PackBuilder.build: host layout and device
# decode are one format, so they are held to the host arrays together.
# ---------------------------------------------------------------------------

def _build(cap, n, cols, schema):
    """``cols``: ("fixed", data, validity) / ("var", matrix, lengths,
    validity, width) / ("dict", indices, matrix, lengths, validity)."""
    from spark_rapids_tpu.columnar.batch import _PackBuilder
    pack = _PackBuilder(cap, True)
    for c in cols:
        getattr(pack, {"fixed": "add_fixed", "var": "add_var",
                       "dict": "add_dict_string"}[c[0]])(*c[1:])
    return pack, pack.build(n, schema)


def _padded(a, cap, validity):
    """What the device must hold: ``a`` with null slots zeroed, then
    zero padding up to ``cap``."""
    if validity is not None:
        a = np.where(validity.reshape((-1,) + (1,) * (a.ndim - 1)), a,
                     a.dtype.type(0))
    out = np.zeros((cap,) + a.shape[1:], a.dtype)
    out[:a.shape[0]] = a
    return out


@pytest.mark.parametrize("nulls", [False, True], ids=["valid", "nulls"])
@pytest.mark.parametrize("rows", ["0", "1", "cap-1", "cap"])
@pytest.mark.parametrize("cap", [2048, 1 << 16, 1 << 20])
@pytest.mark.parametrize("bits", wc._BIT_BUCKETS)
def test_packed_batch_round_trip(bits, cap, rows, nulls):
    """Every width of the format x capacity x row count x {no nulls,
    nulls}: data, validity and lengths on the device equal the host
    arrays bit for bit, null and padding slots zero."""
    from spark_rapids_tpu import types as T
    n = {"0": 0, "1": 1, "cap-1": cap - 1, "cap": cap}[rows]
    rng = np.random.default_rng(bits * 7919 + cap + n + nulls)
    top = (1 << bits) - 1

    def ranged(base, scale, dtype):
        """Values whose frame of reference needs exactly ``bits`` bits
        (both ends present where there are two rows to hold them)."""
        r = rng.integers(0, top + 1, n, dtype=np.uint64).astype(np.int64)
        r[:1], r[-1:] = 0, top if n > 1 else 0
        return ((r + base) * scale).astype(dtype)

    def validity():
        if not nulls or n == 0:
            return None
        v = rng.random(n) < 0.8
        v[:1] = v[-1:] = True      # the ends keep the range exact
        return v

    width = 8
    lens = rng.integers(0, width + 1, n).astype(np.int32)
    chars = rng.integers(97, 123, (n, width), dtype=np.uint8)
    chars[np.arange(width)[None, :] >= lens[:, None]] = 0
    host = [
        ("fixed", ranged(-1000, 1, np.int32 if bits < 32 else np.int64),
         validity()),
        ("fixed", ranged(1_600_000_000, 1_000_000, np.int64), validity()),
        ("fixed", ranged(-50, 1, np.int64) / 100.0, validity()),
        ("fixed", rng.random(n) < 0.5, validity()),
        ("fixed", ranged(10_000, 1, np.int32) if bits < 32
         else rng.integers(-2**31, 2**31, n).astype(np.int32), validity()),
        ("var", chars, lens, validity(), width),
    ]
    schema = T.Schema([
        T.StructField("i", T.IntegerType() if bits < 32 else T.LongType(),
                      True),
        T.StructField("ts", T.LongType(), True),
        T.StructField("cents", T.DoubleType(), True),
        T.StructField("b", T.BooleanType(), True),
        T.StructField("d", T.DateType(), True),
        T.StructField("s", T.StringType(), True)])
    pack, batch = _build(cap, n, host, schema)
    assert batch.known_rows == n and int(batch.num_rows) == n
    assert pack.dict_gathers == 0
    if n > 1 and bits < 32:
        # the widths under test are the ones that shipped
        assert pack.col_specs[0][1][:3:2] == ("bits", bits)
        assert pack.col_specs[2][1][:3:2] == ("fbits", bits)
    for c, col in zip(host, batch.columns):
        v = c[-2] if c[0] == "var" else c[-1]
        want_v = np.zeros(cap, np.bool_)
        want_v[:n] = True if v is None else v
        np.testing.assert_array_equal(np.asarray(col.validity), want_v)
        got = np.asarray(col.data)
        want = _padded(c[1], cap, v)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        if c[0] == "var":
            got_l = np.asarray(col.lengths)
            assert got_l.dtype == np.int32
            np.testing.assert_array_equal(got_l, _padded(c[2], cap, v))


def _unpack_gathers(monkeypatch, cap, n, cols, schema):
    """(gathers in the unpack program's jaxpr, the registry's
    unpack.leaves.* movement, leaves shipped) for one built batch."""
    import jax
    from spark_rapids_tpu.columnar import batch as B
    from spark_rapids_tpu.obs.registry import get_registry
    seen = {}
    real = B._packed_unpack_cached

    def spy(spec):
        program = real(spec)

        def call(bufs):
            seen["program"], seen["bufs"] = program, bufs
            return program(bufs)
        return call
    monkeypatch.setattr(B, "_packed_unpack_cached", spy)
    before = get_registry().counters()
    pack, batch = _build(cap, n, cols, schema)
    moved = get_registry().counters_since(before)
    text = str(jax.make_jaxpr(seen["program"].fn)(seen["bufs"]))
    lowered = seen["program"].fn.lower(seen["bufs"]).as_text()
    assert ("gather" in lowered) == ("gather" in text)
    return (text.count(" gather["), moved.get("unpack.leaves.static", 0),
            moved.get("unpack.leaves.gather", 0), len(pack.leaves), batch)


def _dict_col(rng, n, k, width=8):
    lens = rng.integers(1, width + 1, k).astype(np.int32)
    mat = rng.integers(97, 123, (k, width), dtype=np.uint8)
    mat[np.arange(width)[None, :] >= lens[:, None]] = 0
    idx = rng.integers(0, k, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    return ("dict", idx, mat, lens, valid)


@pytest.mark.parametrize("case,want_gathers", [
    ("fixed+validity+lengths", 0), ("small dictionary", 0),
    ("large dictionary", 2), ("one of each", 2)])
def test_unpack_program_gathers_only_from_large_dictionaries(
        monkeypatch, rng, case, want_gathers):
    """The unpack program computes no index from ``arange``: it holds a
    gather only where the data decides the index (a dictionary too large
    to select from: its bytes and its lengths), and the registry counts
    the same leaves."""
    from spark_rapids_tpu import types as T
    cap, n = 4096, 4000
    valid = rng.random(n) < 0.9
    fixed = [
        ("fixed", rng.integers(0, 3000, n).astype(np.int32), valid),  # 12
        ("fixed", rng.integers(0, 10**6, n).astype(np.int64), None),  # 20
        ("fixed", rng.integers(0, 10**7, n) * 0.01, valid),           # 24
        ("fixed", rng.integers(0, 2**27, n).astype(np.int64), valid),  # 28
        ("fixed", rng.random(n) < 0.5, valid),
        ("var", np.zeros((n, 8), np.uint8),
         rng.integers(0, 9, n).astype(np.int32), valid, 8)]
    fields = [("a", T.IntegerType()), ("b", T.LongType()),
              ("c", T.DoubleType()), ("d", T.LongType()),
              ("e", T.BooleanType()), ("f", T.StringType())]
    small = wc._DICT_SELECT_MAX_ROWS
    cols = {"fixed+validity+lengths": fixed,
            "small dictionary": [_dict_col(rng, n, 3),
                                 _dict_col(rng, n, small)],
            "large dictionary": [_dict_col(rng, n, small + 1)],
            "one of each": fixed + [_dict_col(rng, n, 2),
                                    _dict_col(rng, n, 1000)]}[case]
    fields = {"fixed+validity+lengths": fields,
              "small dictionary": [("s", T.StringType())] * 2,
              "large dictionary": [("s", T.StringType())],
              "one of each": fields + [("s", T.StringType())] * 2}[case]
    schema = T.Schema([T.StructField(f"{nm}{i}", t, True)
                       for i, (nm, t) in enumerate(fields)])
    gathers, static, counted, shipped, batch = _unpack_gathers(
        monkeypatch, cap, n, cols, schema)
    assert gathers == counted == want_gathers
    assert static == shipped - counted and static > 0
    # and what a dictionary decodes to, either way, is its rows
    for c, col in zip(cols, batch.columns):
        if c[0] != "dict":
            continue
        _, idx, mat, lens, v = c
        assert np.asarray(col.data).tobytes() == \
            _padded(mat[idx], cap, v).tobytes()
        np.testing.assert_array_equal(np.asarray(col.lengths),
                                      _padded(lens[idx], cap, v))


# ------------------------------ a float64 reaches the chip as itself
# (tests/chip_f64.py: the chip's float64 is an f32 pair; XLA:CPU's is
# real, so only the emulator can see what the rebuild gives there)

def _rebuilt_as_the_chip_would(v):
    """``(desc, pair)``: how ``encode_fixed`` ships the float64 array
    ``v`` and what ``rebuild_double`` makes of the unpacked bits in the
    chip's arithmetic (the bits unpacked by the device's own code)."""
    import jax.numpy as jnp
    cap = max(2048, 1 << int(np.ceil(np.log2(len(v)))))
    leaves, params = [], []
    desc = wc.encode_fixed(v, None, cap,
                           lambda a: leaves.append(a) or len(leaves) - 1,
                           lambda b: params.append(b) or len(params) - 1)
    if desc[0] != "fbits":
        return desc, None
    _, li, bits, _, pbase, per_unit = desc
    raw = np.asarray(wc._unpack_bits_device(
        jnp.asarray(leaves[li]), cap, bits))[:len(v)]
    return desc, wc.rebuild_double(_PairXP, _on_chip(raw),
                                   np.int64(params[pbase]), per_unit)


_REBUILDS = {
    "every hundredth to 200,000":
        lambda rng: np.arange(0, 200_001, dtype=np.int64),
    "seeded to 1e7 hundredths":
        lambda rng: rng.integers(0, 10**7, 100_000),
    "a negative base": lambda rng: rng.integers(-10**7, -10**6, 100_000),
    "a base across zero": lambda rng: rng.integers(-37, 4000, 100_000),
    "a base near -2^43":
        lambda rng: -(1 << 43) + 5 + rng.integers(0, 1 << 31, 100_000),
    "a base near 2^44":
        lambda rng: (1 << 44) - (1 << 32) + rng.integers(0, 1 << 32,
                                                         100_000),
}


@pytest.mark.parametrize("case", sorted(_REBUILDS))
def test_rebuilt_hundredths_are_the_hosts_doubles_in_f32_pairs(case):
    n = _REBUILDS[case](np.random.default_rng(len(case)))
    v = n / 100.0
    desc, got = _rebuilt_as_the_chip_would(v)
    assert desc[0] == "fbits" and desc[5] == 100
    assert got.is_the_pair_of(v)


@pytest.mark.parametrize("per_unit", [1, 100], ids=["units", "hundredths"])
@pytest.mark.parametrize("bits", wc._BIT_BUCKETS)
def test_each_width_rebuilds_the_hosts_doubles_in_f32_pairs(bits, per_unit):
    """Every width the float probe can return, whole numbers and
    hundredths, a negative base: the pair the chip rebuilds is the pair
    the host's double turns into."""
    rng = np.random.default_rng(bits * 31 + per_unit)
    top = (1 << bits) - 1
    n = -12_345 + rng.integers(0, top + 1, 50_000, dtype=np.int64)
    n[0], n[-1] = -12_345, -12_345 + top
    v = n / float(per_unit)
    desc, got = _rebuilt_as_the_chip_would(v)
    assert desc[0] == "fbits" and desc[5] == per_unit and desc[2] == bits
    assert got.is_the_pair_of(v)


def test_the_formula_before_pr_46_fails_at_five_hundredths():
    """On record, so that nobody restores it: ``(raw + base) * 0.01``
    in the chip's arithmetic is under the literal 0.05 at five
    hundredths (TPC-H Q6 lost 27.7 % of its revenue to it) and is not
    the host's double for about half of all hundredths."""
    n = np.arange(0, 200_001, dtype=np.int64)
    f64 = _PairXP.float64
    old = (_on_chip(n.astype(np.uint32)).astype(f64)
           + _on_chip(np.zeros(1, np.int64)).astype(f64)) * 0.01
    assert not (old >= 0.05)[5] and (n / 100.0 >= 0.05)[5]
    same = np.asarray(old == n / 100.0)
    assert not same[[5, 6, 7]].any()
    assert 0.40 < same.mean() < 0.55
    _, new = _rebuilt_as_the_chip_would(n / 100.0)
    assert (new >= 0.05)[5] and (new <= 0.07)[7] and (new == n / 100.0).all()


@pytest.mark.parametrize("values,why", [
    ([0.05, 1 / 3], "not whole hundredths"),
    ([0.05, np.nan], "NaN"), ([0.05, np.inf], "an infinity"),
    ([0.05, -0.0], "a negative zero"),
    ([0.0, float(1 << 44)], "hundredths past SUM_LIMIT, not whole"),
    ([0.5, float(1 << 33) + 0.5], "a range past 32 bits"),
    ([5 * 0.01 + 2 ** -57, 0.06], "an ulp off the nearest double"),
], ids=lambda x: x if isinstance(x, str) else None)
def test_doubles_that_are_not_rebuilt_exactly_travel_raw(values, why):
    v = np.array(values * 2048)
    if why.startswith("hundredths past"):
        v = v + 0.25
    desc, _ = _rebuilt_as_the_chip_would(v)
    assert desc[0] == "raw", why
