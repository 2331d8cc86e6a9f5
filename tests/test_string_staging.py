"""Staging a raw string column (columnar/batch.py ``_strings_to_matrix``,
a memcpy a row since PR 42) against the position-matrix gather it
replaced, kept here as the oracle: the same bytes and lengths on random
lengths 0..w, with nulls, slices, both offset widths, and through
``ColumnBatch.from_arrow`` to the device and back."""
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import (ColumnBatch, _strings_to_matrix,
                                             _strings_to_row_chunks)
from spark_rapids_tpu.columnar.column import round_string_width
from spark_rapids_tpu.obs.registry import get_registry


def _old_strings_to_matrix(arr, width=None):
    """The function as it stood before PR 42 (an int64[n, w] position
    matrix, a boolean mask, bytes moved by fancy indexing)."""
    arr = arr.cast(pa.large_string())
    n = len(arr)
    buffers = arr.buffers()
    offsets = np.frombuffer(buffers[1], dtype=np.int64, count=n + 1,
                            offset=arr.offset * 8)
    databuf = np.frombuffer(buffers[2], dtype=np.uint8) \
        if buffers[2] is not None else np.zeros(0, np.uint8)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    if arr.null_count:
        valid = np.asarray(arr.is_valid(), dtype=np.bool_)
        lens = np.where(valid, lens, 0)
    maxw = int(lens.max()) if n else 0
    w = width or round_string_width(max(maxw, 1))
    out = np.zeros((n, w), dtype=np.uint8)
    if n and databuf.size:
        pos = offsets[:-1, None] + np.arange(w, dtype=np.int64)[None, :]
        mask = np.arange(w, dtype=np.int32)[None, :] < lens[:, None]
        out[mask] = databuf[pos[mask]]
    return out, lens


def _random_strings(seed, n, w, nulls):
    rng = np.random.default_rng(seed)
    lens = rng.integers(0, w + 1, n)
    lens[rng.integers(0, n)] = w          # one row fills the width
    pool = rng.integers(32, 127, int(lens.sum()) + 1).astype(np.uint8)
    cuts = np.concatenate([[0], np.cumsum(lens)])
    vals = [bytes(pool[a:b]).decode() for a, b in zip(cuts[:-1], cuts[1:])]
    if nulls:
        for i in rng.choice(n, n // 7, replace=False):
            vals[i] = None
    return vals


@pytest.mark.parametrize("typ", [pa.string(), pa.large_string()],
                         ids=["string", "large_string"])
@pytest.mark.parametrize("nulls", [False, True], ids=["valid", "nulls"])
@pytest.mark.parametrize("seed,w", [(1, 4), (2, 32), (3, 128)])
def test_staged_bytes_and_lengths_equal_the_old_function(seed, w, nulls, typ):
    arr = pa.array(_random_strings(seed, 1000, w, nulls), type=typ)
    for a in (arr, arr.slice(17, 400), arr.slice(999), arr.slice(5, 0)):
        want_m, want_l = _old_strings_to_matrix(a)
        got_m, got_l = _strings_to_matrix(a)
        assert got_m.dtype == np.uint8 and got_l.dtype == np.int32
        np.testing.assert_array_equal(got_m, want_m)
        np.testing.assert_array_equal(got_l, want_l)
        # at a hinted width and at the batch's capacity: the tail zero
        (got_m,), got_l = _strings_to_row_chunks(a, 256, 2048)
        assert got_m.shape == (2048, 256) and not got_m[len(a):].any()
        np.testing.assert_array_equal(got_m[:len(a), :want_m.shape[1]],
                                      want_m)
        assert not got_m[:, want_m.shape[1]:].any()
        np.testing.assert_array_equal(got_l, want_l)


def test_a_string_longer_than_the_hinted_width_is_refused():
    with pytest.raises(ValueError, match="exceeds bucket"):
        _strings_to_matrix(pa.array(["abcdefgh"]), 4)


def test_all_null_and_empty_arrays():
    m, lens = _strings_to_matrix(pa.array([None, None], type=pa.string()))
    assert m.shape == (2, 4) and not m.any() and list(lens) == [0, 0]
    m, lens = _strings_to_matrix(pa.array([], type=pa.string()))
    assert m.shape == (0, 4) and lens.shape == (0,)


@pytest.mark.parametrize("nulls", [False, True], ids=["valid", "nulls"])
def test_a_raw_string_batch_round_trips_and_counts_its_bytes(nulls):
    """Distinct strings, so no dictionary: the raw matrix path, counted
    under ``scan.stage.string_bytes``."""
    _round_trip(5000, 8192, nulls, puts=1)


def test_a_fact_sized_raw_matrix_ships_in_row_chunks():
    """2^19 slots x 128 bytes = 64 MiB: four chunks of 16 MiB, each a
    put of its own beside the shared word buffer, the same bytes and
    lengths on the device."""
    from spark_rapids_tpu.columnar import batch as B
    assert B._CHUNK_BYTES == 16 << 20
    chunks, lens = B._strings_to_row_chunks(
        pa.array(["abc", None, "x" * 128]), None, 1 << 19)
    assert [c.shape for c in chunks] == [(1 << 17, 128)] * 4
    assert bytes(chunks[0][0, :4]) == b"abc\0" and not chunks[1].any()
    assert list(lens) == [3, 0, 128]
    _round_trip(300_000, 1 << 19, True, puts=5)


def test_chunk_buffers_are_used_again_only_once_nothing_reads_them(
        monkeypatch):
    """The row chunks of a matrix of several lie in buffers of the
    engine's own (``_chunk_buffer``): a buffer goes back to the idle
    list when the last array over it is gone — a view that something
    still holds (as jax holds the array it was handed until the
    transfer is done) keeps it out — and the next batch's chunks take
    the idle ones, byte for byte what ``np.empty`` buffers would hold."""
    import gc
    from spark_rapids_tpu.columnar import batch as B
    gc.collect()                        # what earlier tests still held
    monkeypatch.setattr(B, "_CHUNK_BYTES", 1 << 12)
    monkeypatch.setattr(B, "_idle_chunks", [])

    def idle():                         # this test's buffers among them
        gc.collect()
        return [o for o in B._idle_chunks if len(o) == 1 << 12]
    arr = pa.array(_random_strings(7, 200, 32, True))
    want, want_l = _old_strings_to_matrix(arr)
    chunks, lens = _strings_to_row_chunks(arr, 32, 256)
    assert [c.shape for c in chunks] == [(128, 32)] * 2
    np.testing.assert_array_equal(np.concatenate(chunks)[:200], want)
    assert not chunks[1][200 - 128:].any()
    np.testing.assert_array_equal(lens, want_l)
    held = np.ravel(chunks[1])          # as _add_leaf and the put hold it
    snapshot = held.copy()
    del chunks
    (owner,) = idle()                   # the first chunk's; not the held one
    again, _ = _strings_to_row_chunks(pa.array(["zz"] * 256), 32, 256)
    assert idle() == [] and again[0].base.base.obj is owner
    np.testing.assert_array_equal(held, snapshot)   # nobody wrote over it
    assert bytes(again[1][127, :3]) == b"zz\0"
    del held, again
    assert len(idle()) == 3
    # no more idle buffers are kept than _CHUNKS_KEPT
    B._idle_chunks.clear()
    monkeypatch.setattr(B, "_CHUNKS_KEPT", 3)
    more, _ = _strings_to_row_chunks(pa.array(["q"] * 1024), 32, 1024)
    assert len(more) == 8
    del more
    assert len(idle()) == 3


def _round_trip(n, cap, nulls, puts):
    vals = [f"{i:05d}" + s for i, s in
            enumerate(_random_strings(11, n, 70, False))]
    if nulls:
        vals[3] = vals[n - 1000] = None
    rb = pa.record_batch({"k": pa.array(np.arange(n, dtype=np.int32)),
                          "s": pa.array(vals)})
    reg = get_registry()
    before = reg.counters()
    b = ColumnBatch.from_arrow(rb)
    assert b.columns[1].data.shape == (cap, 128)
    back = b.to_arrow()
    assert back.column(1).to_pylist() == vals
    assert back.column(0).to_pylist() == list(range(n))
    moved = reg.counters_since(before)
    assert moved["scan.stage.string_bytes"] == cap * 128
    assert moved["h2d_calls"] == puts


def test_a_column_of_distinct_values_is_told_from_its_first_rows(monkeypatch):
    """``maybe_dict_arrow`` does not hash every byte of a fact-sized
    batch of distinct strings only to throw the dictionary away; a
    column of few values still ships as a dictionary."""
    from spark_rapids_tpu.columnar import wirecodec as wc
    n = 1 << 16
    distinct = pa.array([f"comment {i:07d}" for i in range(n)])
    few = pa.array([f"value {i % 300}" for i in range(n)])
    eighth = pa.array([f"value {i % (n // 8)}" for i in range(n)])
    calls = []
    real = pa.StringArray.dictionary_encode

    class Spy:
        def __init__(self, arr):
            self._arr = arr

        def __len__(self):
            return len(self._arr)

        def slice(self, *a):
            return self._arr.slice(*a)

        def dictionary_encode(self):
            calls.append(len(self._arr))
            return real(self._arr)

    assert wc.maybe_dict_arrow(Spy(distinct), n) is None and calls == []
    idx, dictionary = wc.maybe_dict_arrow(Spy(few), n)
    assert calls == [n] and len(dictionary) == 300
    assert dictionary.take(pa.array(idx[:5])).to_pylist() == few[:5].to_pylist()
    # as many values as a dictionary may hold (n / 8), met in order:
    # the first n / 64 rows are all distinct, and the column ships raw
    # where a full encode would have kept it (a column drawn uniformly
    # over n / 8 values shows 88 % distinct there and is still encoded)
    assert wc.maybe_dict_arrow(Spy(eighth), n) is None
    rng = np.random.default_rng(3)
    uniform = pa.array([f"value {i}" for i in rng.integers(0, n // 8, n)])
    assert wc.maybe_dict_arrow(Spy(uniform), n) is not None
