"""Device columnar batch + Arrow host interop.

The TPU analog of the reference's ``ColumnarBatch`` of ``GpuColumnVector``
(GpuColumnVector.java:251,283 ``from(Table)``/``from(ColumnarBatch)``) plus
the host<->device transfer paths (HostColumnarToGpu.scala,
GpuColumnarToRowExec.scala).  Host-side canonical format is Arrow
(pyarrow.RecordBatch) instead of Spark InternalRow — TPU-first choice: Arrow
is the host decode format for Parquet/ORC/CSV and transfers to HBM without
per-row conversion.

Static-shape discipline: a batch has a power-of-two ``capacity`` (static,
part of the jit cache key) and a *device* scalar ``num_rows`` (traced), so
data-dependent operators (filter, join) stay inside one compiled program
without host round-trips; the true row count is only materialized at batch
boundaries (coalesce, collect).
"""
from __future__ import annotations

import functools as _functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import wirecodec as wc
from spark_rapids_tpu.columnar.column import DeviceColumn, round_string_width
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["ColumnBatch", "round_capacity"]

#: wire-codec default (overridable per call; SRT_WIRE_CODEC=0 disables
#: globally for debugging)
_CODEC_DEFAULT = __import__("os").environ.get("SRT_WIRE_CODEC", "1") != "0"
#: below this capacity transfers are latency-bound, not bandwidth-bound:
#: the codec would only multiply compiled unpack variants (a 6x
#: test-suite slowdown when engaged for every tiny batch)
_CODEC_MIN_CAPACITY = 2048


def _codec_auto(cap: int, codec: bool | None) -> bool:
    if codec is not None:
        return codec
    return _CODEC_DEFAULT and cap >= _CODEC_MIN_CAPACITY

_MIN_CAPACITY = 8

# ---------------------------------------------------------------------------
# Packed host->device transfer
#
# Every device_put pays a fixed per-call overhead, and per-column
# transfers made the q6 scan ~97 small device_puts per iteration.
# Packing every column leaf of a batch into ONE contiguous host buffer
# PER DTYPE collapses that to ~3-5 large puts, and a single jitted
# unpack program (cached per schema spec) slices the columns back out on
# device — one dispatch instead of dozens.  Reference analog: JCudfSerialization packs a whole table into
# one contiguous host buffer for the D2H/H2D path (SURVEY §2.2).
# ---------------------------------------------------------------------------


#: a raw string matrix larger than this is staged and put in row chunks
#: of this size (``_strings_to_row_chunks``), each a put of its own:
#: above every dictionary a batch ships (at most capacity / 8 rows)
_CHUNK_BYTES = 16 << 20
#: idle chunk buffers kept for the next batch, at most (1 GiB: eight
#: staged 2^20 x 128 matrices, more than a scan's window holds at once)
_CHUNKS_KEPT = 64
_idle_chunks: list = []
_idle_lock = __import__("threading").Lock()


def _chunk_buffer(rows: int, w: int) -> np.ndarray:
    """uint8[rows, w] (uninitialized, at most ``_CHUNK_BYTES``) over
    memory of the engine's own, used again batch after batch: a mapping
    goes back to ``_idle_chunks`` when the last array over it is gone —
    ours, the views made of it and the reference jax keeps until the
    transfer is done (on XLA:CPU, where the device array may alias the
    host's bytes, when that array is).  A buffer from ``np.empty`` comes
    from the heap of whichever thread stages, a new thread every scan:
    whether its pages were already there differed collect by collect,
    and the chip machine's kernel makes a fresh page dear (PERF.md
    Findings PR 42, refusal round)."""
    import mmap
    import weakref
    with _idle_lock:
        owner = _idle_chunks.pop() if _idle_chunks else None
    if owner is None:
        owner = mmap.mmap(-1, _CHUNK_BYTES)
    # the base of every view made of ``flat`` is ``flat`` itself (numpy
    # collapses a chain of views to the first array over a foreign
    # buffer), so it lives as long as any of them does
    flat = np.frombuffer(owner, dtype=np.uint8)
    weakref.finalize(flat, _chunk_idle, owner).atexit = False
    return flat[:rows * w].reshape(rows, w)


def _chunk_idle(owner) -> None:
    with _idle_lock:
        if len(_idle_chunks) < _CHUNKS_KEPT:
            _idle_chunks.append(owner)


class _PackBuilder:
    """Accumulates per-column host leaves — raw or wire-codec encoded
    (columnar/wirecodec.py) — and materializes them on device with one
    transfer per dtype group + one unpack/decode program."""

    def __init__(self, capacity: int, codec: bool = True):
        self.capacity = capacity
        self.codec = codec
        self.groups: dict[str, list] = {}   # dtype key -> host 1-D chunks
        self.offsets: dict[str, int] = {}   # dtype key -> elements so far
        self.leaves: list[tuple] = []       # ("g"|"w", ...) — see _add_leaf
        self.i64_params: list[int] = []
        self.col_specs: list[tuple] = []
        self.dict_gathers = 0               # gathers the unpack will hold
        self.string_bytes = 0               # raw string matrices, bytes
        # float64 columns, by how they travel (wire.double.*)
        self.doubles = {"scaled": 0, "raw": 0, "bytes": 0}

    def _add_leaf(self, arr: np.ndarray, own_put: bool = False) -> int:
        """Register one host buffer.

        Every dtype of width <= 4 bytes rides ONE shared uint32 word
        buffer (little-endian byte view; decode is a 32-bit bitcast,
        which lowers on TPU — only 64-bit bitcasts don't): a
        device_put has a fixed per-call overhead, so a batch ships
        as one u32 transfer plus (rare) i64/f64 raw leaves instead of
        one transfer per dtype.  Leaf records:
          ("g", gkey, elem_off, elem_size, shape)     — plain group
          ("w", word_off, word_size, dtype, shape, n) — u32-view leaf

        ``own_put``: a buffer so large that copying it into the shared
        word buffer would cost more than a transfer of its own (a row
        chunk of a raw string matrix: 150 ms of memcpy a 128 MB matrix
        against a put's fixed cost, PERF.md Findings PR 42) is a group
        by itself — shipped as it lies, decoded by a reshape.
        """
        dt = arr.dtype
        flat = np.ravel(arr)
        if own_put:
            gkey = f"{dt.str}@{len(self.leaves)}"
            self.groups[gkey] = [flat]
            self.leaves.append(("g", gkey, 0, flat.size, arr.shape))
            return len(self.leaves) - 1
        if dt.itemsize <= 4 and dt.kind in "uifb":
            by = flat.view(np.uint8)
            pad = (-by.size) % 4
            if pad:
                by = np.concatenate([by, np.zeros(pad, np.uint8)])
            words = by.view(np.uint32)
            woff = self.offsets.get("<u4", 0)
            self.groups.setdefault("<u4", []).append(words)
            self.offsets["<u4"] = woff + words.size
            self.leaves.append(("w", woff, words.size, dt.str, arr.shape,
                                flat.size))
            return len(self.leaves) - 1
        gkey = dt.str
        off = self.offsets.get(gkey, 0)
        self.groups.setdefault(gkey, []).append(flat)
        self.offsets[gkey] = off + flat.size
        self.leaves.append(("g", gkey, off, flat.size, arr.shape))
        return len(self.leaves) - 1

    def _add_i64(self, v: int) -> int:
        self.i64_params.append(int(v))
        return len(self.i64_params) - 1

    # -- column registration ------------------------------------------------
    def _val_desc(self, validity: np.ndarray | None) -> tuple:
        """Validity spec: all-valid columns ship nothing (decode derives
        the mask from num_rows); others ship 1 bit/row."""
        if validity is None or bool(validity.all()):
            return ("av",)
        return ("vbits", self._add_leaf(
            wc.pack_bits_host(validity.astype(np.uint8), 1, self.capacity)))

    def add_fixed(self, data: np.ndarray, validity: np.ndarray | None):
        """Fixed-width column from UNPADDED host data (+ validity)."""
        n = data.shape[0]
        if validity is not None and not validity.all():
            data = np.where(validity, data, data.dtype.type(0))
        if self.codec:
            desc = wc.encode_fixed(data, validity, self.capacity,
                                   self._add_leaf, self._add_i64)
        else:
            full = np.zeros((self.capacity,) + data.shape[1:],
                            dtype=data.dtype)
            full[:n] = data
            desc = ("raw", self._add_leaf(full))
        if data.dtype == np.float64:
            scaled = desc[0] == "fbits"
            self.doubles["scaled" if scaled else "raw"] += 1
            self.doubles["bytes"] += \
                self.capacity * (desc[2] if scaled else 64) // 8
        self.col_specs.append(("fixed", desc, self._val_desc(validity)))

    def add_var(self, matrix, lengths: np.ndarray,
                validity: np.ndarray | None, width: int):
        """Var-width (string/array) column from an [n, w] matrix + n
        lengths.  A matrix already at [capacity, w], its tail zero,
        ships as it is, and so does a list of row chunks that make one
        up (``_strings_to_row_chunks``): each chunk a put of its own,
        joined by the unpack program.  Null rows' bytes are zeroed by
        that program, not here."""
        cap = self.capacity
        chunks = matrix if isinstance(matrix, list) else [matrix]
        if sum(c.shape[0] for c in chunks) != cap:
            (short,) = chunks
            chunks = [np.zeros((cap, width), dtype=short.dtype)]
            chunks[0][:short.shape[0]] = short
        if validity is not None and not validity.all():
            lengths = np.where(validity, lengths, 0)
        if chunks[0].dtype == np.uint8:
            self.string_bytes += sum(c.nbytes for c in chunks)
        if len(chunks) == 1:
            mdesc = ("raw", self._add_leaf(chunks[0]))
        else:
            mdesc = ("rows", tuple(self._add_leaf(c, own_put=True)
                                   for c in chunks))
        if self.codec:
            ldesc = wc.encode_lengths(lengths, cap, width, self._add_leaf,
                                      self._add_i64)
        else:
            lfull = np.zeros(cap, dtype=np.int32)
            lfull[:lengths.shape[0]] = lengths
            ldesc = ("raw", self._add_leaf(lfull))
        self.col_specs.append(("var", mdesc, self._val_desc(validity),
                               ldesc))

    def add_dict_string(self, indices: np.ndarray,
                        dict_matrix: np.ndarray, dict_lengths: np.ndarray,
                        validity: np.ndarray | None):
        """Dictionary-encoded string column: bit-packed int32 indices +
        a pow2-row-padded dictionary byte matrix; decode selects among
        a small dictionary's rows and gathers from a larger one
        (wirecodec.decode_dict)."""
        cap = self.capacity
        k, w = dict_matrix.shape
        kp = round_capacity(max(k, 1))
        mfull = np.zeros((kp, w), dtype=np.uint8)
        mfull[:k] = dict_matrix
        lfull = np.zeros(kp, dtype=np.int32)
        lfull[:k] = dict_lengths
        if validity is not None and not validity.all():
            indices = np.where(validity, indices, 0)
        if not wc.dict_selects(kp):
            self.dict_gathers += 2
        idesc = wc.encode_fixed(indices, validity, cap, self._add_leaf,
                                self._add_i64)
        self.col_specs.append(("dict", idesc,
                               self._val_desc(validity),
                               self._add_leaf(mfull),
                               self._add_leaf(lfull)))

    # -- materialization ----------------------------------------------------
    def build(self, num_rows: int, schema: T.Schema) -> "ColumnBatch":
        """One device_put per dtype group — with the u32 word routing in
        :meth:`_add_leaf`, typically ONE transfer total — plus one jitted
        unpack+decode.  The i64 decode params (FOR bases) ship as u32
        words, the low halves then the high halves (a strided read would
        lower to a gather), and are rebuilt arithmetically on device (64-bit
        bitcasts don't lower on TPU; shifts do)."""
        nr = self._add_leaf(np.asarray([num_rows], dtype=np.int32))
        ip = -1
        if self.i64_params:
            p = np.asarray(self.i64_params, np.int64)
            ip = self._add_leaf(np.concatenate(
                [p & 0xFFFFFFFF, (p >> 32) & 0xFFFFFFFF]).astype(np.uint32))
        gkeys = tuple(sorted(self.groups))
        host_bufs = tuple(
            self.groups[k][0] if len(self.groups[k]) == 1
            else np.concatenate(self.groups[k]) for k in gkeys)
        t0 = time.perf_counter()
        dev_bufs = tuple(jax.device_put(b) for b in host_bufs)
        put_s = time.perf_counter() - t0
        # h2d_put_s: the host's seconds inside the puts — near zero where
        # the put returns before the copy is done (the link's seconds are
        # then no host span's), the copy itself where it blocks
        # unpack.leaves.*: how the program about to run decodes what was
        # shipped — leaves it reads by static slices and shifts, and the
        # gathers left (two a dictionary too large to select from)
        # scan.stage.string_bytes: bytes of the raw (not dictionary)
        # string matrices among them, at their staged width
        # wire.double.*: how the float64 columns travel — as scaled
        # integers the unpack rebuilds into the host's doubles
        # (wirecodec.rebuild_double), as 8-byte doubles, and the bytes
        # of both
        get_registry().inc_many((
            ("h2d_calls", len(host_bufs)),
            ("h2d_bytes", sum(b.nbytes for b in host_bufs)),
            ("h2d_put_s", put_s),
            ("unpack.leaves.static", len(self.leaves) - self.dict_gathers),
            ("unpack.leaves.gather", self.dict_gathers))
            + ((("scan.stage.string_bytes", self.string_bytes),)
               if self.string_bytes else ())
            + tuple((f"wire.double.{how}", moved)
                    for how, moved in self.doubles.items() if moved))
        spec = (self.capacity, gkeys, tuple(self.leaves),
                tuple(self.col_specs), nr, ip)
        arrays = _packed_unpack_cached(spec)(dev_bufs)
        cols = [DeviceColumn(d, v, f.data_type, ln)
                for f, (d, v, ln) in zip(schema, arrays[0])]
        return ColumnBatch(cols, arrays[1], schema,
                           known_rows=int(num_rows))


@_functools.lru_cache(maxsize=1024)
def _packed_unpack_cached(spec):
    cap, gkeys, leaves, col_specs, nr_idx, ip_idx = spec

    def unpack(bufs):
        import jax.numpy as jnp
        by_key = dict(zip(gkeys, bufs))

        def leaf(i):
            rec = leaves[i]
            if rec[0] == "g":
                _, gkey, off, size, shape = rec
                piece = jax.lax.slice(by_key[gkey], (off,), (off + size,))
                return piece.reshape(shape)
            _, woff, wsize, dtype_str, shape, nelem = rec
            words = jax.lax.slice(by_key["<u4"], (woff,), (woff + wsize,))
            dt = np.dtype(dtype_str)
            if dt.str == "<u4":
                arr = words
            elif dt.kind == "b":
                arr = jax.lax.bitcast_convert_type(
                    words, jnp.uint8).reshape(-1)[:nelem] != 0
                return arr.reshape(shape)
            elif dt.itemsize == 4:
                arr = jax.lax.bitcast_convert_type(words, dt)
            else:
                arr = jax.lax.bitcast_convert_type(
                    words, dt).reshape(-1)[:nelem]
            return arr.reshape(shape)

        nr = leaf(nr_idx)[0]
        i64p = None
        if ip_idx >= 0:
            pw = leaf(ip_idx)
            k = pw.shape[0] // 2
            i64p = (pw[k:].astype(jnp.int64) << 32) | pw[:k].astype(jnp.int64)
        out_cols = []
        for cspec in col_specs:
            kind = cspec[0]
            validity = wc.decode_validity(cspec[2], leaf, cap, nr)
            if kind == "fixed":
                data = wc.decode_data(cspec[1], leaf, i64p, cap)
                zero = jnp.zeros((), data.dtype)
                data = jnp.where(validity, data, zero)
                out_cols.append((data, validity, None))
            elif kind == "var":
                data = wc.decode_data(cspec[1], leaf, i64p, cap)
                lens = wc.decode_data(cspec[3], leaf, i64p, cap)
                data = jnp.where(validity[:, None], data,
                                 jnp.zeros((), data.dtype))
                lens = jnp.where(validity, lens, 0)
                out_cols.append((data, validity, lens))
            else:  # dict string
                idx = wc.decode_data(cspec[1], leaf, i64p, cap)
                data, lens = wc.decode_dict(leaf(cspec[3]), leaf(cspec[4]),
                                            idx)
                data = jnp.where(validity[:, None], data,
                                 jnp.zeros((), data.dtype))
                lens = jnp.where(validity, lens, 0)
                out_cols.append((data, validity, lens))
        return tuple(out_cols), nr

    # through the shared-jit wrapper: the io scan worker compiles NEW
    # unpack programs mid-query, which must serialize against every
    # other engine compile/dispatch on CPU (compile_cache guard); bound
    # lazily — columnar/ sits below exec/
    from spark_rapids_tpu.exec.compile_cache import instrument
    return instrument(jax.jit(unpack), "batch_unpack")

# Arrow<->device conversions are serialized AND pyarrow's internal pool
# is pinned to one thread (runtime.pin_arrow_threads): pyarrow compute
# kernels running on their multi-threaded pool concurrently with jax CPU
# execution segfault intermittently.  The lock costs little —
# conversions are host-side staging; device programs still overlap.
_ARROW_LOCK = __import__("threading").Lock()


def _arrow_guard():
    from spark_rapids_tpu.runtime import pin_arrow_threads
    pin_arrow_threads()
    return _ARROW_LOCK


def round_capacity(n: int) -> int:
    """Round a row count up to the compilation capacity bucket (pow2)."""
    c = _MIN_CAPACITY
    while c < n:
        c <<= 1
    return c


@jax.tree_util.register_pytree_node_class
class ColumnBatch:
    """An immutable device batch: tuple of DeviceColumn + device num_rows.

    ``known_rows`` is an OPTIONAL host-side int mirror of ``num_rows``,
    set where the count is already on host (the pack builder, shuffle
    map-writers, OOM split halves) — metrics/tracing read it without a
    D2H sync.  It is metadata only: deliberately excluded from both the
    pytree leaves (it must not be traced) and the aux treedef (a static
    per-count treedef would retrigger jit compilation per row count), so
    batches that cross a jit boundary correctly come back with
    known_rows=None (their count is whatever the program computed).
    """

    __slots__ = ("columns", "num_rows", "schema", "known_rows")

    def __init__(self, columns: Sequence[DeviceColumn], num_rows: jax.Array,
                 schema: T.Schema, known_rows: int | None = None):
        self.columns = tuple(columns)
        self.num_rows = num_rows
        self.schema = schema
        self.known_rows = known_rows

    def tree_flatten(self):
        return (self.columns, self.num_rows), (self.schema,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, num_rows = children
        return cls(columns, num_rows, aux[0])

    # ------------------------------------------------------------------
    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        return _MIN_CAPACITY

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def row_mask(self) -> jax.Array:
        """bool[capacity]: True for real (non-padding) rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def with_columns(self, columns: Sequence[DeviceColumn],
                     schema: T.Schema) -> "ColumnBatch":
        return ColumnBatch(columns, self.num_rows, schema,
                           known_rows=self.known_rows)

    def host_num_rows(self, op: str = "fetch@ColumnBatch.host_num_rows") \
            -> int:
        """Materialize the row count on host (sync point); cached into
        ``known_rows`` so a later metrics read is free.  ``op`` names
        the fetch's span (``fetch@<Operator>Exec`` where the caller is
        an operator)."""
        if self.known_rows is None:
            # bound at call time: columnar/ sits below exec/
            from spark_rapids_tpu.exec.core import fetch_to_host
            self.known_rows = int(fetch_to_host(self.num_rows, op))
        return self.known_rows

    # ------------------------------------------------------------------
    # Arrow interop
    # ------------------------------------------------------------------
    @staticmethod
    def from_arrow(rb, capacity: int | None = None,
                   string_widths: dict[str, int] | None = None,
                   codec: bool | None = None) -> "ColumnBatch":
        """Build a device batch from a pyarrow.RecordBatch (H2D transfer)."""
        with _arrow_guard():
            return ColumnBatch._from_arrow_locked(rb, capacity,
                                                  string_widths, codec)

    @staticmethod
    def _from_arrow_locked(rb, capacity=None, string_widths=None,
                           codec=None):
        import pyarrow as pa
        n = rb.num_rows
        cap = capacity or round_capacity(max(n, 1))
        schema = T.Schema.from_arrow(rb.schema)
        pack = _PackBuilder(cap, _codec_auto(cap, codec))
        for i, field in enumerate(schema):
            arr = rb.column(i)
            if isinstance(arr, pa.ChunkedArray):
                arr = arr.combine_chunks()
            validity = T.arrow_validity_numpy(arr)
            if isinstance(field.data_type, T.StringType):
                w = (string_widths or {}).get(field.name)
                dic = wc.maybe_dict_arrow(arr, n) if pack.codec else None
                if dic is not None:
                    idx, dictionary = dic
                    # honor the scan's width hint so batches across
                    # files keep one compiled width bucket
                    dm, dlens = _strings_to_matrix(dictionary, w)
                    pack.add_dict_string(idx, dm, dlens, validity)
                else:
                    chunks, lens = _strings_to_row_chunks(arr, w, cap)
                    pack.add_var(chunks, lens, validity,
                                 chunks[0].shape[1])
            elif isinstance(field.data_type, T.ArrayType):
                m, lens = _lists_to_matrix(arr, field.data_type)
                pack.add_var(m, lens, validity,
                             m.shape[1] if m.ndim == 2 else 1)
            else:
                data = T.arrow_fixed_to_numpy(arr, field.data_type)
                pack.add_fixed(data, validity)
        return pack.build(n, schema)

    def to_arrow(self):
        """Copy the batch back to host as a pyarrow.RecordBatch (D2H).

        Leaves are materialized as OWNED numpy copies: pyarrow keeps
        references to the buffers it is handed, and zero-copy views into
        jax device buffers can dangle once the runtime reclaims them
        (observed as a segfault under the virtual multi-device CPU mesh).
        """
        import pyarrow as pa
        # one device_get for num_rows + leaves (one round trip, not two)
        from spark_rapids_tpu.exec.core import fetch_to_host
        n, host_cols = fetch_to_host(
            (self.num_rows,
             [(c.data, c.validity, c.lengths) for c in self.columns]),
            "fetch@ColumnBatch.to_arrow")
        n = int(n)
        with _arrow_guard():
            return self._to_arrow_locked(n, host_cols)

    def _to_arrow_locked(self, n, host_cols):
        import pyarrow as pa
        # slice to the real rows BEFORE the ownership copy: copying the
        # full pow2-capacity buffers wastes D2H-path memory traffic
        host_cols = [tuple(None if a is None else np.array(a[:n], copy=True)
                           for a in t) for t in host_cols]
        arrays = []
        for field, (data, validity, lengths) in zip(self.schema, host_cols):
            v = np.asarray(validity[:n], dtype=np.bool_)
            mask = ~v  # arrow mask: True = null
            if isinstance(field.data_type, T.StringType):
                bm = np.asarray(data[:n])
                lens = np.asarray(lengths[:n])
                py = [None if not v[i] else bytes(bm[i, :lens[i]]).decode("utf-8", "replace")
                      for i in range(n)]
                arrays.append(pa.array(py, type=pa.string()))
            elif isinstance(field.data_type, T.ArrayType):
                m = np.asarray(data[:n])
                lens = np.asarray(lengths[:n])
                py = [None if not v[i] else m[i, :lens[i]].tolist()
                      for i in range(n)]
                arrays.append(pa.array(py, type=T.to_arrow(field.data_type)))
            else:
                d = np.asarray(data[:n])
                at = T.to_arrow(field.data_type)
                if isinstance(field.data_type, T.TimestampType):
                    arrays.append(pa.Array.from_buffers(
                        at, n, pa.array(d.astype("int64"), mask=mask).buffers()))
                elif isinstance(field.data_type, T.DateType):
                    arrays.append(pa.Array.from_buffers(
                        at, n, pa.array(d.astype("int32"), mask=mask).buffers()))
                else:
                    arrays.append(pa.array(d, type=at, mask=mask))
        return pa.RecordBatch.from_arrays(arrays, schema=self.schema.to_arrow())

    def device_size_bytes(self) -> int:
        """Approximate HBM footprint of this batch."""
        total = 0
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            total += c.validity.size
            if c.lengths is not None:
                total += c.lengths.size * 4
        return total


def _lists_to_matrix(arr, dtype):
    """Arrow list array -> (elem[n, w] padded matrix, int32[n] lengths).
    Same static-shape layout as strings; element nulls are rejected
    (they have no device representation — such columns stay on host)."""
    import pyarrow as pa
    arr = arr.cast(pa.large_list(T.to_arrow(dtype.element_type)))
    n = len(arr)
    offsets = np.frombuffer(arr.buffers()[1], dtype=np.int64, count=n + 1,
                            offset=arr.offset * 8)
    # trim values to THIS slice's offset window — .values spans the
    # whole child buffer and would reject element nulls outside the
    # slice; slicing (not flatten) keeps offset alignment even if a
    # null list row had a nonzero offset span
    values = arr.values.slice(int(offsets[0]),
                              int(offsets[-1] - offsets[0]))
    if values.null_count:
        raise ValueError("arrays with null elements have no device "
                         "representation")
    offsets = offsets - offsets[0]
    flat = T.arrow_fixed_to_numpy(values, dtype.element_type)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    if arr.null_count:
        valid = np.asarray(arr.is_valid(), dtype=np.bool_)
        lens = np.where(valid, lens, 0)
    maxw = int(lens.max()) if n else 0
    w = round_string_width(max(maxw, 1))
    out = np.zeros((n, w), dtype=dtype.np_dtype)
    if n and flat.size:
        pos = offsets[:-1, None] + np.arange(w, dtype=np.int64)[None, :]
        mask = np.arange(w, dtype=np.int32)[None, :] < lens[:, None]
        out[mask] = flat[np.minimum(pos[mask], flat.size - 1)]
    return out, lens


def _string_rows(arr, width: int | None):
    """``(data, starts, lens, w)`` of an Arrow string array: its flat
    bytes (None where it has none), where each row starts in them, the
    rows' byte lengths (a null's is 0) and the width bucket."""
    import pyarrow as pa
    if not (pa.types.is_string(arr.type)
            or pa.types.is_large_string(arr.type)):
        arr = arr.cast(pa.large_string())
    n = len(arr)
    odt = np.int64 if pa.types.is_large_string(arr.type) else np.int32
    buffers = arr.buffers()
    # [validity, offsets, data]
    if n and buffers[1] is not None:
        offsets = np.frombuffer(buffers[1], dtype=odt, count=n + 1,
                                offset=arr.offset * odt().itemsize)
    else:
        offsets = np.zeros(n + 1, odt)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
    # nulls contribute zero-length
    if arr.null_count:
        valid = np.asarray(arr.is_valid(), dtype=np.bool_)
        lens = np.where(valid, lens, 0)
    maxw = int(lens.max()) if n else 0
    w = width or round_string_width(max(maxw, 1))
    if maxw > w:
        raise ValueError(f"string width {maxw} exceeds bucket {w}")
    data = np.frombuffer(buffers[2], dtype=np.uint8) \
        if maxw and buffers[2] is not None else None
    return data, offsets[:-1], lens, w


def _fill_rows(out: np.ndarray, data, starts, lens) -> np.ndarray:
    """Fill uint8[rows, w] ``out`` (uninitialized): one memcpy and one
    memset a row (native ``pad_rows``), every byte written once; rows
    past ``len(lens)`` are zeros."""
    if data is None:
        out[...] = 0
    else:
        from spark_rapids_tpu.native import pad_rows
        pad_rows(data, starts, lens, out)
    return out


def _strings_to_matrix(arr, width: int | None = None):
    """Arrow string array -> (uint8[n, w] padded bytes, int32[n]
    lengths), by a row-wise copy from the Arrow offsets: no index
    matrix, no mask."""
    data, starts, lens, w = _string_rows(arr, width)
    return _fill_rows(np.empty((len(lens), w), np.uint8),
                      data, starts, lens), lens


def _strings_to_row_chunks(arr, width: int | None, rows: int):
    """``_strings_to_matrix`` at ``rows`` >= n rows (a batch's capacity,
    the tail zero), the matrix in row chunks of at most
    ``_CHUNK_BYTES``: ``(chunks, lengths)``.  A fact-sized
    matrix in one piece is 128 MB of fresh pages a batch (an allocation
    that large is mapped anew every time, and the chip machine's kernel
    makes a fresh page dear: PERF.md Findings PRs 41, 42); its chunks
    lie in buffers that are used again (``_chunk_buffer``), and each
    ships by a put of its own, with no copy into a shared buffer.  A
    matrix of one chunk is copied into that buffer, and is the heap's."""
    data, starts, lens, w = _string_rows(arr, width)
    step = max(_CHUNK_BYTES // w, 1)
    make = _chunk_buffer if rows > step \
        else lambda r, w: np.empty((r, w), np.uint8)
    return [_fill_rows(make(min(step, rows - lo), w), data,
                       starts[lo:lo + step], lens[lo:lo + step])
            for lo in range(0, rows, step)], lens
