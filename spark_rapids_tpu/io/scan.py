"""File scan execs: Parquet / ORC / CSV -> columnar batches.

Reference: GpuParquetScan.scala (PERFILE :1451 / COALESCING :824 /
MULTITHREADED :1145 reader modes; predicate pushdown via ParquetFilters
:217-271; schema clipping), GpuOrcScan.scala:63, GpuBatchScanExec.scala:465
(CSV).  TPU design: pyarrow decodes on host threads (prefetch pool ≈
MultiFileThreadPoolFactory, GpuParquetScan.scala:771-823) into Arrow record
batches; the device backend transfers them to HBM (``ColumnBatch.from_arrow``)
while the next files decode — the same I/O/compute overlap, with XLA compile
stability preserved by pow2 capacity/width bucketing.
"""
from __future__ import annotations

import concurrent.futures as cf
import glob as _glob
import os
from typing import Iterator, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.conf import ConfEntry, register
from spark_rapids_tpu.exec.core import ExecCtx, PlanNode
from spark_rapids_tpu.expr.core import Expression
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["FileScanExec", "ParquetScanExec", "OrcScanExec", "CsvScanExec"]

# per-format reader knobs, as in the reference (RapidsConf.scala:510,:548
# registers parquet-specific keys; orc/csv get their own here so setting
# one format's mode never changes another's behavior)
READER_TYPE = {
    fmt: register(ConfEntry(
        f"spark.rapids.sql.format.{fmt}.reader.type", "MULTITHREADED",
        "Reader mode: PERFILE, COALESCING, or MULTITHREADED (prefetching "
        "thread pool; reference RapidsConf.scala:510).",
        check=lambda v: v in ("PERFILE", "COALESCING", "MULTITHREADED"),
        check_doc="one of PERFILE|COALESCING|MULTITHREADED"))
    for fmt in ("parquet", "orc", "csv")
}
READER_THREADS = {
    fmt: register(ConfEntry(
        f"spark.rapids.sql.format.{fmt}.multiThreadedRead.numThreads", 4,
        "Prefetch threads per scan (reference RapidsConf.scala:548).",
        conv=int))
    for fmt in ("parquet", "orc", "csv")
}
BATCH_ROWS = register(ConfEntry(
    "spark.rapids.sql.reader.batchRows", 1 << 22,
    "Max rows per decoded batch (reference "
    "spark.rapids.sql.reader.batchSizeRows, RapidsConf.scala:370). The "
    "default is large on purpose: every device program launch pays "
    "host->device dispatch latency, "
    "so the TPU wants FEW LARGE batches — the reference's ~2GiB "
    "batchSizeBytes target (RapidsConf.scala:364) serves the same goal.",
    conv=int))


def _effective_batch_rows(schema: T.Schema, settings: dict) -> int:
    """Row cap honoring BOTH reader.batchRows and reader.batchSizeBytes
    (reference maxReadBatchSizeRows/maxReadBatchSizeBytes,
    RapidsConf.scala:370-386): bytes are converted to rows through a
    static per-row width estimate of the pruned schema."""
    from spark_rapids_tpu.conf import MAX_READER_BATCH_SIZE_BYTES
    rows = BATCH_ROWS.get(settings)
    byte_cap = MAX_READER_BATCH_SIZE_BYTES.get(settings)
    width = 1  # validity
    for f in schema:
        # ArrayType.np_dtype is the ELEMENT dtype — one element's
        # itemsize would undercount a row by up to max_len x, so arrays
        # use the variable-width estimate like strings and maps
        if f.data_type.np_dtype is None or \
                isinstance(f.data_type, T.ArrayType):
            width += 32          # offset + data estimate
        else:
            width += max(1, f.data_type.np_dtype.itemsize)
    # the floor protects only the bytes-derived cap (a degenerate byte
    # budget must not produce 0-row batches); an explicit row cap wins
    return min(rows, max(256, byte_cap // width))


def _expand_paths(paths) -> list[str]:
    if isinstance(paths, str):
        paths = [paths]
    out: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            for f in sorted(_glob.glob(os.path.join(p, "**", "*"),
                                       recursive=True)):
                if not os.path.isfile(f):
                    continue
                # hidden-component filter applies to the WHOLE relative
                # path, not just the basename: files under `_staging/`
                # (io/writer.py task-attempt dirs) or `_metadata/` trees
                # must be invisible to scans — uncommitted attempts are
                # not data (reference Spark's HadoopFsRelation hidden-
                # file convention)
                rel = os.path.relpath(f, p)
                if any(part.startswith(("_", "."))
                       for part in rel.split(os.sep)):
                    continue
                out.append(f)
        else:
            out.append(p)
    return out


def _to_arrow_filter(e: Expression):
    """Convert a pushable predicate to a pyarrow.dataset filter expression;
    None when not convertible (reference ParquetFilters pushdown,
    GpuParquetScan.scala:217).  Applied identically on both backends so the
    differential oracle stays valid."""
    import pyarrow.dataset as ds
    from spark_rapids_tpu.expr import predicates as P
    from spark_rapids_tpu.expr.core import Literal, UnresolvedAttribute

    def conv(n: Expression):
        if isinstance(n, UnresolvedAttribute):
            return ds.field(n.name)
        if isinstance(n, Literal):
            # ds.scalar keeps both operands pyarrow Expressions, so
            # literal-on-left comparisons don't fall into Python's
            # NotImplemented reflected-operator path
            return ds.scalar(n.value)
        return None

    if isinstance(e, P.And):
        l, r = (_to_arrow_filter(c) for c in e.children)
        return l & r if l is not None and r is not None else None
    if isinstance(e, P.Or):
        l, r = (_to_arrow_filter(c) for c in e.children)
        return l | r if l is not None and r is not None else None
    binmap = {P.EqualTo: "__eq__", P.LessThan: "__lt__",
              P.LessThanOrEqual: "__le__", P.GreaterThan: "__gt__",
              P.GreaterThanOrEqual: "__ge__"}
    for cls, meth in binmap.items():
        if isinstance(e, cls):
            l, r = conv(e.children[0]), conv(e.children[1])
            if l is not None and r is not None:
                return getattr(l, meth)(r)
            return None
    if isinstance(e, P.IsNull):
        c = conv(e.children[0])
        return c.is_null() if c is not None else None
    if isinstance(e, P.IsNotNull):
        c = conv(e.children[0])
        return ~c.is_null() if c is not None else None
    return None


class FileScanExec(PlanNode):
    """Base scan: files split across partitions; per-partition batches
    decoded on host (optionally via a prefetch pool) then H2D on the
    device backend."""

    format_name = "file"

    def __init__(self, paths, columns: Sequence[str] | None = None,
                 partitions: int | None = None,
                 pushdown: Expression | None = None,
                 string_width: int | None = None):
        super().__init__([])
        #: directory roots among the requested paths — kept so the
        #: optional commit-manifest CRC verification (verifyCrcOnScan)
        #: knows where a ``_MANIFEST.json`` could live
        self._roots = [p for p in
                       ([paths] if isinstance(paths, str) else list(paths))
                       if os.path.isdir(p)]
        self._files = _expand_paths(paths)
        if not self._files:
            raise FileNotFoundError(f"no input files in {paths}")
        self._columns = list(columns) if columns else None
        self._requested_parts = partitions
        self._pushdown = pushdown
        if pushdown is not None and _to_arrow_filter(pushdown) is None:
            # refuse silently-unapplied predicates: the planner only pushes
            # supported ones (reference keeps a residual FilterExec above)
            raise ValueError(f"predicate not pushable: {pushdown!r}")
        self._string_width = string_width
        #: AQE dynamic filters (plan/adaptive.py): (column, values, lo, hi)
        #: tuples derived from a small materialized join build side and
        #: pushed here before the probe stage launches (the DPP analog).
        #: Applied at the arrow layer alongside the static pushdown.
        self._runtime_filters: list[tuple] = []
        self._buckets_cache: dict[int, list[list[str]]] = {}
        #: stripes/row-groups skipped via statistics pruning (diagnostic)
        self.stripes_skipped = 0
        #: set by the planner when this scan's (files, columns, pushdown)
        #: fingerprint appears MORE THAN ONCE in the plan: consumers then
        #: share one materialization parked spillable in the catalog
        #: instead of re-decoding + re-transferring per instance (q28
        #: reads store_sales 12x; the reference's analog is Spark's
        #: ReuseExchange over identical scan-bearing subtrees)
        self.share_output = False
        #: how many consumptions the planner counted for the shared
        #: fingerprint (0 = unknown): the last one closes the parked
        #: entries so the shared table's catalog registration (and its
        #: host/disk spill storage) is released as soon as every branch
        #: has read it, not at catalog close
        self.share_consumers = 0
        full = self._read_schema()
        if self._columns:
            fields = [full.field(c) for c in self._columns]
            self._schema = T.Schema(fields)
        else:
            self._schema = full

    # -- per-format hooks --------------------------------------------------
    def _read_schema(self) -> T.Schema:
        raise NotImplementedError

    def _read_file(self, path: str, batch_rows: int = 1 << 16):
        """Return an iterator of pyarrow.RecordBatch for one file with
        column pruning + pushdown applied, chunked at ``batch_rows``."""
        raise NotImplementedError

    # -- PlanNode ----------------------------------------------------------
    @property
    def output_schema(self) -> T.Schema:
        return self._schema

    def num_partitions(self, ctx: ExecCtx) -> int:
        return self._requested_parts or min(len(self._files), 8)

    def _partition_files(self, ctx: ExecCtx, pid: int) -> list[str]:
        nparts = self.num_partitions(ctx)
        if nparts not in self._buckets_cache:
            # greedy size-balanced assignment (reference FilePartition
            # packing), computed once per partition count
            sizes = sorted(((os.path.getsize(f), f) for f in self._files),
                           reverse=True)
            buckets: list[list[str]] = [[] for _ in range(nparts)]
            loads = [0] * nparts
            for sz, f in sizes:
                i = loads.index(min(loads))
                buckets[i].append(f)
                loads[i] += sz
            self._buckets_cache[nparts] = buckets
        return self._buckets_cache[nparts][pid]

    def add_runtime_filter(self, column: str, values=None, lo=None,
                           hi=None) -> None:
        """Install a join-key filter derived at runtime (AQE dynamic
        filter): either an IN-set (``values``) or a min-max range
        (``lo``/``hi``).  Only ever narrows the scan's output — rows it
        removes are exactly rows the downstream join would drop — so it
        is safe to install between stages of a running query."""
        assert not self.share_output, \
            "dynamic filters must not narrow a shared scan"
        assert column in self._schema.names
        self._runtime_filters.append(
            (column, tuple(values) if values is not None else None, lo, hi))

    def _arrow_filter(self):
        """The combined arrow-level filter: static pushdown composed with
        any runtime (AQE dynamic) filters."""
        import pyarrow.dataset as ds
        filt = _to_arrow_filter(self._pushdown) \
            if self._pushdown is not None else None
        for column, values, lo, hi in self._runtime_filters:
            if values is not None:
                f = ds.field(column).isin(list(values))
            else:
                f = (ds.field(column) >= ds.scalar(lo)) & \
                    (ds.field(column) <= ds.scalar(hi))
            filt = f if filt is None else (filt & f)
        return filt

    def scan_fingerprint(self) -> tuple:
        """Structural identity: two scans with equal fingerprints read
        the same files, columns, and pushdown — identical output."""
        return (self.format_name, tuple(self._files),
                tuple(self._schema.names), repr(self._pushdown),
                tuple(self._runtime_filters),
                self._string_width, self._requested_parts)

    def snapshot_fingerprint(self) -> tuple:
        """Input-snapshot identity: (path, size, mtime_ns) per file, so
        two scans with equal structural AND snapshot fingerprints read
        byte-identical inputs — the invalidation half of every
        result-cache key (exec/result_cache.py).  Raises OSError when a
        file vanished; callers treat that as "no provable snapshot"."""
        out = []
        for f in self._files:
            st = os.stat(f)
            out.append((f, st.st_size, st.st_mtime_ns))
        return tuple(out)

    def _maybe_verify_manifests(self, ctx: ExecCtx) -> None:
        """When ``spark.rapids.io.write.transactional.verifyCrcOnScan``
        is on, recompute each scanned output directory's committed-file
        CRCs against its ``_MANIFEST.json`` before reading — a paranoia
        tier that turns silent post-commit corruption into a
        WriteIntegrityError.  Verified once per (exec, directory)."""
        from spark_rapids_tpu.io.writer import (MANIFEST_NAME,
                                                WRITE_VERIFY_CRC_ON_SCAN,
                                                verify_manifest)
        if not WRITE_VERIFY_CRC_ON_SCAN.get(ctx.conf.settings):
            return
        for root in self._roots:
            if os.path.exists(os.path.join(root, MANIFEST_NAME)):
                ctx.cached(("scan_crc_verified", os.path.abspath(root)),
                           lambda r=root: verify_manifest(r, full=True))

    def partition_iter(self, ctx: ExecCtx, pid: int) -> Iterator:
        self._maybe_verify_manifests(ctx)
        files = self._partition_files(ctx, pid)
        mode = READER_TYPE[self.format_name].get(ctx.conf.settings)
        rbs = self._decode_iter(ctx, files, mode)
        if ctx.is_device:
            if self.share_output:
                from spark_rapids_tpu.exec.result_cache import maybe_cache
                rc = maybe_cache(ctx.conf)
                if rc is not None:
                    try:
                        snap = self.snapshot_fingerprint()
                    except OSError:
                        snap = None
                    if snap is not None:
                        # cross-query path: one host-read + pack shared
                        # by every concurrent query over this table at
                        # this snapshot.  Raw device batches (no
                        # catalog parking — a cached fragment must not
                        # die with one query's catalog); the entry is
                        # consumer-pinned for the drain and governor-
                        # evictable when idle.
                        from spark_rapids_tpu.exec.recovery import \
                            conf_fingerprint
                        fkey = ("scan", self.scan_fingerprint(), snap,
                                conf_fingerprint(ctx.conf), pid)
                        entry = rc.fragment_entry(
                            fkey, lambda: list(self._staged_shared(rbs)),
                            lifecycle=ctx.cache.get("lifecycle"))
                        try:
                            get_registry().inc("scan.shared.handed_batches",
                                               len(entry.value))
                            yield from entry.value
                        finally:
                            rc.fragment_release(entry)
                        return
                from spark_rapids_tpu.memory.catalog import (
                    SpillableColumnarBatch, SpillPriority)
                key = ("scan_share", self.scan_fingerprint(), pid)
                parked = ctx.cached(
                    key,
                    lambda: [SpillableColumnarBatch(
                        b, ctx.catalog, SpillPriority.READ_SHUFFLE)
                        for b in self._staged_shared(rbs)])
                get_registry().inc("scan.shared.handed_batches", len(parked))
                for sb in parked:
                    b = sb.get()
                    # unpin immediately: the yielded pytree keeps the
                    # arrays alive for this consumer, while the catalog
                    # stays free to spill the parked copy between
                    # consumers (a held pin would make the whole shared
                    # table permanently unspillable — review finding)
                    sb.unpin()
                    yield b
                # consumer-counted close: once every sharing branch has
                # drained this partition, the parked entries are dead
                # weight in the catalog (formerly leaked until catalog
                # close — a session running many queries accumulated
                # every shared table in the spill tiers)
                if self.share_consumers:
                    ckey = ("scan_share_left", self.scan_fingerprint(), pid)
                    with ctx._lock:
                        left = ctx.cache.get(ckey, self.share_consumers) - 1
                        ctx.cache[ckey] = left
                        if left <= 0:
                            ctx.cache.pop(key, None)
                    if left <= 0:
                        for sb in parked:
                            sb.close()
                return
            yield from self._device_batches(rbs)
        else:
            for rb in rbs:
                if rb.num_rows == 0:
                    continue
                yield _arrow_to_host(rb, self._schema)

    def _staged_shared(self, rbs) -> Iterator:
        """``_device_batches`` of a shared scan, which stages the whole
        partition before its first consumer sees a batch: counted as
        ``scan.shared.staged_batches`` and ``scan.shared.parked_bytes``
        (device bytes held for replay).  Every consumer, the first too,
        counts what it is handed as ``scan.shared.handed_batches``:
        handed less staged were replays."""
        n = nbytes = 0
        for b in self._device_batches(rbs):
            n += 1
            nbytes += b.device_size_bytes()
            yield b
        get_registry().inc_many((("scan.shared.staged_batches", n),
                                 ("scan.shared.parked_bytes", nbytes)))

    def _device_batches(self, rbs) -> Iterator:
        """Stage-and-transfer pipeline: a worker thread encodes and
        device_puts batch k+1 while the consumer computes on batch k.
        Host-side staging (arrow decode + wire-codec encode) is the
        scan's serial CPU cost; overlapping it with device compute hides
        it entirely on multi-batch scans (reference: the multithreaded
        reader's decode-ahead does the same for the host half,
        GpuMultiFileReader.scala).  Window of 2 bounds host+HBM usage.

        Who owns which span and counter.  The ``scan-prefetch`` worker
        has no enclosing operator annotation, so its spans say whose work
        it does, and the three together are its life:
        ``starved@<Scan>Exec`` around obtaining each record batch (blocked
        on the reader pool's future, or decoding in-thread where the
        reader is pulled lazily: ``decode@<Scan>Exec`` then nests inside
        it), ``stage@<Scan>Exec`` per batch (encode + pack +
        ``device_put`` + the ``batch_unpack`` launch: of it ``h2d_put_s``
        is the put, ``program.batch_unpack.dispatch_s`` the launch), and
        the seconds it is blocked on the full queue, ``scan_backpressure_s``.
        The pulling thread's own ``<Scan>Exec`` annotation covers
        ``q.get()``, the wait; counted from inside as ``scan.wait_s``,
        the first get of a pipeline also as ``scan.first_batch_s`` beside
        ``scan.pipelines`` (a counter: a child annotation would empty the
        self time the annotation is read for).  The stage that sets the
        pace is the one that never waits."""
        import queue
        import threading
        import time
        q: queue.Queue = queue.Queue(maxsize=2)
        DONE = object()
        stop = threading.Event()
        reg = get_registry()
        stage = f"stage@{type(self).__name__}"
        starved = f"starved@{type(self).__name__}"

        def put(item) -> bool:
            t0 = time.perf_counter()
            try:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.25)
                        return True
                    except queue.Full:
                        continue
                return False
            finally:
                reg.inc("scan_backpressure_s", time.perf_counter() - t0)

        def worker():
            try:
                it = iter(rbs)
                while not stop.is_set():
                    with reg.span(starved):
                        rb = next(it, DONE)
                    if rb is DONE:
                        put(DONE)
                        return
                    if rb.num_rows == 0:
                        continue
                    with reg.span(stage):
                        b = ColumnBatch.from_arrow(
                            rb, string_widths=self._width_map(rb))
                    if not put(b):
                        return
            # enginelint: disable=RL001 (prefetch thread forwards the exception through the queue; the consumer re-raises it)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                put(e)

        t = threading.Thread(target=worker, daemon=True,
                             name="scan-prefetch")
        t.start()
        first = True
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                waited = time.perf_counter() - t0
                pairs = (("scan.wait_s", waited),)
                if first:
                    pairs += (("scan.first_batch_s", waited),
                              ("scan.pipelines", 1))
                    first = False
                reg.inc_many(pairs)
                if item is DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # consumer abandoned the scan (limit) or errored: release
            # the worker, which may be blocked on a full queue
            stop.set()
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break

    def _width_map(self, rb) -> dict[str, int] | None:
        if self._string_width is None:
            return None
        return {f.name: self._string_width for f in self._schema
                if isinstance(f.data_type, T.StringType)}

    def _decode_iter(self, ctx: ExecCtx, files: list[str], mode: str):
        """Arrow record batches of ``files``.  Every decode runs under
        a ``decode@<Scan>Exec`` span on the thread that does it: one per
        file on the prefetch pool's threads (MULTITHREADED, COALESCING),
        one per record batch where the reader is pulled lazily."""
        batch_rows = _effective_batch_rows(self._schema, ctx.conf.settings)
        reg = get_registry()
        decode = f"decode@{type(self).__name__}"

        def read_all(p):
            with reg.span(decode):
                return list(self._read_file(p, batch_rows))
        try:
            # process-wide scan-volume counter (mirrors the shuffle
            # plane's shuffle.fetch.bytes): on-disk bytes this partition
            # is about to decode, metered per tenant by obs/metering
            reg.inc("scan.bytes", float(
                sum(os.path.getsize(p) for p in files)))
        # enginelint: disable=RL001 (accounting must never fail a scan)
        except Exception:
            pass
        if mode == "MULTITHREADED" and len(files) > 1:
            # prefetch pool: decode next files while current is consumed,
            # bounded to a numThreads-file window so host memory stays
            # bounded (reference MultiFileCloudParquetPartitionReader
            # inflight limits)
            from collections import deque
            nthreads = READER_THREADS[self.format_name].get(ctx.conf.settings)
            with cf.ThreadPoolExecutor(max_workers=nthreads) as pool:
                window: deque = deque()
                it = iter(files)
                for p in it:
                    window.append(pool.submit(read_all, p))
                    if len(window) >= nthreads:
                        break
                for p in it:
                    yield from window.popleft().result()
                    window.append(pool.submit(read_all, p))
                while window:
                    yield from window.popleft().result()
        elif mode == "COALESCING" and len(files) > 1:
            # stitch many small files into larger batches (reference
            # MultiFileParquetPartitionReader): concat arrow tables then
            # re-chunk at the target size. Files yielding zero batches
            # (e.g. empty ORC/CSV parts) are skipped.
            import pyarrow as pa
            tables = []
            for p in files:
                bs = read_all(p)
                if bs:
                    t = pa.Table.from_batches(bs)
                    if t.num_rows:
                        tables.append(t)
            if not tables:
                return
            # combine_chunks is what actually merges: concat_tables keeps
            # per-file chunk boundaries and to_batches only splits chunks
            merged = pa.concat_tables(tables).combine_chunks()
            yield from merged.to_batches(max_chunksize=batch_rows)
        else:
            for p in files:
                it = self._read_file(p, batch_rows)
                while True:
                    with reg.span(decode):
                        rb = next(it, None)
                    if rb is None:
                        break
                    yield rb

    def node_desc(self) -> str:
        return (f"{type(self).__name__}[{self.format_name}, "
                f"{len(self._files)} files, cols={self._schema.names}]")


def _arrow_to_host(rb, schema: T.Schema):
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.host.batch import HostBatch, HostColumn
    cols = []
    for i, f in enumerate(schema):
        arr = rb.column(i)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_dictionary(arr.type):
            arr = arr.cast(pa.string())
        n = len(arr)
        validity = np.ones(n, np.bool_) if arr.null_count == 0 else \
            np.asarray(arr.is_valid(), dtype=np.bool_)
        if isinstance(f.data_type, T.StringType):
            data = np.array([x if x is not None else None
                             for x in arr.to_pylist()], dtype=object)
        elif isinstance(f.data_type, T.ArrayType):
            data = np.empty(n, dtype=object)
            for j, x in enumerate(arr.to_pylist()):
                data[j] = x
        elif isinstance(f.data_type, T.MapType):
            data = T.arrow_map_to_numpy(arr)
        else:
            data = T.arrow_fixed_to_numpy(arr, f.data_type)
        cols.append(HostColumn(data, validity, f.data_type))
    return HostBatch(cols, schema)


class ParquetScanExec(FileScanExec):
    """Parquet scan (reference GpuParquetScanBase:84-112): footer schema,
    column pruning, predicate pushdown at row-group granularity via
    pyarrow."""

    format_name = "parquet"

    def _read_schema(self) -> T.Schema:
        import pyarrow.parquet as pq
        return T.Schema.from_arrow(pq.read_schema(self._files[0]))

    def _read_file(self, path: str, batch_rows: int = 1 << 16):
        import pyarrow.dataset as ds
        dataset = ds.dataset(path, format="parquet")
        scanner = dataset.scanner(columns=self._schema.names,
                                  filter=self._arrow_filter(),
                                  batch_size=batch_rows)
        yield from scanner.to_batches()


class OrcScanExec(FileScanExec):
    """ORC scan (reference GpuOrcScanBase, GpuOrcScan.scala:63) with
    stripe pruning: stripes whose statistics cannot match the pushdown
    predicate are skipped without being read (reference SearchArgument
    stripe selection, GpuOrcScan.scala:240-245,327-360; statistics read
    by io/orc_meta.py since pyarrow doesn't expose them)."""

    format_name = "orc"

    def _read_schema(self) -> T.Schema:
        import pyarrow.orc as orc
        return T.Schema.from_arrow(orc.ORCFile(self._files[0]).schema)

    def _read_file(self, path: str, batch_rows: int = 1 << 16):
        import pyarrow as pa
        import pyarrow.orc as orc
        from spark_rapids_tpu.io import orc_meta
        f = orc.ORCFile(path)
        cols = self._schema.names
        # stripe pruning stays keyed on the STATIC pushdown; runtime
        # filters join at the residual row-level filter below
        filt = self._arrow_filter()
        stats = None
        if self._pushdown is not None:
            # flattened-stats index: root struct is column 0, fields
            # follow in FILE schema order — valid ONLY for flat schemas
            # (nested types interleave their children into the id
            # space, which would compare predicates against the wrong
            # column's statistics); nested files skip pruning entirely
            file_schema = f.schema
            if all(not pa.types.is_nested(fld.type)
                   for fld in file_schema):
                if not hasattr(self, "_orc_stats_cache"):
                    self._orc_stats_cache = {}
                if path not in self._orc_stats_cache:
                    self._orc_stats_cache[path] = \
                        orc_meta.stripe_column_stats(path)
                stats = self._orc_stats_cache[path]
                col_index = {n: i + 1
                             for i, n in enumerate(file_schema.names)}
        for stripe in range(f.nstripes):
            if stats is not None and stripe < len(stats) and \
                    not orc_meta.stripe_may_match(
                        self._pushdown, stats[stripe], col_index):
                self.stripes_skipped += 1
                continue
            out = f.read_stripe(stripe, columns=cols)
            # read_stripe returns columns in file order; re-select to the
            # requested order (RecordBatch or Table depending on version)
            if isinstance(out, pa.RecordBatch):
                out = pa.Table.from_batches([out])
            out = out.select(cols)
            if filt is not None:
                # residual row-level filter over surviving stripes (the
                # reference applies the same SearchArgument rows too)
                out = out.filter(filt)
            yield from out.to_batches(max_chunksize=batch_rows)


class CsvScanExec(FileScanExec):
    """CSV scan (reference GpuBatchScanExec.scala:465 Table.readCSV):
    host parse via pyarrow.csv with an explicit or inferred schema."""

    format_name = "csv"

    def __init__(self, paths, schema: T.Schema | None = None,
                 header: bool = True, delimiter: str = ",", **kw):
        self._explicit_schema = schema
        self._header = header
        self._delim = delimiter
        super().__init__(paths, **kw)

    def _csv_options(self):
        import pyarrow.csv as pc
        ropts = pc.ReadOptions()
        popts = pc.ParseOptions(delimiter=self._delim)
        copts = None
        if self._explicit_schema is not None:
            at = self._explicit_schema.to_arrow()
            if not self._header:
                ropts = pc.ReadOptions(column_names=[f.name for f in at])
            copts = pc.ConvertOptions(
                column_types={f.name: f.type for f in at})
        elif not self._header:
            # headerless without a schema: synthesize f0..fN names so the
            # first data row is NOT consumed as the header
            ropts = pc.ReadOptions(autogenerate_column_names=True)
        return ropts, popts, copts

    def _read_schema(self) -> T.Schema:
        if self._explicit_schema is not None:
            return self._explicit_schema
        import pyarrow.csv as pc
        ropts, popts, _ = self._csv_options()
        # streaming reader: schema comes from the first block without
        # decoding the whole file
        with pc.open_csv(self._files[0], read_options=ropts,
                         parse_options=popts) as reader:
            return T.Schema.from_arrow(reader.schema)

    def _read_file(self, path: str, batch_rows: int = 1 << 16):
        import pyarrow.csv as pc
        ropts, popts, copts = self._csv_options()
        tbl = pc.read_csv(path, read_options=ropts, parse_options=popts,
                          convert_options=copts)
        if self._columns:
            tbl = tbl.select(self._schema.names)
        filt = self._arrow_filter()
        if filt is not None:
            tbl = tbl.filter(filt)
        yield from tbl.to_batches(max_chunksize=batch_rows)
