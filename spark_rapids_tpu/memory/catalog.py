"""3-tier buffer catalog: HBM -> host arena (C++) -> disk.

Reference mapping (SURVEY.md §2.2, §3.5):
  * `BufferCatalog` = RapidsBufferCatalog (RapidsBufferCatalog.scala:34)
    + the three RapidsBufferStore tiers wired device->host->disk
    (:136-137), with acquire/release refcounts and priority-ordered
    synchronous spill (RapidsBufferStore.synchronousSpill:147-200).
  * `SpillPriority` = SpillPriorities.scala:26-60 bands.
  * `SpillableColumnarBatch` = SpillableColumnarBatch.scala:28 — hold
    data across iterator steps without pinning HBM.
  * `run_with_spill_retry` = DeviceMemoryEventHandler.onAllocFailure:
    PJRT exposes no RMM-style alloc callback, so the hook is a catch of
    XLA RESOURCE_EXHAUSTED around dispatch -> spill -> retry.
  * `DeviceSemaphore` = GpuSemaphore.scala (concurrent tasks per chip).

TPU-first storage design: a spilled batch's leaves are packed into ONE
contiguous slice of the native host arena (native/arena.cpp) so the
host tier has real pooling and the disk tier writes one file per
buffer; restore rebuilds the ColumnBatch pytree from zero-copy numpy
views of the slice.
"""
from __future__ import annotations

import errno
import functools
import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnBatch
from spark_rapids_tpu.conf import ConfEntry, register, _bool
from spark_rapids_tpu.shuffle.compression import get_codec

__all__ = ["BufferCatalog", "SpillPriority", "SpillableColumnarBatch",
           "SpillCorruptionError", "DeviceSemaphore", "run_with_spill_retry"]

#: spill-file integrity checksum: CRC32C when the C binding is present,
#: zlib's CRC32 otherwise (same ladder as the TCP frame checksum in
#: shuffle/tcp.py — the disk tier must carry its own integrity just
#: like the DCN plane does)
try:
    import google_crc32c as _gcrc32c

    _SPILL_CRC_NAME, _spill_crc = "crc32c", _gcrc32c.value
except ImportError:  # pragma: no cover - env without the binding
    _SPILL_CRC_NAME, _spill_crc = "crc32", zlib.crc32


class SpillCorruptionError(RuntimeError):
    """A spilled buffer's disk read-back failed its checksum (or its
    storage was invalidated): the DATA is lost, not the operation.
    Consumers that can recompute the buffer from lineage (the shuffle
    store -> exec/recovery.py) translate this into MapOutputLostError;
    everything else fails with a diagnosable error instead of silently
    consuming flipped bytes."""


def _sidecar(path: str) -> str:
    return path + ".crc"


def _timed_spill(fn):
    """Record each spill/unspill movement's wall time in the
    ``spill.io_seconds`` histogram (failures included: a slow corrupt
    read-back is still I/O the query waited on)."""
    @functools.wraps(fn)
    def inner(self, *args, **kwargs):
        t0 = time.perf_counter()
        try:
            return fn(self, *args, **kwargs)
        finally:
            from spark_rapids_tpu.obs.registry import get_registry
            get_registry().observe("spill.io_seconds",
                                   time.perf_counter() - t0)
    return inner


def _write_sidecar(path: str, value: int, nbytes: int) -> None:
    with open(_sidecar(path), "w") as f:
        f.write(f"{_SPILL_CRC_NAME}:{value & 0xFFFFFFFF:08x}:{nbytes}")


def _verify_sidecar(path: str, data) -> None:
    """Check ``data`` (bytes-like) against the spill file's sidecar;
    raises SpillCorruptionError on mismatch or a missing/garbled
    sidecar — an unverifiable spill file is treated as lost, never
    trusted."""
    try:
        with open(_sidecar(path)) as f:
            algo, want_hex, want_len = f.read().strip().split(":")
    except (OSError, ValueError) as e:
        raise SpillCorruptionError(
            f"spill file {path} has no readable checksum sidecar: "
            f"{type(e).__name__}: {e}") from e
    if algo != _SPILL_CRC_NAME:
        raise SpillCorruptionError(
            f"spill file {path} was checksummed with {algo!r} but this "
            f"process verifies {_SPILL_CRC_NAME!r}")
    got = _spill_crc(bytes(data)) & 0xFFFFFFFF
    if int(want_len) != len(data) or got != int(want_hex, 16):
        raise SpillCorruptionError(
            f"spill file {path} failed its {algo} read-back check "
            f"(wrote {want_hex}/{want_len}B, read {got:08x}/"
            f"{len(data)}B): corrupted on disk")


def _is_enospc(e: OSError) -> bool:
    return e.errno in (errno.ENOSPC, errno.EDQUOT)


class _SpillDiskFull(RuntimeError):
    """Internal: the disk tier is full; the buffer stays where it is and
    the spill pass returns what it already freed, letting the OOM
    split-and-retry scope (memory/retry.py) absorb the pressure."""


DEVICE_SPILL_LIMIT = register(ConfEntry(
    "spark.rapids.memory.tpu.spillStoreSize", 2 << 30,
    "Soft HBM budget for catalog-registered batches; adding past it "
    "spills lowest-priority buffers to host (reference "
    "spark.rapids.memory.gpu pool fraction, RapidsConf.scala:269+)."))
HOST_SPILL_LIMIT = register(ConfEntry(
    "spark.rapids.memory.host.spillStorageSize", 1 << 30,
    "Host arena size for spilled buffers (reference "
    "RapidsConf.scala:330)."))
MEMORY_DEBUG = register(ConfEntry(
    "spark.rapids.memory.debug", False,
    "Leak tracking: warn with per-buffer detail when catalog buffers "
    "are still registered at close (reference "
    "spark.rapids.memory.gpu.debug -> cudf MemoryCleaner, "
    "RapidsConf.scala:288).", conv=_bool))
SPILL_DIR = register(ConfEntry(
    "spark.rapids.memory.spill.dir", "",
    "Directory for disk-tier spill files (one file per buffer plus a "
    ".crc checksum sidecar). Empty = $TMPDIR/srt_spill_<pid>. Files "
    "are fsynced before the catalog entry flips to tier=disk and "
    "deleted on restore, invalidation, and catalog close (reference "
    "spark.local.dir placement of RapidsDiskStore block files)."))
SPILL_COMPRESSION_CODEC = register(ConfEntry(
    "spark.rapids.memory.spill.compression.codec", "none",
    "Codec for disk-tier spill files: none, lz4 (native C++ block codec, "
    "native/lz4.cpp) or zstd — the shuffle codec ladder "
    "(shuffle/compression.py) applied to the RapidsDiskStore analog. "
    "The .crc sidecar is computed over the COMPRESSED bytes, so "
    "read-back verifies exactly what the disk stored; a corrupt or "
    "truncated compressed spill degrades into the existing lost-tier "
    "path (SpillCorruptionError -> lineage recompute where available), "
    "never a decompressor crash. (ref RapidsConf.scala:729)",
    check=lambda v: v in ("none", "lz4", "zstd"),
    check_doc="must be none|lz4|zstd"))


class SpillPriority:
    """Lower spills first (reference SpillPriorities.scala:26-60)."""
    SHUFFLE_OUTPUT = 0
    READ_SHUFFLE = 100
    ACTIVE_BATCH = 1 << 30


@dataclass
class _Entry:
    buffer_id: int
    priority: int
    size: int
    refcount: int = 0
    tier: str = "device"            # device | host | disk | lost
    batch: ColumnBatch | None = None
    # host/disk tier state
    treedef: Any = None
    leaf_meta: list | None = None   # (dtype, shape, nbytes, offset_in_slice)
    arena_offset: int | None = None
    disk_path: str | None = None
    disk_codec: str | None = None   # codec the disk file was written with


class BufferCatalog:
    """id -> buffer map with acquire/refcount + tiered spill."""

    def __init__(self, device_limit: int | None = None,
                 host_limit: int | None = None,
                 spill_dir: str | None = None, conf=None):
        settings = getattr(conf, "settings", {}) if conf is not None else {}
        self._lock = threading.RLock()
        self._entries: dict[int, _Entry] = {}
        self._next_id = 0
        self._debug = MEMORY_DEBUG.get(settings)
        if device_limit:
            self.device_limit = device_limit
        elif DEVICE_SPILL_LIMIT.key in settings:
            self.device_limit = DEVICE_SPILL_LIMIT.get(settings)
        else:
            # no explicit budget: size from the initialized device's HBM
            # via allocFraction/reserve (reference computeRmmInitSizes,
            # GpuDeviceManager.scala:159-194); conf default otherwise
            from spark_rapids_tpu.device import device_pool_limit
            self.device_limit = (device_pool_limit()
                                 or DEVICE_SPILL_LIMIT.get(settings))
        self.device_used = 0
        # the C++ arena maps its full capacity up front (~0.3s for 1GB),
        # so it is created on FIRST SPILL, not per catalog/query — unless
        # spark.rapids.memory.pinnedPool.size asks for an eager staging
        # pool, which is a PROCESS-level singleton (reference
        # allocatePinnedMemory: once per executor, GpuDeviceManager.scala:
        # 264-270)
        self._host_limit = host_limit or HOST_SPILL_LIMIT.get(settings)
        self._arena_obj = None
        self._arena_shared = False
        from spark_rapids_tpu.conf import PINNED_POOL_SIZE
        pinned = PINNED_POOL_SIZE.get(settings)
        if pinned and pinned > 0:
            from spark_rapids_tpu.runtime import get_pinned_arena
            # borrower=self: this catalog holds numpy views into the
            # arena, so a later larger request must park (not destroy)
            # this mapping until the catalog is collected
            self._arena_obj = get_pinned_arena(
                max(self._host_limit, pinned), borrower=self)
            self._arena_shared = True
        self._spill_dir_base = spill_dir or SPILL_DIR.get(settings) or None
        self._spill_dir_made: str | None = None
        self._spill_codec = get_codec(SPILL_COMPRESSION_CODEC.get(settings))
        # deterministic fault plan (spark.rapids.test.faults): the
        # memory.oom point drives run_with_spill_retry exactly like a
        # real XLA RESOURCE_EXHAUSTED; None when unset (inert)
        from spark_rapids_tpu.faults import FaultRegistry
        self.faults = FaultRegistry.from_conf(settings)
        # query lifecycle handle (exec/lifecycle.py), bound by ExecCtx:
        # spill I/O checks it so a cancelled query stops pushing bytes
        # between tiers instead of finishing a multi-buffer spill sweep
        self.lifecycle = None
        # cross-query memory governor (memory/governor.py), bound by
        # ExecCtx via maybe_register when the governor conf is on: the
        # catalog mirrors every device-byte move into the per-query
        # ledger so arbitration and admission shedding see who holds
        # HBM.  None (the default) keeps the catalog query-blind —
        # byte-identical to the pre-governor engine
        self.governor = None
        self.query_id = None
        self.metrics = {"device_spills": 0, "host_spills": 0,
                        "bytes_spilled_to_host": 0,
                        "bytes_spilled_to_disk": 0,
                        # OOM retry framework (memory/retry.py):
                        # attempts re-run after an exhaustion, inputs
                        # halved when spill freed nothing, and the HBM
                        # pressure high-watermark of registered batches
                        "oom_retries": 0, "oom_splits": 0,
                        "device_bytes_peak": 0,
                        # disk-tier integrity + stage recovery
                        # (exec/recovery.py bumps the recovery counters;
                        # they live here because the catalog is the one
                        # metrics sink the bench runner already exports)
                        "spill_crc_failures": 0, "spill_enospc": 0,
                        # disk-tier compression: bytes before/after the
                        # spill codec (zero deltas when codec=none)
                        "spill_raw_bytes": 0, "spill_compressed_bytes": 0,
                        "stage_recomputes": 0, "map_outputs_recomputed": 0,
                        "recovery_wall_s": 0.0}
        # surface catalog counters in the process metrics registry as
        # pull gauges (weakref-bound; dropped again in close())
        from spark_rapids_tpu.obs.registry import get_registry
        self._reg_source = get_registry().register_object_source(
            f"catalog.{id(self):x}", self)

    def occupancy(self) -> dict:
        """Device-tier occupancy alone (no per-entry walk): the cheap
        high-rate probe the HBM occupancy sampler (obs/profile.py)
        reads when no governor ledger is available."""
        with self._lock:
            return {"device_used": self.device_used,
                    "device_limit": self.device_limit}

    def tier_occupancy(self) -> dict:
        """Buffers/bytes currently registered per spill tier — the
        at-a-glance memory picture diagnostics bundles carry."""
        occ: dict[str, dict] = {}
        with self._lock:
            for e in self._entries.values():
                t = occ.setdefault(e.tier, {"buffers": 0, "bytes": 0})
                t["buffers"] += 1
                t["bytes"] += e.size
            occ["_totals"] = {"device_used": self.device_used,
                              "device_limit": self.device_limit}
        return occ

    @property
    def _arena(self):
        if self._arena_obj is None:
            from spark_rapids_tpu.native import HostArena
            self._arena_obj = HostArena(self._host_limit)
        return self._arena_obj

    @property
    def _spill_dir(self) -> str:
        if self._spill_dir_made is None:
            d = self._spill_dir_base or os.path.join(
                os.environ.get("TMPDIR", "/tmp"), f"srt_spill_{os.getpid()}")
            os.makedirs(d, exist_ok=True)
            self._spill_dir_made = d
        return self._spill_dir_made

    def _gov_account(self, delta: int) -> None:
        """Mirror a device_used move into the governor's per-query
        ledger (no-op when ungoverned).  Called at every site that
        mutates ``device_used`` so the ledger can never drift from
        catalog occupancy."""
        gov = self.governor
        if gov is not None:
            gov.account(self, delta)

    def _gov_pinned(self, delta: int) -> None:
        gov = self.governor
        if gov is not None:
            gov.account_pinned(self, delta)

    # -- registration --------------------------------------------------
    def add_batch(self, batch: ColumnBatch, priority: int) -> int:
        """Register a device batch; may synchronously spill others."""
        size = batch.device_size_bytes()
        with self._lock:
            bid = self._next_id
            self._next_id += 1
            self._entries[bid] = _Entry(bid, priority, size, batch=batch)
            self.device_used += size
            self._gov_account(size)
            if self.device_used > self.metrics["device_bytes_peak"]:
                self.metrics["device_bytes_peak"] = self.device_used
            if self.device_used > self.device_limit:
                self._spill_device_locked(self.device_used
                                          - self.device_limit)
            return bid

    def acquire(self, buffer_id: int) -> ColumnBatch:
        """Materialize on device (unspilling if needed) and pin."""
        with self._lock:
            e = self._entries[buffer_id]
            e.refcount += 1   # pin BEFORE unspill so the over-budget pass
            try:              # cannot immediately re-spill this buffer
                if e.tier != "device":
                    self._unspill_locked(e)
            except Exception:
                e.refcount -= 1
                raise
            if e.refcount == 1:
                self._gov_pinned(e.size)
            return e.batch

    def release(self, buffer_id: int) -> None:
        with self._lock:
            e = self._entries[buffer_id]
            assert e.refcount > 0, f"release without acquire: {buffer_id}"
            e.refcount -= 1
            if e.refcount == 0:
                self._gov_pinned(-e.size)

    def remove(self, buffer_id: int) -> None:
        with self._lock:
            e = self._entries.pop(buffer_id)
            if e.refcount > 0:
                self._gov_pinned(-e.size)
            self._drop_storage_locked(e)

    # -- spill ----------------------------------------------------------
    def spill_device(self, target_bytes: int) -> int:
        with self._lock:
            return self._spill_device_locked(target_bytes)

    def _spillable_locked(self):
        return sorted((e for e in self._entries.values()
                       if e.tier == "device" and e.refcount == 0),
                      key=lambda e: e.priority)

    def _spill_device_locked(self, target: int) -> int:
        freed = 0
        for e in self._spillable_locked():
            if freed >= target:
                break
            try:
                self._spill_one_to_host_locked(e)
            except _SpillDiskFull:
                # disk tier is full: stop spilling and report what was
                # freed so far (possibly 0) — the OOM retry scope then
                # splits its input instead of the operator crashing on a
                # write error (ENOSPC degrades into PR 2's retry path)
                break
            freed += e.size
        return freed

    def _check_cancel(self) -> None:
        """Cooperative cancellation point at spill-I/O entry: checked
        BEFORE any tier state mutates, so an abort here leaves the
        entry where it was (still consistent) and the query unwinds
        without half-moved buffers."""
        lc = self.lifecycle
        if lc is not None:
            lc.check()

    def _compress_spill(self, raw: bytes) -> "tuple[bytes, str | None]":
        """Apply the spill codec to one disk payload; identity when
        codec=none.  Counters track the before/after byte volumes so
        the compression ratio is observable per catalog."""
        codec = self._spill_codec
        if codec is None:
            return raw, None
        data = codec.compress(raw)
        self.metrics["spill_raw_bytes"] += len(raw)
        self.metrics["spill_compressed_bytes"] += len(data)
        return data, codec.name

    def _decompress_spill_locked(self, e: _Entry, data: bytes,
                                 out_size: int) -> bytes:
        """Inverse of ``_compress_spill`` at read-back (the sidecar CRC
        over the compressed bytes already passed).  Any decode failure
        — truncation racing the sidecar, a codec the process can no
        longer construct — marks the entry LOST like a CRC failure
        does: data loss the lineage layer can recompute, not a
        decompressor crash."""
        if not e.disk_codec:
            return data
        try:
            codec = self._spill_codec \
                if self._spill_codec is not None \
                and self._spill_codec.name == e.disk_codec \
                else get_codec(e.disk_codec)
            out = codec.decompress(data, out_size)
            if len(out) != out_size:
                raise ValueError(f"decompressed {len(out)}B, "
                                 f"want {out_size}B")
            return out
        except Exception as ex:
            self._mark_lost_locked(e)
            raise SpillCorruptionError(
                f"buffer {e.buffer_id}: {e.disk_codec} spill "
                f"decompression failed ({type(ex).__name__}: {ex}); "
                "storage dropped") from ex

    @_timed_spill
    def _spill_one_to_host_locked(self, e: _Entry) -> None:
        self._check_cancel()
        leaves, treedef = jax.tree_util.tree_flatten(e.batch)
        from spark_rapids_tpu.exec.core import fetch_to_host
        host = fetch_to_host(leaves, "fetch@BufferCatalog.spill")
        metas, total = [], 0
        host = [np.asarray(a) for a in host]
        for a in host:
            nb = a.nbytes
            # record the ORIGINAL shape: ascontiguousarray would promote
            # 0-d scalars (num_rows) to 1-d and corrupt the restore
            metas.append([a.dtype, a.shape, nb, total])
            total = _align(total + nb)
        off = None
        if total <= self._arena.capacity:
            off = self._arena.alloc(max(total, 1))
            while off is None and self._spill_host_one_locked():
                off = self._arena.alloc(max(total, 1))
        e.treedef = treedef
        e.leaf_meta = metas
        if off is not None:
            for a, m in zip(host, metas):
                flat = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                self._arena.view(off + m[3], m[2])[:] = flat
            e.arena_offset = off
            e.tier = "host"
            self.metrics["bytes_spilled_to_host"] += total
        else:
            # buffer cannot fit in the host arena (too large, or arena
            # fragmented with nothing spillable): fall through device->disk
            # (reference RapidsHostMemoryStore spill-through)
            packed = np.zeros(max(total, 1), np.uint8)
            for a, m in zip(host, metas):
                flat = np.ascontiguousarray(a).reshape(-1).view(np.uint8)
                packed[m[3]:m[3] + m[2]] = flat
            path = os.path.join(self._spill_dir, f"buf_{e.buffer_id}.bin")
            data, disk_codec = self._compress_spill(packed.tobytes())
            try:
                self._check_enospc_fault(e)
                with open(path, "wb") as f:
                    f.write(data)
                    f.flush()
                    # durable BEFORE the entry flips to tier=disk: a
                    # torn page-cache write must not become the only
                    # copy of the buffer
                    os.fsync(f.fileno())
                _write_sidecar(path, _spill_crc(data), len(data))
            except OSError as ex:
                if not _is_enospc(ex):
                    raise
                self.metrics["spill_enospc"] += 1
                _unlink_quiet(path)
                _unlink_quiet(_sidecar(path))
                e.treedef = None
                e.leaf_meta = None
                raise _SpillDiskFull(str(ex)) from ex
            e.disk_path = path
            e.disk_codec = disk_codec
            e.tier = "disk"
            self.metrics["bytes_spilled_to_disk"] += total
        e.batch = None
        self.device_used -= e.size
        self._gov_account(-e.size)
        self.metrics["device_spills"] += 1

    @_timed_spill
    def _spill_host_one_locked(self) -> bool:
        """Move one host-tier buffer to disk; False if none exist."""
        self._check_cancel()
        cands = sorted((e for e in self._entries.values()
                        if e.tier == "host" and e.refcount == 0),
                       key=lambda e: e.priority)
        if not cands:
            return False
        e = cands[0]
        total = _align_total(e.leaf_meta)
        path = os.path.join(self._spill_dir, f"buf_{e.buffer_id}.bin")
        disk_codec = None
        if self._spill_codec is not None:
            # compressed spill cannot stream straight from the arena:
            # materialize the slice, compress, write + fsync; the
            # sidecar covers the COMPRESSED bytes (what the disk holds)
            raw = bytes(self._arena.view(e.arena_offset, total))
            data, disk_codec = self._compress_spill(raw)
            try:
                self._check_enospc_fault(e)
                with open(path, "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
                _write_sidecar(path, _spill_crc(data), len(data))
            except OSError as ex:
                if not _is_enospc(ex):
                    raise
                self.metrics["spill_enospc"] += 1
                _unlink_quiet(path)
                _unlink_quiet(_sidecar(path))
                return False
        else:
            # checksum the arena slice (the source of truth) before it
            # is freed; verified against the file on read-back
            crc = _spill_crc(bytes(self._arena.view(e.arena_offset, total)))
            try:
                self._check_enospc_fault(e)
                self._arena.write_to_disk(e.arena_offset, total, path)
                fd = os.open(path, os.O_RDONLY)
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)
                _write_sidecar(path, crc, total)
            except OSError as ex:
                if not _is_enospc(ex):
                    raise
                # full disk: the buffer stays on the host tier; callers
                # see False ("nothing moved") and stop pushing
                self.metrics["spill_enospc"] += 1
                _unlink_quiet(path)
                _unlink_quiet(_sidecar(path))
                return False
        self._arena.free(e.arena_offset)
        e.arena_offset = None
        e.disk_path = path
        e.disk_codec = disk_codec
        e.tier = "disk"
        self.metrics["host_spills"] += 1
        self.metrics["bytes_spilled_to_disk"] += total
        return True

    # -- unspill ---------------------------------------------------------
    @_timed_spill
    def _unspill_locked(self, e: _Entry) -> None:
        import jax.numpy as jnp
        self._check_cancel()
        if e.tier == "lost":
            raise SpillCorruptionError(
                f"buffer {e.buffer_id}: storage was lost to disk "
                "corruption; only lineage recomputation can restore it")
        total = _align_total(e.leaf_meta)
        if e.tier == "disk" and e.arena_offset is None:
            self._check_corrupt_fault(e)
            # oversized direct-to-disk buffers restore without the arena
            if total > self._arena.capacity:
                with open(e.disk_path, "rb") as f:
                    raw = f.read()
                try:
                    _verify_sidecar(e.disk_path, raw)
                except SpillCorruptionError:
                    self._mark_lost_locked(e)
                    raise
                raw = self._decompress_spill_locked(e, raw, max(total, 1))
                packed = np.frombuffer(raw, np.uint8)
                leaves = [jnp.asarray(np.frombuffer(
                    packed[rel:rel + nb].tobytes(), dtype=dtype
                ).reshape(shape)) for dtype, shape, nb, rel in e.leaf_meta]
                os.unlink(e.disk_path)
                _unlink_quiet(_sidecar(e.disk_path))
                e.disk_path = None
                e.disk_codec = None
                self._finish_unspill_locked(e, leaves)
                return
            off = self._arena.alloc(max(total, 1))
            while off is None:
                if not self._spill_host_one_locked():
                    raise MemoryError("host arena exhausted during unspill")
                off = self._arena.alloc(max(total, 1))
            if e.disk_codec:
                # compressed file is smaller than the arena slice: read,
                # verify the sidecar over the compressed bytes, inflate,
                # then copy into the slice
                try:
                    with open(e.disk_path, "rb") as f:
                        raw = f.read()
                    _verify_sidecar(e.disk_path, raw)
                    raw = self._decompress_spill_locked(e, raw, total)
                    self._arena.view(off, total)[:] = np.frombuffer(
                        raw, np.uint8)
                except SpillCorruptionError:
                    self._arena.free(off)
                    if e.tier != "lost":
                        self._mark_lost_locked(e)
                    raise
                except Exception:
                    self._arena.free(off)
                    raise
            else:
                try:
                    self._arena.read_from_disk(off, total, e.disk_path)
                    _verify_sidecar(e.disk_path,
                                    bytes(self._arena.view(off, total)))
                except SpillCorruptionError:
                    self._arena.free(off)
                    self._mark_lost_locked(e)
                    raise
                except Exception:
                    self._arena.free(off)
                    raise
            os.unlink(e.disk_path)
            _unlink_quiet(_sidecar(e.disk_path))
            e.disk_path = None
            e.disk_codec = None
            e.arena_offset = off
            e.tier = "host"
        leaves = []
        for dtype, shape, nb, rel in e.leaf_meta:
            raw = self._arena.view(e.arena_offset + rel, nb)
            leaves.append(jnp.asarray(
                np.frombuffer(raw.tobytes(), dtype=dtype).reshape(shape)))
        self._arena.free(e.arena_offset)
        e.arena_offset = None
        self._finish_unspill_locked(e, leaves)

    def _finish_unspill_locked(self, e: _Entry, leaves) -> None:
        e.batch = jax.tree_util.tree_unflatten(e.treedef, leaves)
        e.leaf_meta = None
        e.treedef = None
        e.tier = "device"
        self.device_used += e.size
        self._gov_account(e.size)
        if self.device_used > self.metrics["device_bytes_peak"]:
            self.metrics["device_bytes_peak"] = self.device_used
        if self.device_used > self.device_limit:
            self._spill_device_locked(self.device_used - self.device_limit)

    def _check_enospc_fault(self, e: _Entry) -> None:
        """spill.disk.enospc injection point: make a spill-to-disk write
        fail exactly like a full disk would."""
        if self.faults is not None:
            act = self.faults.check("spill.disk.enospc",
                                    buffer_id=e.buffer_id,
                                    priority=e.priority, size=e.size)
            if act is not None:
                raise OSError(errno.ENOSPC,
                              "injected fault: no space left on device")

    def _check_corrupt_fault(self, e: _Entry) -> None:
        """spill.disk.corrupt injection point: flip one seeded byte of
        the on-disk payload so the read-back checksum catches it — real
        bit rot as the verifier sees it."""
        if self.faults is not None and e.disk_path:
            act = self.faults.check("spill.disk.corrupt",
                                    buffer_id=e.buffer_id,
                                    priority=e.priority, size=e.size)
            if act is not None:
                with open(e.disk_path, "r+b") as f:
                    data = f.read()
                    if data:
                        i = act.rng.randrange(len(data))
                        f.seek(i)
                        f.write(bytes([data[i] ^ 0xFF]))

    def _mark_lost_locked(self, e: _Entry) -> None:
        """Corrupt read-back: drop the unverifiable storage and mark the
        entry lost so every later acquire fails fast with
        SpillCorruptionError instead of re-reading flipped bytes."""
        self.metrics["spill_crc_failures"] += 1
        if e.disk_path:
            _unlink_quiet(e.disk_path)
            _unlink_quiet(_sidecar(e.disk_path))
        e.disk_path = None
        e.disk_codec = None
        e.arena_offset = None
        e.batch = None
        e.treedef = None
        e.leaf_meta = None
        e.tier = "lost"

    def _drop_storage_locked(self, e: _Entry) -> None:
        if e.tier == "device":
            self.device_used -= e.size
            self._gov_account(-e.size)
        elif e.tier == "host" and e.arena_offset is not None:
            self._arena.free(e.arena_offset)
        elif e.tier == "disk" and e.disk_path:
            _unlink_quiet(e.disk_path)
            _unlink_quiet(_sidecar(e.disk_path))
        e.batch = None

    # -- introspection ---------------------------------------------------
    def tier_of(self, buffer_id: int) -> str:
        with self._lock:
            return self._entries[buffer_id].tier

    def close(self) -> None:
        """Free everything.  With spark.rapids.memory.debug, buffers
        still registered (or pinned) at close are reported — the leak
        tracker analog of cudf's MemoryCleaner behind
        spark.rapids.memory.gpu.debug (RapidsConf.scala:288): a buffer
        alive at executor teardown means some operator failed to
        release it."""
        from spark_rapids_tpu.obs.registry import get_registry
        get_registry().unregister_source(self._reg_source)
        with self._lock:
            if self._debug and self._entries:
                leaks = [f"id={i} tier={e.tier} size={e.size} "
                         f"refcount={e.refcount} priority={e.priority}"
                         for i, e in sorted(self._entries.items())]
                import warnings
                # UserWarning, not ResourceWarning: the default filters
                # silently drop ResourceWarning, which would make the
                # debug flag a no-op in normal runs
                warnings.warn(
                    f"BufferCatalog leak check: {len(leaks)} buffer(s) "
                    "still registered at close:\n  " + "\n  ".join(leaks),
                    UserWarning)
            for e in list(self._entries.values()):
                self._drop_storage_locked(e)
            self._entries.clear()
            if self._arena_obj is not None and not self._arena_shared:
                self._arena_obj.close()
            self._arena_obj = None
        gov = self.governor
        if gov is not None:
            # after the entries drained (each drop mirrored its ledger
            # move): a finished query stops counting against the shed
            # watermark the moment its catalog closes
            gov.unregister(self)


def _unlink_quiet(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def _align(n: int) -> int:
    return (n + 63) & ~63


def _align_total(metas) -> int:
    if not metas:
        return 1
    last = metas[-1]
    return max(_align(last[3] + last[2]), 1)


class SpillableColumnarBatch:
    """Hold a batch across iterator steps without pinning HBM
    (reference SpillableColumnarBatch.scala:28-47)."""

    def __init__(self, batch: ColumnBatch, catalog: BufferCatalog,
                 priority: int = SpillPriority.ACTIVE_BATCH):
        self._catalog = catalog
        self._id = catalog.add_batch(batch, priority)
        self._closed = False
        self._pins = 0
        # pin accounting is lock-protected: plan branches sharing one
        # parked list (scan reuse) and concurrent partition workers
        # get/unpin the same handle from different threads; an unlocked
        # read-modify-write loses pins and lets the catalog spill HBM
        # still in use
        self._lock = threading.Lock()

    def get(self) -> ColumnBatch:
        """Materialize AND pin; pair every get() with an unpin() once the
        batch is no longer referenced (reference incRefCount/close
        contract) so the catalog cannot spill HBM still in use."""
        with self._lock:
            if self._closed:
                # a stage recovery invalidated this map output while a
                # concurrent pull still held the handle: that pull's
                # data is gone, which is loss, not a usage bug
                raise SpillCorruptionError(
                    f"buffer {self._id}: handle closed by a concurrent "
                    "invalidation")
            b = self._catalog.acquire(self._id)
            self._pins += 1
            return b

    def unpin(self) -> None:
        with self._lock:
            if self._closed:
                return  # close() already released every pin
            assert self._pins > 0
            self._catalog.release(self._id)
            self._pins -= 1

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            while self._pins:
                self._catalog.release(self._id)
                self._pins -= 1
            self._catalog.remove(self._id)
            self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class DeviceSemaphore:
    """Bound concurrent tasks touching the chip (reference
    GpuSemaphore.scala: spark.rapids.sql.concurrentGpuTasks)."""

    def __init__(self, concurrency: int):
        self._sem = threading.BoundedSemaphore(concurrency)
        self.concurrency = concurrency

    def __enter__(self):
        if not self._sem.acquire(blocking=False):
            # the chip is occupied: the seconds a dispatch waits for it
            from spark_rapids_tpu.obs.registry import get_registry
            t0 = time.perf_counter()
            self._sem.acquire()
            get_registry().inc("dispatch_wait_s",
                               time.perf_counter() - t0)
        return self

    def __exit__(self, *exc):
        self._sem.release()


_SYNC_DISPATCH: bool | None = None


def _sync_dispatch() -> bool:
    """Whether dispatches block for synchronous OOM capture.

    On the ``tpu`` backend a ``block_until_ready`` after every dispatch
    serializes host and device while completing no useful work — there
    the engine dispatches asynchronously and the spill-retry loop
    catches only errors that surface at dispatch/sync points
    (best-effort, like the reference with the retry iterator disabled).
    Other backends keep the reference's synchronous
    DeviceMemoryEventHandler semantics.  SRT_SYNC_DISPATCH=0/1 forces
    either mode."""
    global _SYNC_DISPATCH
    if _SYNC_DISPATCH is None:
        import os
        force = os.environ.get("SRT_SYNC_DISPATCH")
        if force is not None:
            _SYNC_DISPATCH = force != "0"
        else:
            import jax
            _SYNC_DISPATCH = jax.default_backend() != "tpu"
    return _SYNC_DISPATCH


def _need_estimate(args, kwargs) -> int:
    """Estimate the failed allocation from the dispatched inputs: the
    device bytes of every batch argument (a program's output is on the
    order of its inputs).  0 when nothing measurable was passed — the
    governor then applies its conf'd floor."""
    need = 0
    for a in list(args) + list(kwargs.values()):
        sz = getattr(a, "device_size_bytes", None)
        if callable(sz):
            try:
                need += int(sz())
            except Exception:  # enginelint: disable=RL001 (sizing is best-effort; the floor covers a batch that cannot report)
                pass
    return need


def run_with_spill_retry(fn, catalog: BufferCatalog, *args,
                         max_retries: int = 3, spill_bytes: int | None = None,
                         **kwargs):
    """Dispatch ``fn(*args, **kwargs)``; on XLA OOM spill from the catalog
    and retry (the DeviceMemoryEventHandler.onAllocFailure loop).

    Spill sizing: governed catalogs ask the memory governor for a
    need-sized reclaim (own buffers first, then younger peers' —
    memory/governor.py); ungoverned catalogs keep the legacy blind
    quarter-budget sweep, byte-identical to the pre-governor engine."""
    faults = getattr(catalog, "faults", None)
    attempt = 0
    while True:
        try:
            if faults is not None:
                act = faults.check("memory.oom",
                                   op=getattr(fn, "__name__", str(fn)))
                if act is not None:
                    # same shape as a real XLA HBM exhaustion so the
                    # handler below spills and retries, proving the
                    # recovery path without a real device
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: injected fault: simulated "
                        "HBM OOM (spark.rapids.test.faults memory.oom)")
            out = fn(*args, **kwargs)
            if _sync_dispatch():
                jax.block_until_ready(jax.tree_util.tree_leaves(out))
            return out
        except (RuntimeError, jax.errors.JaxRuntimeError) as ex:
            msg = str(ex)
            if "RESOURCE_EXHAUSTED" not in msg and "Out of memory" not in msg:
                raise
            catalog.metrics["oom_retries"] = \
                catalog.metrics.get("oom_retries", 0) + 1
            attempt += 1
            if attempt > max_retries:
                raise
            gov = getattr(catalog, "governor", None)
            if gov is not None:
                freed = gov.reclaim(
                    catalog, spill_bytes or _need_estimate(args, kwargs))
            else:
                freed = catalog.spill_device(
                    spill_bytes or catalog.device_limit // 4)
            if freed == 0:
                raise
