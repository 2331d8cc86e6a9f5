"""Typed, self-documenting configuration registry.

TPU-native analog of the reference's RapidsConf (reference
sql-plugin/src/main/scala/com/nvidia/spark/rapids/RapidsConf.scala:30-1059):
a builder-based registry of `spark.rapids.*` entries with docs, defaults,
value checking and doc generation (`RapidsConf.help`, RapidsConf.scala:785),
plus per-operator auto-generated enable keys
(`spark.rapids.sql.expression.<Name>` etc., GpuOverrides.scala:132-137).
"""
from __future__ import annotations

import re
from typing import Any, Callable

__all__ = ["ConfEntry", "TpuConf", "register", "registered_entries", "help_text"]

_BYTE_SUFFIXES = {
    "b": 1, "k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40,
}


def parse_bytes(v) -> int:
    """Parse '512m', '2g', plain ints. Mirrors Spark byte-unit parsing used by
    RapidsConf (reference RapidsConf.scala bytesConf entries, e.g. :364)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = re.fullmatch(r"\s*(\d+)\s*([bkmgt]?)b?\s*", str(v).lower())
    if not m:
        raise ValueError(f"cannot parse byte size: {v!r}")
    return int(m.group(1)) * _BYTE_SUFFIXES.get(m.group(2) or "b", 1)


class ConfEntry:
    def __init__(self, key: str, default: Any, doc: str, *,
                 conv: Callable[[Any], Any] | None = None,
                 check: Callable[[Any], bool] | None = None,
                 check_doc: str = "", internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.conv = conv
        self.check = check
        self.check_doc = check_doc
        self.internal = internal

    def get(self, settings: dict) -> Any:
        if self.key in settings:
            v = settings[self.key]
            if self.conv is not None:
                v = self.conv(v)
            if self.check is not None and not self.check(v):
                raise ValueError(f"{self.key}={v!r}: {self.check_doc}")
            return v
        return self.default


_REGISTRY: dict[str, ConfEntry] = {}


def register(entry: ConfEntry) -> ConfEntry:
    _REGISTRY[entry.key] = entry
    return entry


def registered_entries() -> dict[str, ConfEntry]:
    return dict(_REGISTRY)


def _bool(v):
    if isinstance(v, bool):
        return v
    return str(v).lower() in ("true", "1", "yes")


def conf(key, default, doc, **kw):
    return register(ConfEntry(key, default, doc, **kw))


def bool_conf(key, default, doc, **kw):
    return register(ConfEntry(key, default, doc, conv=_bool, **kw))


def int_conf(key, default, doc, **kw):
    return register(ConfEntry(key, default, doc, conv=int, **kw))


def float_conf(key, default, doc, **kw):
    return register(ConfEntry(key, default, doc, conv=float, **kw))


def bytes_conf(key, default, doc, **kw):
    return register(ConfEntry(key, parse_bytes(default), doc, conv=parse_bytes, **kw))


# ---------------------------------------------------------------------------
# Core entries — names mirror the reference where the concept matches.
# ---------------------------------------------------------------------------

SQL_ENABLED = bool_conf(
    "spark.rapids.sql.enabled", True,
    "Enable or disable TPU acceleration of SQL operators entirely. "
    "(ref RapidsConf.scala ENABLE_SQL)")

EXPLAIN = conf(
    "spark.rapids.sql.explain", "NONE",
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE, ALL, or NOT_ON_TPU. (ref RapidsConf.scala:744)",
    check=lambda v: v in ("NONE", "ALL", "NOT_ON_TPU"),
    check_doc="must be NONE|ALL|NOT_ON_TPU")

BATCH_SIZE_BYTES = bytes_conf(
    "spark.rapids.sql.batchSizeBytes", 1 << 30,
    "Target byte size for coalesced TPU batches; the CoalesceGoal target. "
    "(ref RapidsConf.scala:364)")

BATCH_CAPACITY_ROWS = int_conf(
    "spark.rapids.sql.batchRowCapacity", 1 << 20,
    "Default logical row capacity bucket for device batches. Batches are "
    "padded up to power-of-two capacities for static-shape XLA compilation "
    "(TPU-specific; no reference analog — cuDF supports dynamic shapes).")

INCOMPATIBLE_OPS = bool_conf(
    "spark.rapids.sql.incompatibleOps.enabled", False,
    "Enable operators flagged as not bit-for-bit compatible with the CPU "
    "engine. (ref RapidsConf.scala INCOMPATIBLE_OPS)")

HAS_NANS = bool_conf(
    "spark.rapids.sql.hasNans", True,
    "Assume floating point data may contain NaNs; disables some ops whose "
    "NaN semantics differ. (ref RapidsConf.scala HAS_NANS)")

ALLOW_FLOAT_AGG = bool_conf(
    "spark.rapids.sql.variableFloatAgg.enabled", True,
    "Allow float aggregations whose result may differ in last-bit rounding "
    "due to reduction order. (ref RapidsConf.scala ENABLE_FLOAT_AGG)")

EXACT_DOUBLE_AGG = bool_conf(
    "spark.rapids.sql.exactDoubleAggregation", False,
    "Force aggregations over DOUBLE columns to the host engine: TPU f64 "
    "is a float32-pair emulation (~48 mantissa bits, f32 exponent range "
    "— docs/compatibility.md) and sums/averages can deviate from exact "
    "f64; scripts/verify_exprs_tpu.py measures the error per op on "
    "the chip. float32 aggregations are exact on TPU and stay on device. "
    "(ref RapidsConf.scala incompat machinery :461-492)")

REPLACE_SORT_MERGE_JOIN = bool_conf(
    "spark.rapids.sql.replaceSortMergeJoin.enabled", True,
    "Replace sort-merge joins with hash joins on TPU. "
    "(ref RapidsConf.scala:450)")

TEST_ENABLED = bool_conf(
    "spark.rapids.sql.test.enabled", False,
    "Test mode: assert the whole plan runs on the TPU. "
    "(ref RapidsConf.scala TEST_CONF)", internal=True)

TEST_ALLOWED_NONTPU = conf(
    "spark.rapids.sql.test.allowedNonTpu", "",
    "Comma separated exec names allowed on CPU in test mode.", internal=True)

SCAN_REUSE = bool_conf(
    "spark.rapids.sql.scanReuse", True,
    "Share one materialization among identical scans (same files, "
    "columns, pushdown) within a plan, parked spillable in the buffer "
    "catalog — leaf-level ReuseExchange (Spark's rule the reference "
    "inherits); q28-style multi-branch plans otherwise re-read and "
    "re-transfer the same table per branch.")

MAX_READER_BATCH_SIZE_BYTES = bytes_conf(
    "spark.rapids.sql.reader.batchSizeBytes", 1 << 30,
    "Soft cap on bytes per scan batch, converted to a row cap through a "
    "static schema width estimate (io/scan.py). Combines with "
    "spark.rapids.sql.reader.batchRows. (ref RapidsConf.scala:378)")

HBM_ALLOC_FRACTION = float_conf(
    "spark.rapids.memory.tpu.allocFraction", 0.75,
    "Fraction of device HBM the buffer store may occupy before spilling. "
    "(ref RapidsConf.scala gpu.allocFraction, docs/configs.md:33)")

PINNED_POOL_SIZE = bytes_conf(
    "spark.rapids.memory.pinnedPool.size", 0,
    "Size of the native pinned host staging pool (0 disables). "
    "(ref GpuDeviceManager.scala:264-270)")

SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.shuffle.compression.codec", "none",
    "Codec for shuffle partition buffers: none, lz4 (native C++ block "
    "codec, native/lz4.cpp) or zstd. (ref RapidsConf.scala:729, "
    "NvcompLZ4CompressionCodec.scala:25)",
    check=lambda v: v in ("none", "lz4", "zstd"),
    check_doc="must be none|lz4|zstd")

SHUFFLE_TRANSPORT_CLASS = conf(
    "spark.rapids.shuffle.transport.class",
    "spark_rapids_tpu.shuffle.local.LocalShuffleTransport",
    "Fully qualified class of the shuffle transport implementation, loaded "
    "by reflection. (ref RapidsConf.scala:652, RapidsShuffleTransport.scala:638)")

SHUFFLE_MAX_METADATA_SIZE = bytes_conf(
    "spark.rapids.shuffle.maxMetadataSize", 1 << 20,
    "Max size for shuffle metadata messages. (ref RapidsConf.scala shuffle)")

SHUFFLE_PARTITIONS = int_conf(
    "spark.sql.shuffle.partitions", 8,
    "Number of shuffle partitions for exchanges (Spark's own knob; honored "
    "here for parity).")

MESH_DEVICE_COUNT = int_conf(
    "spark.rapids.tpu.mesh.deviceCount", 0,
    "Devices in the 1-D mesh used for collective shuffle/aggregation. "
    "When > 1, grouped aggregations and hash repartitions lower to "
    "shard_map all-to-all programs over the mesh (the ICI data plane, "
    "SURVEY.md §5.8) instead of the in-process exchange. 0 disables. "
    "(ref: the UCX transport enable, RapidsConf.scala:652)")

MESH_REGIONS_ENABLED = bool_conf(
    "spark.rapids.tpu.mesh.regions.enabled", True,
    "Form mesh REGIONS: a contiguous elementwise pipeline "
    "(filter/project/fused stage) feeding a mesh collective operator "
    "(aggregate, exchange, sort) runs INSIDE the per-device shard_map "
    "program — batches are sharded once at the region leaves and stay "
    "device-resident through the whole pipeline, with host/device-0 "
    "transitions only at region boundaries. Disable to run each mesh "
    "operator as an isolated island (the pre-region plan shape).")

MESH_SEND_CAPACITY = int_conf(
    "spark.rapids.tpu.mesh.exchange.sendCapacityRows", 0,
    "Per-target row capacity C of the [P, C] all-to-all send buffers in "
    "mesh exchanges. 0 (default) sizes C to the full shard capacity — "
    "the static worst case where every row targets one device, which "
    "can never overflow but costs P x shard bytes of send-buffer HBM "
    "per device. A smaller C bounds that memory; if a skewed key "
    "distribution overflows it, the exchange detects the overflow "
    "in-program (no silent truncation), counts mesh_send_overflows, "
    "and degrades into a retry at worst-case capacity — the mesh "
    "analog of the OOM split-and-retry ladder (memory/retry.py).")

MESH_JOIN_BUILD_THRESHOLD = bytes_conf(
    "spark.rapids.tpu.mesh.join.buildThresholdBytes", 128 << 20,
    "Mesh joins replicate the build side to every device while it fits "
    "under this many bytes (broadcast-style, GpuBroadcastHashJoinExec); "
    "above it BOTH sides hash-exchange on the join keys over the mesh "
    "and each device joins its co-partitioned shards locally "
    "(GpuShuffledHashJoinExec.scala:162). 0 forces the partitioned "
    "path.")

MESH_WINDOW_ENABLED = bool_conf(
    "spark.rapids.tpu.mesh.window.enabled", True,
    "Lower window functions to MeshWindowExec when a mesh is active. "
    "Partitioned windows hash-exchange rows on the PARTITION BY keys "
    "in-program (whole groups land on one device) and run the columnar "
    "window kernel per device; unpartitioned windows all-gather the "
    "input and evaluate the global frame on every device, each keeping "
    "its contiguous slice of the ordered output (the MeshSortExec "
    "global-order machinery). Disable to gather window inputs to a "
    "single device (the pre-mesh WindowExec path).")

UDF_COMPILER_ENABLED = bool_conf(
    "spark.rapids.sql.udfCompiler.enabled", False,
    "Compile Python UDF bytecode to native expressions when possible. "
    "(ref udf-compiler Plugin.scala:29-35)")

FALLBACK_ON_DEVICE_ERROR = bool_conf(
    "spark.rapids.sql.fallbackOnDeviceError", False,
    "Re-run a query on the host engine when device execution raises at "
    "runtime (loud warning). Off by default: the reference only falls "
    "back at plan time, and silent runtime masking would defeat "
    "differential testing.")

SPILL_ENABLED = bool_conf(
    "spark.rapids.memory.spill.enabled", True,
    "Enable HBM->host->disk spill of catalog-registered buffers. "
    "(ref RapidsBufferCatalog.scala:128-142)")

METRICS_ENABLED = bool_conf(
    "spark.rapids.sql.metrics.enabled", True,
    "Collect per-operator metrics (rows/batches/time). (ref GpuExec.scala:47-55)")

TEST_FAULTS = conf(
    "spark.rapids.test.faults", "",
    "Deterministic fault-injection plan: 'point:action,k=v;...' rules "
    "interpreted by spark_rapids_tpu/faults.py and threaded through the "
    "TCP shuffle server/client, the local shuffle store, and the spill "
    "path. Empty (the default) builds no registry at all, so every "
    "injection site is a single None check. Test-only: never set in "
    "production. (reference: RapidsShuffleTestHelper exercises failure "
    "paths with mocked transports; here the REAL transport runs under "
    "seeded faults)")

TEST_FAULTS_SEED = int_conf(
    "spark.rapids.test.faults.seed", 0,
    "Seed for the fault plan's per-rule PRNGs (probabilistic triggers, "
    "corrupted-byte selection), so a chaos run replays identically.")


class TpuConf:
    """An immutable snapshot of settings, queried through typed entries.

    Reference: `class RapidsConf` (RapidsConf.scala:894+). Per-operator enable
    keys look like `spark.rapids.sql.expression.Add` and are checked via
    :meth:`is_op_enabled` (ref GpuOverrides.scala confKey :132-137).
    """

    def __init__(self, settings: dict | None = None):
        self.settings = dict(settings or {})

    def get(self, entry: ConfEntry):
        return entry.get(self.settings)

    # convenience properties mirroring RapidsConf accessors
    @property
    def sql_enabled(self) -> bool: return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str: return self.get(EXPLAIN)

    @property
    def batch_size_bytes(self) -> int: return self.get(BATCH_SIZE_BYTES)

    @property
    def batch_capacity_rows(self) -> int: return self.get(BATCH_CAPACITY_ROWS)

    @property
    def incompatible_ops(self) -> bool: return self.get(INCOMPATIBLE_OPS)

    @property
    def has_nans(self) -> bool: return self.get(HAS_NANS)

    @property
    def test_enabled(self) -> bool: return self.get(TEST_ENABLED)

    @property
    def shuffle_partitions(self) -> int: return self.get(SHUFFLE_PARTITIONS)

    @property
    def is_udf_compiler_enabled(self) -> bool: return self.get(UDF_COMPILER_ENABLED)

    @property
    def mesh_device_count(self) -> int: return self.get(MESH_DEVICE_COUNT)

    def is_op_enabled(self, op_conf_key: str, default: bool = True) -> bool:
        v = self.settings.get(op_conf_key)
        if v is None:
            return default
        return _bool(v)

    def set(self, key: str, value) -> "TpuConf":
        s = dict(self.settings)
        s[key] = value
        return TpuConf(s)


def help_text(include_internal: bool = False) -> str:
    """Generate markdown docs for all registered entries.

    Reference: `RapidsConf.help` generates docs/configs.md (RapidsConf.scala:785).
    """
    lines = ["# spark_rapids_tpu configuration", "",
             "| Key | Default | Description |", "|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal and not include_internal:
            continue
        doc = e.doc.replace("\n", " ")
        lines.append(f"| {e.key} | {e.default} | {doc} |")
    return "\n".join(lines) + "\n"


def generate_docs() -> str:
    """Render every registered conf entry as markdown (the analog of
    RapidsConf.help generating docs/configs.md, RapidsConf.scala:785).

    Modules register entries at import near their consumers, so the
    generator imports EVERY package module first — a hand-kept list
    here silently drops new modules' keys from the docs."""
    import importlib
    import pkgutil
    import spark_rapids_tpu
    for m in pkgutil.walk_packages(spark_rapids_tpu.__path__,
                                   "spark_rapids_tpu."):
        if "._native" in m.name or m.name.endswith("_native"):
            continue
        try:
            importlib.import_module(m.name)
        # enginelint: disable=RL001 (docs walker: one failing import skips one module and warns loudly below; no query context)
        except Exception as e:  # noqa: BLE001 - any import-time failure
            # (not just ImportError: device/backend init in a module
            # must not abort the whole generator) skips ONE module; a
            # skipped module silently drops its keys from the docs —
            # make that loud instead of invisible
            import warnings
            warnings.warn(f"generate_docs: could not import {m.name} "
                          f"({e}); its conf keys are missing from the "
                          "generated docs", RuntimeWarning)
    lines = [
        "# Configuration",
        "",
        "Generated by `spark_rapids_tpu.conf.generate_docs()` "
        "(`python scripts/gen_config_docs.py`). Do not edit by hand.",
        "",
        "Reference analog: docs/configs.md generated by RapidsConf.help.",
        "",
        "| Name | Default | Description |",
        "|---|---|---|",
    ]
    for key in sorted(registered_entries()):
        e = registered_entries()[key]
        if e.internal:
            continue
        doc = " ".join(str(e.doc).split())
        default = e.default
        if isinstance(default, str) and not default:
            default = "(unset)"
        lines.append(f"| `{key}` | `{default}` | {doc} |")
    lines.append("")
    lines.append("Per-operation enable keys "
                 "(`spark.rapids.sql.{exec,expression}.<Name>`) default to "
                 "true and are generated from the registries "
                 "(reference ReplacementRule.confKey, "
                 "GpuOverrides.scala:132-137).")
    return "\n".join(lines) + "\n"
