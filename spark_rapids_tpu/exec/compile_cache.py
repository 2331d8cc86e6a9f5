"""Process-wide compiled-kernel cache: jitted programs keyed on a
canonical plan-fragment fingerprint and shared across exec-node
instances, plans, queries, and sessions.

The reference's hot loop never compiles: every kernel is a pre-built
libcudf entry point (SURVEY §3.3).  The XLA analog used to re-``jax.jit``
per exec-node INSTANCE (``basic.py`` ``_project_jit``, ``joins.py``
``_cond_jit`` …), so two queries over the same plan fragment — or one
query re-run — paid tracing again because the wrapper died with the
plan.  Here the wrapper itself is process-wide: identical fragments
resolve to ONE shared jit callable, and jax's own executable cache keys
the compiled artifacts per (shape, dtype) signature underneath it.
Batch capacities are pow2-bucketed at the producers (and re-normalized
at fused-stage entry), so shape polymorphism cannot fragment that
inner cache.

Key design: the python-level key is the *program* (canonicalized
expression trees + schemas + static closure state), NOT the capacity
bucket — one wrapper serves every bucket, and the (capacity, dtype)
signature selects the executable inside jax.  ``SharedJit`` tracks the
signatures it has seen so ``compile_count`` / ``compile_wall_s`` move
exactly when a new executable is built, which makes "a second run of
the same query compiles nothing" a testable invariant
(tests/test_fusion.py::test_second_run_zero_new_compiles).

Counters (MetricsRegistry): ``fusion_cache_hits`` / ``fusion_cache_misses``
move per fragment-key lookup; ``compile_count`` / ``compile_wall_s`` per
first invocation of a new input signature (trace + compile + first run);
``program.<name>.launches`` / ``.arg_bytes`` / ``.result_bytes`` per call
of the SharedJit program ``name`` (what it was handed and gave back).
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
import time
import weakref
import types as _pytypes
from collections import OrderedDict
from functools import partial as _partial

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import bool_conf, int_conf
from spark_rapids_tpu.obs.registry import get_registry

__all__ = ["fragment_key", "fingerprint", "get_or_build", "shared_jit",
           "instrument", "SharedJit", "cache_info", "reset_cache",
           "mesh_key_part",
           "FUSION_ENABLED", "FUSION_MIN_OPS", "FUSION_DONATE"]

FUSION_ENABLED = bool_conf(
    "spark.rapids.sql.fusion.enabled", True,
    "Collapse adjacent filter/project pipelines into single FusedStageExec "
    "nodes whose body is ONE jitted program — one dispatch and one kernel "
    "launch per batch instead of one per operator (the whole-stage-codegen "
    "analog; reference GpuTransitionOverrides, PAPER.md §L3). Disable to "
    "restore the per-operator plan shape.")

FUSION_MIN_OPS = int_conf(
    "spark.rapids.sql.fusion.minOperators", 2,
    "Minimum number of adjacent fusible operators before a FusedStageExec "
    "replaces the run; below it the per-operator nodes are kept.")

FUSION_DONATE = bool_conf(
    "spark.rapids.sql.fusion.donateInputs", True,
    "Donate input buffers to the fused jit region (jax donate_argnums) so "
    "XLA may reuse them for outputs — halves peak HBM per fused batch. "
    "Only applied when the stage's input is provably exclusive: the "
    "planner disables donation per stage when any producer below it is "
    "consumed by multiple parents (a CTE scanned once, joined twice) or "
    "shares a parked scan materialization, since donating a shared batch "
    "deletes its buffers under the sibling consumer. Tradeoff: a donated "
    "batch cannot be re-dispatched, so a REAL device OOM inside a fused "
    "stage cannot replay/split that batch and surfaces an actionable "
    "error instead; set false to trade buffer reuse for full "
    "split-and-retry coverage (docs/tuning-guide.md).")

COMPILE_CACHE_MAX_ENTRIES = int_conf(
    "spark.rapids.sql.compile.cacheMaxEntries", 1024,
    "Upper bound on distinct plan fragments kept in the process-wide "
    "compile cache; least-recently-used entries (and their jax "
    "executables) are dropped past it.", internal=True)

COMPILE_CACHE_MAP_PRESSURE = int_conf(
    "spark.rapids.sql.compile.mapPressureLimit", 0,
    "Purge every cached executable when the process's memory-mapping "
    "count reaches this value at a compile event.  Each XLA:CPU "
    "executable pins ~10 mappings for the life of the process, so a "
    "long-lived engine eventually hits the kernel's vm.max_map_count "
    "and the NEXT compile dies with an unexplained SIGSEGV/SIGABRT "
    "inside backend_compile.  0 (default) = auto: 70% of "
    "/proc/sys/vm/max_map_count, disabled where /proc is absent.",
    internal=True)


# ---------------------------------------------------------------------------
# Canonical fingerprints
# ---------------------------------------------------------------------------

_MAX_DEPTH = 64

#: attribute values whose equality the recursion cannot prove
#: (callables, modules): poisoned with a process-unique serial, NOT
#: ``id()`` — a dead object's id can be reused by a NEW object, and an
#: id-based key would then falsely HIT the old entry.  The serial makes
#: such fingerprints unique per call: sharing is lost (the per-instance
#: ``hasattr`` guards still amortize the cost), correctness is not.
_OPAQUE = (_pytypes.FunctionType, _pytypes.MethodType,
           _pytypes.BuiltinFunctionType, _pytypes.ModuleType, _partial)

_SERIAL_LOCK = threading.Lock()
_SERIAL = 0


def _next_serial() -> int:
    global _SERIAL
    with _SERIAL_LOCK:
        _SERIAL += 1
        return _SERIAL


def _fp(v, out: list, seen: set, depth: int) -> None:
    if depth > _MAX_DEPTH:
        out.append(f"<deep:#{_next_serial()}>")
        return
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        out.append(repr(v))
        out.append(";")
        return
    if isinstance(v, T.DataType):
        # DataType reprs are structural (ArrayType includes its element)
        out.append(f"dt<{v!r}>;")
        return
    if isinstance(v, T.StructField):
        out.append(f"sf<{v.name}:")
        _fp(v.data_type, out, seen, depth + 1)
        out.append(f"{v.nullable}>;")
        return
    if isinstance(v, T.Schema):
        out.append("schema[")
        for f in v.fields:
            _fp(f, out, seen, depth + 1)
        out.append("];")
        return
    if isinstance(v, (list, tuple)):
        out.append("[" if isinstance(v, list) else "(")
        for x in v:
            _fp(x, out, seen, depth + 1)
        out.append("];" if isinstance(v, list) else ");")
        return
    if isinstance(v, dict):
        out.append("{")
        for k in sorted(v, key=repr):
            out.append(f"{k!r}=")
            _fp(v[k], out, seen, depth + 1)
        out.append("};")
        return
    if isinstance(v, _OPAQUE) or callable(v) and not hasattr(v, "children"):
        out.append(f"<opaque:{type(v).__name__}:#{_next_serial()}>;")
        return
    if id(v) in seen:
        out.append("<cycle>;")
        return
    seen.add(id(v))
    try:
        # generic object (Expression, resolved sort order, agg spec …):
        # class identity + every attribute, with expression children
        # LAST so tree shape is unambiguous.  Attributes the recursion
        # cannot canonicalize fall back to identity above — safety
        # (never share a program whose state we cannot prove equal)
        # over sharing.
        try:
            d = vars(v)
        except TypeError:
            out.append(f"<slots:{type(v).__name__}:#{_next_serial()}>;")
            return
        out.append(type(v).__name__)
        out.append("{")
        children = d.get("children", ())
        for k in sorted(d):
            if k == "children":
                continue
            out.append(f"{k}=")
            _fp(d[k], out, seen, depth + 1)
        out.append("}(")
        for c in children:
            _fp(c, out, seen, depth + 1)
        out.append(");")
    finally:
        seen.discard(id(v))


def fingerprint(*parts) -> str:
    """Canonical structural serialization of expressions / schemas /
    static closure state.  Unlike ``repr``, this captures non-child
    attributes (a LIKE pattern, a Cast target type, a resolved sort
    direction), every node's bound dtype, and poisons the result with a
    unique serial — never a lossy summary — for state it cannot prove
    canonical."""
    out: list = []
    _fp(list(parts), out, set(), 0)
    return "".join(out)


def mesh_key_part(mesh, axis_name: str) -> tuple:
    """The mesh component of a fragment key: a ``shard_map`` program is
    specialized to its mesh SHAPE (the all-to-all degree is baked into
    every buffer shape) and to the participating device set (the
    executable is lowered against those devices' memories), so a mesh-2
    and a mesh-4 lowering of the same fragment must MISS each other,
    and both must miss the single-chip program (which has no mesh part
    at all).  ``mesh`` may be a ``jax.sharding.Mesh`` or a plain device
    count."""
    if isinstance(mesh, int):
        return ("mesh", mesh, axis_name)
    devs = tuple(int(d.id) for d in mesh.devices.flat)
    return ("mesh", len(devs), axis_name, devs)


def fragment_key(kind: str, *parts) -> str:
    """Cache key for one plan fragment's program: a ``kind`` tag plus the
    md5 of the canonical fingerprint of everything the traced closure
    captures."""
    digest = hashlib.md5(fingerprint(*parts).encode()).hexdigest()
    return f"{kind}:{digest}"


# ---------------------------------------------------------------------------
# Shared jit wrappers + compile accounting
# ---------------------------------------------------------------------------

# XLA's CPU backend is not reliably safe against backend_compile running
# *concurrently* with other compiles OR with executions on sibling
# python threads (drain threads segfault inside the LLVM JIT while a
# peer dispatches) — observed as rare full-suite SIGSEGVs on single-host
# CPU runs.  On the CPU backend every SharedJit call therefore passes a
# process-wide readers-writer lock: warm dispatches share it, while a
# first-signature call — the one that traces + compiles — holds it
# exclusively.  Both sides are re-entrant for the lock-holding thread
# (jit-of-jit tracing re-enters wrappers).  Non-CPU backends take no
# lock at all.

class _CompileRWLock:
    """Many concurrent executors, one exclusive compiler."""

    __slots__ = ("_cond", "_readers", "_writer", "_depth")

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer: int | None = None
        self._depth = 0

    @contextlib.contextmanager
    def reading(self):
        me = threading.get_ident()
        with self._cond:
            if self._writer == me:
                counted = False  # already exclusive; pass through
            else:
                while self._writer is not None:
                    self._cond.wait()
                self._readers += 1
                counted = True
        try:
            yield
        finally:
            if counted:
                with self._cond:
                    self._readers -= 1
                    if not self._readers:
                        self._cond.notify_all()

    @contextlib.contextmanager
    def writing(self):
        me = threading.get_ident()
        with self._cond:
            if self._writer == me:
                self._depth += 1
            else:
                while self._writer is not None or self._readers:
                    self._cond.wait()
                self._writer = me
                self._depth = 1
        try:
            yield
        finally:
            with self._cond:
                self._depth -= 1
                if not self._depth:
                    self._writer = None
                    self._cond.notify_all()


_COMPILE_RW = _CompileRWLock()
_NULL_GUARD = contextlib.nullcontext()
_SERIALIZE_COMPILES: bool | None = None


def _cpu_backend() -> bool:
    global _SERIALIZE_COMPILES
    if _SERIALIZE_COMPILES is None:
        try:
            import jax
            _SERIALIZE_COMPILES = jax.default_backend() == "cpu"
        # enginelint: disable=RL001 (backend probe; falls back to non-serialized compiles)
        except Exception:
            _SERIALIZE_COMPILES = False
        if _SERIALIZE_COMPILES:
            from spark_rapids_tpu.runtime import sync_cpu_dispatch
            sync_cpu_dispatch()  # locks can't see the async native pool
    return _SERIALIZE_COMPILES


def compile_guard():
    """Exclusive guard to hold while a call WILL trace + compile."""
    return _COMPILE_RW.writing() if _cpu_backend() else _NULL_GUARD


def dispatch_guard():
    """Shared guard to hold while dispatching an already-built program."""
    return _COMPILE_RW.reading() if _cpu_backend() else _NULL_GUARD


# ---------------------------------------------------------------------------
# Mapping-pressure valve
# ---------------------------------------------------------------------------

_ALL_SHARED: "weakref.WeakSet" = weakref.WeakSet()
_MAP_LIMIT: int | None = None


def _map_pressure_limit() -> int:
    global _MAP_LIMIT
    if _MAP_LIMIT is None:
        lim = COMPILE_CACHE_MAP_PRESSURE.default
        if not lim:
            try:
                with open("/proc/sys/vm/max_map_count") as f:
                    lim = int(f.read()) * 7 // 10
            except (OSError, ValueError):
                lim = 0
        _MAP_LIMIT = lim
    return _MAP_LIMIT


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return f.read().count(b"\n")
    except OSError:
        return 0


def purge_compiled() -> None:
    """Drop every compiled executable the process holds.

    Clears the fragment cache, every SharedJit's signature bookkeeping,
    and jax's own executable caches, then collects — executables only
    release their code-page mappings once the last reference dies.
    Callers must already hold the exclusive compile guard (or be
    otherwise single-threaded): live plans keep their wrapper objects
    and simply recompile on next dispatch."""
    import gc
    import jax
    with _LOCK:
        _CACHE.clear()
    for sj in list(_ALL_SHARED):
        with sj._lock:
            sj._sigs.clear()
    jax.clear_caches()
    gc.collect()
    get_registry().inc("compile_cache_purges")


def _purge_if_pressured() -> bool:
    lim = _map_pressure_limit()
    if not lim or _map_count() < lim:
        return False
    purge_compiled()
    return True


def _leaf_bytes(leaves) -> int:
    """Summed ``size * itemsize`` of the array-like leaves (shape and
    dtype known); python scalars and static leaves count nothing."""
    import numpy as np
    total = 0
    for leaf in leaves:
        if not hasattr(leaf, "shape"):      # as _signature tells arrays
            continue
        try:
            n = np.dtype(leaf.dtype).itemsize
            for d in leaf.shape:
                n *= d
        # a static leaf that merely looks like an array (an expression
        # with a ``dtype`` property) or an extended dtype: no bytes
        except (TypeError, AttributeError, ValueError):
            continue
        total += n
    return total


class SharedJit:
    """A process-wide jit callable with per-signature accounting.

    ``name`` says whose program this is (``agg_update``,
    ``join_probe_fast``, ``mesh_region_chain`` …): it is stamped on the
    wrapped python function, so XLA names the module ``jit_<name>`` in
    device traces, and it is the key of the program's counters
    ``program.<name>.launches`` / ``.arg_bytes`` / ``.result_bytes`` /
    ``.dispatch_s``.
    One name per jit site — tests/test_query_record.py holds them unique.

    jax compiles one executable per abstract input signature inside the
    wrapper; this class mirrors that bookkeeping at the python level in
    ONE dict, signature -> ``[arg_bytes, result_bytes]`` (the summed
    sizes of the call's array leaves, computed when the signature is
    first seen).  The first call for a NEW (shapes, dtypes, tree)
    signature — the one that traces and compiles — runs under a
    ``program.compile@<name>`` span, moves ``compile_count`` and is
    timed into ``compile_wall_s``; a signature already seen costs one
    dict lookup, two clock reads and four adds: ``.dispatch_s`` is the
    host's seconds inside that warm call (the launch's enqueue; where
    the device's queue is full, its backlog seen from the host), and the
    compiling call never moves it.  ``compile_count`` / ``compile_wall_s``
    see SharedJit programs only: the compiles of eager ``jnp``
    operations outside any program (PERF.md Findings PR 23) reach only
    a ``jax.monitoring`` listener on
    ``/jax/core/compile/backend_compile_duration``
    (benchmark/harness/compiles.py).  A call made while an enclosing
    program is being traced (jit-of-jit) is counted like a launch; that
    happens at compile time only, never in a warm collect."""

    __slots__ = ("fn", "name", "_sigs", "_lock", "_keys", "__weakref__")

    def __init__(self, fn, name: str):
        self.fn = fn
        self.name = name
        inner = getattr(fn, "__wrapped__", None)
        if inner is not None:
            # jax reads the name when it traces: module ``jit_<name>``
            inner.__name__ = inner.__qualname__ = name
        self._sigs: dict = {}
        self._lock = threading.Lock()
        self._keys = (f"program.{name}.launches",
                      f"program.{name}.arg_bytes",
                      f"program.{name}.result_bytes",
                      f"program.{name}.dispatch_s")
        _ALL_SHARED.add(self)

    def signature_count(self) -> int:
        return len(self._sigs)

    @staticmethod
    def _signature(args, kwargs):
        import jax
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        sig = (treedef, tuple(
            (l.shape, str(l.dtype)) if hasattr(l, "shape") else l
            for l in leaves))
        hash(sig)  # unhashable static leaf -> fall back to uncounted
        return sig, leaves

    def _count(self, facts, dispatch_s: float | None = None) -> None:
        launches, arg_bytes, result_bytes, dispatch = self._keys
        pairs = ((launches, 1), (arg_bytes, facts[0]),
                 (result_bytes, facts[1]))
        if dispatch_s is not None:
            pairs += ((dispatch, dispatch_s),)
        get_registry().inc_many(pairs)

    def __call__(self, *args, **kwargs):
        try:
            sig, leaves = self._signature(args, kwargs)
        # enginelint: disable=RL001 (unhashable static leaf falls back to an uncounted dispatch)
        except Exception:
            with dispatch_guard():
                return self.fn(*args, **kwargs)
        facts = self._sigs.get(sig)
        new = False
        if facts is None:
            with self._lock:
                facts = self._sigs.get(sig)
                new = facts is None
                if new:
                    # result bytes are known once the first call returns
                    facts = self._sigs[sig] = [_leaf_bytes(leaves), 0]
        if not new:
            with dispatch_guard():
                t0 = time.perf_counter()
                try:
                    return self.fn(*args, **kwargs)
                finally:
                    self._count(facts, time.perf_counter() - t0)
        reg = get_registry()
        t0 = time.perf_counter()
        try:
            with reg.span(f"program.compile@{self.name}"), compile_guard():
                if _purge_if_pressured():
                    with self._lock:
                        self._sigs[sig] = facts  # purge cleared it
                out = self.fn(*args, **kwargs)
            import jax
            facts[1] = _leaf_bytes(jax.tree_util.tree_leaves(out))
            self._count(facts)
            return out
        finally:
            elapsed = time.perf_counter() - t0
            reg.inc("compile_count")
            reg.inc("compile_wall_s", elapsed)
            reg.observe("compile.wall_seconds", elapsed)


def instrument(fn, name: str) -> SharedJit:
    """Wrap an already-jitted callable with the SharedJit accounting
    under the program name ``name``."""
    return SharedJit(fn, name)


def guarded_jit(name: str, **jit_kwargs):
    """``jax.jit`` + the SharedJit wrapper, as a decorator.

    Module-level kernels (`@guarded_jit("join_probe", static_argnames=...)`)
    get the same accounting as fragment-keyed programs AND pass the
    process-wide compile/dispatch guard, so on the CPU backend no raw
    kernel can compile concurrently with another engine compile or
    dispatch (the XLA-build crash class documented above).  jax already
    requires static args to be hashable, so the signature bookkeeping
    mirrors jax's own executable cache exactly."""
    def wrap(fn):
        import jax
        return SharedJit(jax.jit(fn, **jit_kwargs), name)
    return wrap


# ---------------------------------------------------------------------------
# The process-wide cache
# ---------------------------------------------------------------------------

_CACHE: "OrderedDict[str, object]" = OrderedDict()
_LOCK = threading.Lock()


def get_or_build(key: str, builder, *, max_entries: int | None = None):
    """Return the process-wide entry for ``key``, building it once.

    ``builder()`` runs OUTSIDE the cache lock (it may construct several
    jit wrappers); a concurrent duplicate build is discarded in favor of
    the first published entry, so callers always share one object per
    key.  ``fusion_cache_hits`` / ``fusion_cache_misses`` move per
    lookup."""
    reg = get_registry()
    with _LOCK:
        got = _CACHE.get(key)
        if got is not None:
            _CACHE.move_to_end(key)
            reg.inc("fusion_cache_hits")
            return got
    val = builder()
    bound = max_entries if max_entries is not None \
        else COMPILE_CACHE_MAX_ENTRIES.default
    with _LOCK:
        got = _CACHE.get(key)
        if got is not None:
            reg.inc("fusion_cache_hits")
            return got
        reg.inc("fusion_cache_misses")
        _CACHE[key] = val
        while len(_CACHE) > max(bound, 1):
            _CACHE.popitem(last=False)
    return val


def shared_jit(key: str, fn, *, name: str, **jit_kwargs) -> SharedJit:
    """``get_or_build`` specialization for the common one-function case:
    jit ``fn`` (with ``jit_kwargs``, e.g. ``donate_argnums``) behind the
    process-wide key and wrap it as the program ``name``."""
    def build():
        import jax
        return SharedJit(jax.jit(fn, **jit_kwargs), name)
    return get_or_build(key, build)


def cache_info() -> dict:
    """Test/diagnostic hook: entry count + per-entry signature counts."""
    with _LOCK:
        entries = list(_CACHE.items())
    return {
        "entries": len(entries),
        "keys": [k for k, _ in entries],
        "signatures": {k: v.signature_count() for k, v in entries
                       if isinstance(v, SharedJit)},
    }


def reset_cache() -> None:
    """Test hook: drop every cached program (jax's own caches are
    untouched — they key on the jitted function object, which dies with
    the entry)."""
    with _LOCK:
        _CACHE.clear()
