"""Process-level device runtime: persistent XLA compilation cache.

The reference's hot loop has zero per-batch compilation (every kernel is a
pre-built libcudf entry point, SURVEY.md §3.3).  The XLA analog spends real
wall time in ``lowered.compile()`` — seconds to minutes per program at
the large capacity buckets on the TPU compiler — so the engine turns on
JAX's persistent compilation cache: each (program, capacity-bucket)
compiles once per cache directory.  Subsequent sessions and processes load
the serialized executable in milliseconds.

One placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
uses that directory and the engine sets none in code; otherwise the
directory is the fixed ``<checkout>/.jax_cache``.

Reference analog: the CUDA build ships precompiled fatbins in libcudf; the
TPU build's "precompiled kernels" are this cache directory.
"""
from __future__ import annotations

import os

from spark_rapids_tpu.conf import ConfEntry, register, _bool

__all__ = ["enable_compilation_cache", "ensure_runtime",
           "widen_thread_stacks"]

def _cache_mode(v) -> str:
    s = str(v).strip().lower()
    if s in ("auto",):
        return "auto"
    return "true" if _bool(v) else "false"


COMPILATION_CACHE_ENABLED = register(ConfEntry(
    "spark.rapids.tpu.compilationCache.enabled", "auto",
    "Persistent XLA compilation cache so each kernel capacity bucket "
    "compiles once per machine (reference: libcudf ships precompiled "
    "kernels; XLA must cache its executables instead).  The directory "
    "is JAX_COMPILATION_CACHE_DIR where the environment sets it, else "
    "the fixed <checkout>/.jax_cache.  'auto' (default): on for the "
    "TPU backend, where a compile costs seconds to minutes, and OFF "
    "for plain XLA:CPU unless the environment placed a cache — this "
    "XLA build's cpu_aot_loader re-checks machine features on every "
    "cached load and falsely flags its own entries "
    "(+prefer-no-scatter/gather are compile-time tuning prefs, not "
    "cpuinfo flags), burying CI logs in could-lead-to-SIGILL noise.  "
    "'true'/'false' force it.",
    conv=_cache_mode))

#: the one cache location the engine ever chooses itself: a fixed,
#: git-ignored path inside the checkout (a directory that moves between
#: runs never hits)
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_enabled_dir: str | None = None
_arrow_pinned = False
_pinned_arena = None
_pinned_borrowers = None       # weakref.WeakSet of current borrowers
_retired_arenas: list = []     # (arena, borrower WeakSet) until drained
import threading as _threading

_pinned_lock = _threading.Lock()
_stacks_widened = False


_cpu_sync_dispatch = False


def sync_cpu_dispatch() -> None:
    """Make XLA:CPU dispatch synchronous.

    With async dispatch (jax's default) an execution keeps running on
    the backend's internal thread pool after the python call returns;
    a compile starting on another engine thread then overlaps it, and
    this XLA build segfaults intermittently inside ``backend_compile``
    under exactly that overlap (same family as the pyarrow pool races
    pinned away in ``pin_arrow_threads``).  Synchronous dispatch closes
    the window; the engine's drain pool supplies the parallelism
    instead, so CPU throughput is unaffected.  Called once, when the
    compile cache first observes the CPU backend.
    """
    global _cpu_sync_dispatch
    if _cpu_sync_dispatch:
        return
    try:
        import jax
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    # enginelint: disable=RL001 (jax may be absent; sync dispatch only matters once it exists)
    except Exception:
        pass
    _cpu_sync_dispatch = True


def widen_thread_stacks(size: int = 64 * 1024 * 1024) -> None:
    """Deepen the stack of engine-created worker threads.

    XLA:CPU compilation (LLVM's recursive optimizer passes) can exceed
    the default 8 MiB pthread stack when a drain worker hits a new
    executable mid-query; the overflow lands as a bare SIGSEGV inside
    ``backend_compile``.  The stack is virtual address space committed
    lazily, so a deep reserve costs nothing.  ``threading.stack_size``
    only applies to threads created AFTER the call, so this runs at
    exec-layer import, before the first drain pool exists.
    """
    global _stacks_widened
    if _stacks_widened:
        return
    try:
        import threading
        threading.stack_size(size)
    except (ValueError, RuntimeError, OverflowError):
        pass
    _stacks_widened = True


def get_pinned_arena(size: int, borrower=None):
    """Process-level pinned staging arena (reference
    allocatePinnedMemory, GpuDeviceManager.scala:264-270: allocated once
    per executor process, not per query).  BufferCatalog shares it when
    pinnedPool.size > 0.

    Growth is by REPLACEMENT (the C++ arena cannot extend its mapping),
    and the replaced arena must outlive its borrowers: a catalog handed
    the old arena holds numpy views whose base pointers reach into the
    old mapping, so letting the ref drop here would run
    ``HostArena.__del__`` -> ``arena_destroy`` and turn every
    outstanding view into a use-after-free.  Replaced arenas are parked
    in ``_retired_arenas`` keyed by a WeakSet of their borrowers and
    only released (closing via ``__del__``) once every borrower has
    been collected.  Callers that may outlive a growth event pass
    themselves as ``borrower``; an untracked borrower set behaves like
    the pre-fix code (immediate replacement) for callers that provably
    don't retain views."""
    global _pinned_arena, _pinned_borrowers
    import weakref
    with _pinned_lock:
        # sweep: a retired arena whose borrowers all drained can close
        _retired_arenas[:] = [(a, s) for a, s in _retired_arenas
                              if len(s) > 0]
        if _pinned_arena is None or _pinned_arena.capacity < size:
            from spark_rapids_tpu.native import HostArena
            if _pinned_arena is not None and _pinned_borrowers and \
                    len(_pinned_borrowers) > 0:
                _retired_arenas.append((_pinned_arena, _pinned_borrowers))
            _pinned_arena = HostArena(size)
            _pinned_borrowers = weakref.WeakSet()
        if borrower is not None:
            if _pinned_borrowers is None:
                _pinned_borrowers = weakref.WeakSet()
            _pinned_borrowers.add(borrower)
        return _pinned_arena


def pin_arrow_threads() -> None:
    """Pin pyarrow's internal compute/IO pools to one thread.

    Empirically required in this runtime: pyarrow compute kernels
    (fill_null/cast/array) segfault intermittently when their internal
    pool runs concurrently with jax CPU execution on other python
    threads.  The engine supplies its own parallelism (drain worker
    pool), so single-threaded pyarrow conversions lose nothing.
    """
    global _arrow_pinned
    if _arrow_pinned:
        return
    try:
        import pyarrow as pa
        pa.set_cpu_count(1)
        pa.set_io_thread_count(1)
    # enginelint: disable=RL001 (pyarrow optional; thread pinning is best-effort)
    except Exception:
        pass
    _arrow_pinned = True


def enable_compilation_cache() -> str:
    """Idempotently turn on the persistent compilation cache and return
    the directory in effect.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already uses it, so no
    directory is set in code.  Unset: the fixed ``<checkout>/.jax_cache``
    (JAX's own key covers platform, flags and compiler version, so one
    flat directory serves every compile environment).  A directory that
    cannot be made raises — a chip run that silently recompiles
    everything per process is not a degraded mode worth having.
    """
    global _enabled_dir
    if _enabled_dir is not None:
        return _enabled_dir
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache everything: the many small programs add up on a cold start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = cache_dir
    return _enabled_dir


def ensure_runtime(conf=None) -> None:
    """Session-start runtime init (reference RapidsExecutorPlugin.init,
    Plugin.scala:124-154): arrow thread pinning + fail-fast device
    acquisition with HBM pool sizing (device.py) + compilation cache;
    semaphore wiring lives in memory/catalog.py."""
    pin_arrow_threads()
    settings = getattr(conf, "settings", None) or {}
    from spark_rapids_tpu.device import device_info, initialize_device
    initialize_device(conf)
    mode = COMPILATION_CACHE_ENABLED.get(settings)
    if mode == "auto":
        on = (device_info()["platform"] != "cpu"
              or bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    else:
        on = mode == "true"
    if on:
        enable_compilation_cache()
