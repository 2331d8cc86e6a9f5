"""Device manager: fail-fast runtime init + HBM pool sizing.

Reference: GpuDeviceManager.scala (initializeGpuAndMemory :120-127 —
device acquisition + memory-pool init at executor start;
computeRmmInitSizes :159-194 — alloc-fraction/reserve math) and
Plugin.scala's fail-fast discipline (checkCudfVersion :156-201 with an
override flag :198; executor init failure exits rather than hangs
:146-153).

TPU analog:

* validate the jax/pyarrow runtime once per process with CLEAR errors
  (instead of a version-skew crash deep inside a query), overridable via
  ``spark.rapids.tpu.allowIncompatibleRuntime``;
* acquire the accelerator under a DEADLINE — the reference's contract
  is fail-fast-and-relaunch, not hang;
* never fall back: the engine runs on CPU only when CPU was asked for
  (``JAX_PLATFORMS=cpu``); otherwise a resolved platform other than
  ``tpu`` is a TpuInitError, not a quiet CPU session;
* derive the spill catalog's HBM budget from the device's actual
  ``memory_stats()`` via allocFraction/reserve instead of a fixed
  default (PJRT exposes ``bytes_limit`` on TPU; a TPU without it is an
  error, the CPU backend leaves the budget to the conf default).
"""
from __future__ import annotations

import threading

from spark_rapids_tpu.conf import (ConfEntry, HBM_ALLOC_FRACTION, register,
                                   _bool, parse_bytes)

__all__ = ["TpuInitError", "initialize_device", "device_pool_limit",
           "device_info"]

MIN_JAX = (0, 4, 26)
MIN_PYARROW = (10, 0, 0)

INIT_TIMEOUT = register(ConfEntry(
    "spark.rapids.tpu.initTimeoutSeconds", 90,
    "Deadline for accelerator backend initialization. The reference "
    "treats executor init failure as fail-fast-and-relaunch "
    "(Plugin.scala:146-153), so device acquisition that has not "
    "returned by this deadline raises TpuInitError instead of hanging "
    "the session.", conv=int))

ALLOW_INCOMPATIBLE = register(ConfEntry(
    "spark.rapids.tpu.allowIncompatibleRuntime", False,
    "Continue despite a jax/pyarrow version below the supported minimum "
    "(reference cudf version-check override, Plugin.scala:198).",
    conv=_bool))

DEVICE_RESERVE = register(ConfEntry(
    "spark.rapids.memory.tpu.reserve", 256 << 20,
    "HBM held back from the spill catalog's budget for XLA scratch and "
    "runtime allocations (reference RESERVE in computeRmmInitSizes, "
    "GpuDeviceManager.scala:159-194).", conv=parse_bytes))


class TpuInitError(RuntimeError):
    """Raised when the device runtime cannot be initialized (version
    skew, backend init failure, or init deadline exceeded)."""


class _State:
    lock = threading.Lock()
    initialized = False
    platform: str | None = None
    device_kind: str | None = None
    device_count = 0
    hbm_bytes_limit: int | None = None
    pool_limit: int | None = None


def _vtuple(v: str) -> tuple:
    out = []
    for part in str(v).split(".")[:3]:
        digits = "".join(ch for ch in part if ch.isdigit())
        out.append(int(digits or 0))
    return tuple(out)


def _check_versions(allow_incompatible: bool) -> None:
    import jax
    problems = []
    if _vtuple(jax.__version__) < MIN_JAX:
        problems.append(f"jax {jax.__version__} < required "
                        f"{'.'.join(map(str, MIN_JAX))}")
    try:
        import pyarrow
        if _vtuple(pyarrow.__version__) < MIN_PYARROW:
            problems.append(f"pyarrow {pyarrow.__version__} < required "
                            f"{'.'.join(map(str, MIN_PYARROW))}")
    except ImportError:
        problems.append("pyarrow is not installed")
    if problems:
        msg = ("incompatible runtime: " + "; ".join(problems)
               + " (set spark.rapids.tpu.allowIncompatibleRuntime=true "
                 "to continue anyway)")
        if not allow_incompatible:
            raise TpuInitError(msg)
        import warnings
        warnings.warn(msg, RuntimeWarning)


def _cpu_requested() -> bool:
    """True when the process explicitly asked for the CPU platform
    (``JAX_PLATFORMS=cpu`` or the equivalent ``jax_platforms`` config,
    as tier-1 and tests/conftest.py do)."""
    import jax
    # the config is seeded from JAX_PLATFORMS at import, so it covers both
    requested = jax.config.jax_platforms or ""
    return requested.split(",")[0].strip().lower() == "cpu"


def _probe_devices():
    """Run in a worker thread: returns jax.devices() (the caller
    enforces the deadline)."""
    import jax
    return jax.devices()


def _compute_pool_limit(bytes_limit: int, alloc_fraction: float,
                        reserve: int) -> int:
    """allocFraction/reserve math (computeRmmInitSizes analog): the
    catalog may fill alloc_fraction of HBM minus the runtime reserve,
    floored so a tiny/misconfigured limit still leaves a usable pool."""
    pool = int(bytes_limit * alloc_fraction) - reserve
    return max(pool, 64 << 20)


def initialize_device(conf=None, probe=None) -> None:
    """Idempotent per-process device init (reference
    initializeGpuAndMemory, called from RapidsExecutorPlugin.init).

    ``probe`` overrides the device query for tests.
    """
    with _State.lock:
        if _State.initialized:
            return
        settings = getattr(conf, "settings", None) or {}
        _check_versions(ALLOW_INCOMPATIBLE.get(settings))
        timeout = float(INIT_TIMEOUT.get(settings))
        result: dict = {}

        def work():
            try:
                result["devices"] = (probe or _probe_devices)()
            # enginelint: disable=RL001 (probe error is forwarded via the result dict and re-raised by the caller)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                result["error"] = e

        t = threading.Thread(target=work, daemon=True,
                             name="tpu-device-init")
        t.start()
        t.join(timeout)
        if t.is_alive():
            raise TpuInitError(
                f"accelerator backend initialization did not complete in "
                f"{timeout:.0f}s; failing fast per "
                "spark.rapids.tpu.initTimeoutSeconds")
        if "error" in result:
            raise TpuInitError(
                f"accelerator backend initialization failed: "
                f"{result['error']}") from result.get("error")
        devices = result["devices"]
        if not devices:
            raise TpuInitError("no jax devices visible")
        d = devices[0]
        platform = getattr(d, "platform", "unknown")
        if platform != "tpu" and not _cpu_requested():
            raise TpuInitError(
                f"resolved platform {platform!r} is not 'tpu' and CPU was "
                "not requested: the engine does not fall back (set "
                "JAX_PLATFORMS=cpu to run on the CPU backend on purpose)")
        limit = None
        if platform == "tpu":
            try:
                limit = (d.memory_stats() or {}).get("bytes_limit")
            except Exception as e:
                raise TpuInitError(
                    f"tpu device memory_stats() failed: {e}") from e
            if not limit:
                raise TpuInitError(
                    "tpu device reports no bytes_limit in memory_stats(); "
                    "cannot size the HBM pool")
        _State.platform = platform
        _State.device_kind = getattr(d, "device_kind", "unknown")
        _State.device_count = len(devices)
        if limit:
            _State.hbm_bytes_limit = int(limit)
            _State.pool_limit = _compute_pool_limit(
                int(limit), HBM_ALLOC_FRACTION.get(settings),
                DEVICE_RESERVE.get(settings))
        _State.initialized = True


def device_pool_limit() -> int | None:
    """Catalog HBM budget from the initialized device's stats; None when
    uninitialized or the platform exposes no memory stats (callers fall
    back to spark.rapids.memory.tpu.spillStoreSize)."""
    return _State.pool_limit if _State.initialized else None


def device_info() -> dict:
    """Snapshot for logs/diagnostics (reference logs GPU + pool sizes at
    executor start)."""
    return {
        "initialized": _State.initialized,
        "platform": _State.platform,
        "device_kind": _State.device_kind,
        "device_count": _State.device_count,
        "hbm_bytes_limit": _State.hbm_bytes_limit,
        "pool_limit": _State.pool_limit,
    }


def _reset_for_tests() -> None:
    with _State.lock:
        _State.initialized = False
        _State.platform = _State.device_kind = None
        _State.device_count = 0
        _State.hbm_bytes_limit = _State.pool_limit = None
