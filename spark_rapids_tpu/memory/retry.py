"""Operator-level OOM retry with split-and-retry.

Reference mapping (SURVEY §2.2): the plugin grows the alloc-failure
spill hook (DeviceMemoryEventHandler.onAllocFailure) into a full retry
framework — RmmRapidsRetryIterator.scala's ``withRetry`` /
``withRetryNoSplit`` / ``splitAndRetry``: an operator step runs inside
a retry scope; on RetryOOM it is re-attempted after spilling, and on
SplitAndRetryOOM its input is split in half by rows and each half is
retried, emitting partial outputs in order.  Operator state is
checkpoint/restored around each attempt (Retryable.scala) so a failed
attempt leaves no half-updated accumulators.

The TPU port has no RMM alloc callback — OOM is a caught XLA
``RESOURCE_EXHAUSTED`` around dispatch (or around the *sync point* on
async backends, where the error surfaces at the first
``block_until_ready``/``device_get`` after the poisoned dispatch).
Three scopes cover both shapes:

* :func:`with_retry` — run ``fn(batch)`` over one input (ColumnBatch or
  SpillableColumnarBatch).  On OOM: spill; when spill frees nothing,
  unpin the input, split it in half by rows, and retry each half
  recursively — partial outputs are returned in row order — down to
  ``spark.rapids.memory.tpu.oomRetry.minSplitRows``.
* :func:`with_retry_no_split` — same, splitting disabled (the reference
  uses withRetryNoSplit where partial outputs would break semantics,
  e.g. GpuSortExec's total sort).
* :func:`retry_sync` — guard a blocking sync of asynchronously
  dispatched work (the chunk-flush ``device_get`` in aggregate/join).
  On OOM: spill, then call ``redo()`` to re-dispatch the poisoned
  values (re-deriving them from retained inputs, which may split), and
  sync again.  This closes the ``_sync_dispatch`` gap where async
  backends surfaced OOMs at sync points outside any retry loop.
"""
from __future__ import annotations

from functools import partial

import jax

from spark_rapids_tpu.columnar.batch import ColumnBatch, round_capacity
from spark_rapids_tpu.conf import bool_conf, int_conf
from spark_rapids_tpu.memory.catalog import (SpillableColumnarBatch,
                                             _sync_dispatch)
from spark_rapids_tpu.ops import kernels as dk

__all__ = ["with_retry", "with_retry_no_split", "retry_sync", "split_half",
           "is_oom", "SplitAndRetryOOM"]


OOM_RETRY_ENABLED = bool_conf(
    "spark.rapids.memory.tpu.oomRetry.enabled", True,
    "Operator-level OOM retry: on RESOURCE_EXHAUSTED the failed step is "
    "re-attempted after spilling from the buffer catalog, and when spill "
    "frees nothing the input batch is split in half by rows and each "
    "half retried (reference RmmRapidsRetryIterator withRetry / "
    "split-and-retry).  Disabled: only the plain spill-and-retry "
    "dispatch hook runs.")
OOM_RETRY_MAX = int_conf(
    "spark.rapids.memory.tpu.oomRetry.maxRetries", 8,
    "Attempts per input piece before the OOM propagates (a split "
    "produces fresh pieces with a fresh budget).")
OOM_RETRY_MIN_ROWS = int_conf(
    "spark.rapids.memory.tpu.oomRetry.minSplitRows", 32,
    "Row floor for split-and-retry: a batch is not split below this "
    "many rows per half; at the floor the OOM propagates (reference "
    "splitSpillableInHalfByRows' single-row stop).")


class SplitAndRetryOOM(RuntimeError):
    """OOM that survived spilling with splitting unavailable or
    exhausted (reference com.nvidia.spark.rapids.jni.SplitAndRetryOOM)."""


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory")


def is_oom(ex: BaseException) -> bool:
    """True when ``ex`` is an HBM exhaustion (real XLA or injected).
    Terminal errors (QueryCancelled / QueryDeadlineExceeded /
    MapOutputLostError carry ``terminal = True``) are never OOMs, no
    matter what their message says — a cancelled query must not be
    split-and-retried back to life."""
    if getattr(ex, "terminal", False):
        return False
    msg = str(ex)
    return any(m in msg for m in _OOM_MARKERS)


@partial(jax.jit, static_argnames=("out_cap",))
def _slice_rows_jit(batch: ColumnBatch, start, count, out_cap: int):
    import jax.numpy as jnp
    idx = jnp.asarray(start, jnp.int32) + jnp.arange(out_cap,
                                                     dtype=jnp.int32)
    return dk.take(batch, idx, jnp.asarray(count, jnp.int32))


_SHARED_SLICE: dict = {}


def _shared_slice():
    """Split compiles a new executable per (shape, out_cap) right in the
    middle of an OOM storm, concurrently with other drain threads'
    compiles; route it through the shared-jit wrapper (which serializes
    CPU compiles).  Bound lazily — memory/ sits below exec/."""
    w = _SHARED_SLICE.get("slice")
    if w is None:
        from spark_rapids_tpu.exec.compile_cache import instrument
        w = _SHARED_SLICE.setdefault("slice", instrument(_slice_rows_jit,
                                                      "batch_slice_rows"))
    return w


def split_half(batch: ColumnBatch) -> list[ColumnBatch]:
    """Split a front-packed batch into two row-contiguous halves, each
    at its own right-sized pow2 capacity (reference
    splitSpillableInHalfByRows, RmmRapidsRetryIterator.scala)."""
    n = batch.host_num_rows()
    if n <= 1:
        raise SplitAndRetryOOM(f"cannot split a {n}-row batch further")
    h = (n + 1) // 2
    slice_rows = _shared_slice()
    lo = slice_rows(batch, dk.device_scalar(0), dk.device_scalar(h),
                    round_capacity(h))
    hi = slice_rows(batch, dk.device_scalar(h),
                    dk.device_scalar(n - h),
                    round_capacity(max(n - h, 1)))
    # the jit boundary strips known_rows; the halves' counts are host
    # facts here, so restore them (metrics then never double-count a
    # split: each half reports its own exact rows)
    lo.known_rows = h
    hi.known_rows = n - h
    return [lo, hi]


def _check_oom_fault(faults, op: str, rows: int | None = None) -> None:
    """Fire memory.oom / memory.oom.until_rows injection points.  The
    ``rows`` context enables until_rows rules: OOM persists while the
    dispatched batch is above the threshold, so split-and-retry is
    deterministically provable without a real device."""
    ctx = {"op": op}
    if rows is not None:
        ctx["rows"] = rows
    act = faults.check("memory.oom", **ctx)
    if act is None:
        act = faults.check("memory.oom.until_rows", **ctx)
    if act is not None:
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: injected fault: simulated HBM OOM "
            f"(spark.rapids.test.faults {act.point})")


def _bump(catalog, key: str) -> None:
    catalog.metrics[key] = catalog.metrics.get(key, 0) + 1


def _reclaim(catalog, need_bytes: int) -> int:
    """Free device memory for a failed allocation, sized to the actual
    need (the dispatched batch's device bytes; the governor applies a
    conf'd floor, spark.rapids.memory.governor.minSpillBytes) instead
    of the historical blind ``device_limit // 4`` sweep.  Governed
    catalogs arbitrate cross-query (own lowest-priority buffers first,
    then younger peers', wound-wait ordered — memory/governor.py);
    ungoverned catalogs keep the legacy sweep byte-identical to the
    pre-governor engine."""
    gov = getattr(catalog, "governor", None)
    if gov is not None:
        return gov.reclaim(catalog, need_bytes)
    return catalog.spill_device(catalog.device_limit // 4)


def with_retry(fn, catalog, inp, *, split=split_half, op: str | None = None,
               settings=None, checkpoint=None, restore=None,
               pairs: bool = False, max_retries: int | None = None,
               min_split_rows: int | None = None, sync: bool | None = None):
    """Run ``fn(batch)`` under the OOM retry scope.

    ``inp`` is a ColumnBatch or a SpillableColumnarBatch (materialized
    per attempt, pinned through the spill pass — evicting our own input
    is no progress — and closed when replaced by split halves).
    Returns the list of outputs
    — one per final input piece, in row order; with ``pairs=True`` each
    element is ``(piece, output)`` so callers can retain the processed
    piece for a later :func:`retry_sync` redo.

    ``checkpoint()``/``restore(state)`` bracket each attempt: whatever
    external state ``fn`` mutates must be restorable so a failed attempt
    leaves no half-applied update (reference Retryable.scala contract).
    """
    settings = settings if settings is not None else {}
    if not OOM_RETRY_ENABLED.get(settings):
        from spark_rapids_tpu.memory.catalog import run_with_spill_retry
        if isinstance(inp, SpillableColumnarBatch):
            b = inp.get()
            try:
                r = run_with_spill_retry(fn, catalog, b)
            finally:
                inp.unpin()
        else:
            r = run_with_spill_retry(fn, catalog, inp)
        return [(inp, r)] if pairs else [r]
    if max_retries is None:
        max_retries = OOM_RETRY_MAX.get(settings)
    if min_split_rows is None:
        min_split_rows = OOM_RETRY_MIN_ROWS.get(settings)
    faults = getattr(catalog, "faults", None)
    do_sync = _sync_dispatch() if sync is None else sync
    name = op or getattr(fn, "__name__", str(fn))

    out = []
    pending: list = [inp]
    while pending:
        piece = pending.pop(0)
        spillable = isinstance(piece, SpillableColumnarBatch)
        attempts = 0
        while True:
            saved = checkpoint() if checkpoint is not None else None
            b = piece.get() if spillable else piece
            try:
                if faults is not None:
                    _check_oom_fault(faults, name, b.host_num_rows())
                r = fn(b)
                if do_sync:
                    jax.block_until_ready(jax.tree_util.tree_leaves(r))
            except (RuntimeError, jax.errors.JaxRuntimeError) as ex:
                if not is_oom(ex):
                    if spillable:
                        piece.unpin()
                    raise
                if restore is not None:
                    restore(saved)
                _bump(catalog, "oom_retries")
                attempts += 1
                if attempts > max_retries:
                    if spillable:
                        piece.unpin()
                    raise
                # spill with the piece still PINNED: evicting our own
                # input is not progress — it would round-trip back on
                # the next attempt and the budget would exhaust without
                # ever splitting.  Sized to the failed work (input
                # bytes), not a blind quarter of the budget
                try:
                    need = int(b.device_size_bytes())
                except Exception:  # enginelint: disable=RL001 (sizing is best-effort; the governor floor covers it)
                    need = 0
                freed = _reclaim(catalog, need)
                if spillable:
                    piece.unpin()
                if freed > 0:
                    continue  # room was made: retry the piece whole
                # spill freed nothing — every unpinned buffer is already
                # out of HBM: halve the working set instead
                n = b.host_num_rows()
                if split is None:
                    raise SplitAndRetryOOM(
                        f"{name}: OOM with nothing left to spill and "
                        "splitting disabled") from ex
                if n <= 1 or (n + 1) // 2 < min_split_rows:
                    raise SplitAndRetryOOM(
                        f"{name}: OOM at the {min_split_rows}-row split "
                        f"floor ({n} rows)") from ex
                halves = split(b)
                if spillable:
                    piece.close()  # replaced by the halves
                _bump(catalog, "oom_splits")
                pending[0:0] = list(halves)
                break
            else:
                out.append((piece, r) if pairs else r)
                if spillable:
                    piece.unpin()
                break
    return out


def with_retry_no_split(fn, catalog, inp, **kw):
    """`with_retry` with split-and-retry disabled — for steps whose
    partial outputs would break semantics (reference withRetryNoSplit:
    total sort, final-merge concat)."""
    kw["split"] = None
    return with_retry(fn, catalog, inp, **kw)


def retry_sync(sync_fn, catalog, *, redo=None, op: str = "sync",
               settings=None, max_retries: int | None = None):
    """Guard a blocking sync point of asynchronously dispatched work.

    On the ``tpu`` backend dispatches don't block
    (``_sync_dispatch()`` is False), so an OOM raised by XLA for an
    earlier dispatch surfaces HERE, outside every dispatch-level retry
    loop.  On OOM: spill from the
    catalog, call ``redo()`` to re-dispatch the poisoned device values
    from retained inputs (a redo may itself run :func:`with_retry` and
    split), then run ``sync_fn()`` again."""
    settings = settings if settings is not None else {}
    if not OOM_RETRY_ENABLED.get(settings):
        return sync_fn()
    if max_retries is None:
        max_retries = OOM_RETRY_MAX.get(settings)
    faults = getattr(catalog, "faults", None)
    attempts = 0
    while True:
        try:
            if faults is not None:
                _check_oom_fault(faults, op)
            return sync_fn()
        except (RuntimeError, jax.errors.JaxRuntimeError) as ex:
            if not is_oom(ex):
                raise
            _bump(catalog, "oom_retries")
            attempts += 1
            if attempts > max_retries:
                raise
            # a sync point reports no allocation size; the governor's
            # minSpillBytes floor sizes the request (ungoverned: legacy
            # quarter-budget sweep)
            _reclaim(catalog, 0)
            if redo is not None:
                redo()
