"""Device-OOM retry with split-and-retry, plus the shared retry-backoff
helper for the shuffle plane.

Reference: RmmRapidsRetryIterator.scala (withRetry / withRetryNoSplit) +
SplitAndRetryOOM — on a device allocation failure the operator first lets
the spill layer free memory and retries, then splits its input and
processes the halves independently.

TPU shape: XLA raises RESOURCE_EXHAUSTED from a kernel launch; we ask the
spill catalog to demote everything it can, retry once, then split the
input batch rows in half and recurse (bounded depth).  Under JAX async
dispatch the error can surface at a later consumption point, so the
retry scope synchronizes on ``fn``'s result before returning — a
deferred launch failure is raised HERE, inside the scope that can
recover, not downstream where nothing can."""

from __future__ import annotations

import random
import time
from typing import Callable, List, Optional

from spark_rapids_tpu import faults


def is_device_oom(e: BaseException) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s)


class Backoff:
    """Exponential backoff with a cap and decorrelating jitter: attempt
    ``k`` (0-based) sleeps ``min(cap, base * 2^k)`` scaled by a uniform
    factor in ``[1 - jitter, 1]``.  Seedable so tests replay the exact
    delay sequence.  Used by the shuffle manager between peer retries so
    a recovering peer is not hammered back-to-back (reference: the
    plugin retries UCX fetches on a delay rather than in a hot loop)."""

    def __init__(self, base: float = 0.05, cap: float = 2.0,
                 jitter: float = 0.2, seed: Optional[int] = None):
        self.base = max(0.0, float(base))
        self.cap = max(0.0, float(cap))
        self.jitter = min(1.0, max(0.0, float(jitter)))
        self._rng = random.Random(seed)

    def delay(self, attempt: int) -> float:
        d = min(self.cap, self.base * (2 ** max(0, attempt)))
        if self.jitter > 0.0:
            d *= 1.0 - self.jitter * self._rng.random()
        return d

    def sleep(self, attempt: int) -> float:
        d = self.delay(attempt)
        if d > 0.0:
            time.sleep(d)
        return d


def split_batch_half(batch):
    """Default splitter: top/bottom halves by row position."""
    n = batch.num_rows
    mid = n // 2
    return [batch.slice_rows(0, mid), batch.slice_rows(mid, n - mid)]


def _collect_arrays(obj, out: List) -> None:
    """Gather every device array reachable from ``fn``'s result (lists/
    tuples, columnar batches, bare arrays)."""
    if obj is None:
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _collect_arrays(o, out)
        return
    cols = getattr(obj, "columns", None)
    if cols is not None:
        for c in cols:
            # an encoded column's device planes are its CODES — reading
            # .data here would force the late decode this sync exists
            # to avoid touching (columnar/encoding.py)
            if hasattr(c, "codes"):
                planes = (c.codes, c.validity, None)
            else:
                planes = (getattr(c, "data", None),
                          getattr(c, "validity", None),
                          getattr(c, "chars", None))
            for a in planes:
                if a is not None and hasattr(a, "block_until_ready"):
                    out.append(a)
        return
    if hasattr(obj, "block_until_ready"):
        out.append(obj)


def _sync_result(obj) -> None:
    """Force any deferred device work in ``fn``'s result to complete so
    an async launch failure raises inside the retry scope.  One batched
    ``jax.block_until_ready`` over every reachable array (a single wait,
    not one sync round trip per plane)."""
    arrays = []
    _collect_arrays(obj, arrays)
    if arrays:
        # the host's longest wait on the device in most queries: spanned
        # (d2h.sync:retry) and counted like every other blocking read
        from spark_rapids_tpu.columnar.transfer import blocking_wait
        blocking_wait(arrays, "retry")


def with_retry(fn: Callable, batch, ctx=None,
               split: Optional[Callable] = None,
               max_depth: int = 3,
               fire_launch_site: bool = True) -> List:
    """Run ``fn(batch)`` returning ``[result]``; on device OOM spill
    everything spillable and retry, then split and recurse.  With
    ``split=None`` behaves like withRetryNoSplit (spill-retry only).

    The ``kernel.launch`` fault site fires here, so conf-driven tests
    exercise the whole spill-retry-split path without monkeypatching
    (the injectOOM analog, RmmSparkRetrySuiteBase).  Callers whose
    ``fn`` fires the site itself — the fused stage dispatches it at
    the ACTUAL kernel launch, once per attempt — pass
    ``fire_launch_site=False`` so one attempt never consumes two
    injection triggers.

    Synchronization policy: EVERY attempt synchronizes on ``fn``'s
    result (one batched ``jax.block_until_ready``) before the scope
    returns.  Under JAX async dispatch a launch failure can otherwise
    surface at a later consumption point where nothing can recover —
    the sort/window/FK-join fns return un-synced device arrays, so
    without the sync their retries would never fire for real device
    OOMs.  The lost overlap is recovered structurally by the scan
    prefetch/double-buffer pipeline (docs/io_overlap.md), which overlaps
    host work with device compute across batches rather than relying on
    un-synced results escaping the retry scope.

    The split call itself runs under the same spill-retry: materializing
    both halves while the original batch is live can OOM under exactly
    the pressure that triggered the split, so a split-time OOM gets one
    pressure-relief attempt instead of propagating uncaught."""
    try:
        if fire_launch_site:
            faults.maybe_fail_oom("kernel.launch")
        res = fn(batch)
        _sync_result(res)
        return [res]
    except Exception as e:
        if not is_device_oom(e):
            raise
        if ctx is not None:
            # pressure-relief retry: demote every unpinned handle (a
            # catalog-locked sweep; the budget itself is never mutated, so
            # concurrent retries cannot corrupt it)
            ctx.runtime.catalog.spill_all()
            try:
                res = fn(batch)
                _sync_result(res)
                return [res]
            except Exception as e2:
                if not is_device_oom(e2):
                    raise
        if split is None or max_depth <= 0 or batch.num_rows <= 1:
            raise
    out: List = []
    for part in _split_with_relief(split, batch, ctx):
        out.extend(with_retry(fn, part, ctx, split, max_depth - 1,
                              fire_launch_site=fire_launch_site))
    return out


def _split_with_relief(split: Callable, batch, ctx) -> List:
    """Run ``split(batch)`` with one spill-relief retry on device OOM:
    the halves are fresh device allocations gathered while the original
    batch is still live, so the split can itself exhaust memory under
    the very pressure that forced it (ADVICE r05; the reference makes
    split inputs spillable before materializing halves)."""
    try:
        halves = split(batch)
        _sync_result(halves)
        return halves
    except Exception as e:
        if not is_device_oom(e) or ctx is None:
            raise
        ctx.runtime.catalog.spill_all()
        halves = split(batch)
        _sync_result(halves)
        return halves
