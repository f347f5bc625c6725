"""Bounded write-behind executor: the spill side of the out-of-core
overlap tier.

The reference's foxxll-backed BlockPool never blocks an operator on a
spill write — sorted runs stream to disk while the next run forms
(PAPER.md, the async external-memory block manager Thrill's whole
batch story rests on). This module is that contract for the Python
layers that used to flush synchronously on the caller's thread: the
BlockPool pure-python fallback and em_sort's run spilling.

:class:`AsyncWriter` is the PR-6 async-sender pattern
(data/multiplexer.py ``_exchange_frames_async``) recast for storage:

* ONE background writer thread, FIFO — submission order is completion
  order, so run files land in the order the sort produced them;
* a bounded queue (``THRILL_TPU_WRITEBACK_QUEUE``) applies
  backpressure instead of buffering every pending run in RAM;
* errors are captured and RE-RAISED on the submitting thread at the
  next ``submit``/``flush``/``close`` — the poison scope: a failed
  flush surfaces with its root cause before any consumer reads the
  (absent) data, never silent loss. ``poison=False`` writers (the
  BlockPool fallback, where a failed eviction write legitimately
  keeps the block RAM-resident) route errors to an ``on_error``
  callback instead;
* ``THRILL_TPU_WRITEBACK=0`` (or the ``THRILL_TPU_OVERLAP=0`` master
  switch) runs every job inline on the caller — today's synchronous
  behavior exactly, same bytes, same file naming.

The ``data.spill.writeback`` fault site fires on the WRITER thread
before a job runs (nothing written yet), exercising both contracts:
poison writers surface it at the barrier, degrade writers keep the
data resident and note the recovery.

:func:`make_readahead` is the read-side sibling for sites that
prefetch BLOCKS rather than byte streams (the k-way merge's
one-slot-per-run readahead, the double-buffered spill restore): a
short-lived, bounded thread pool the caller shuts down with its
operation, so no framework thread outlives the work it overlapped.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

from ..common import faults
from ..common.config import _env_flag, overlap_enabled
from ..common.iostats import IO as _IOSTATS

_F_WRITEBACK = faults.declare("data.spill.writeback")


def writeback_enabled() -> bool:
    """THRILL_TPU_WRITEBACK=0 restores synchronous spill writes on the
    caller's thread (byte-identical, same file naming); the
    THRILL_TPU_OVERLAP=0 master switch disables it too."""
    return overlap_enabled() and _env_flag("THRILL_TPU_WRITEBACK", True)


def writeback_queue_depth() -> int:
    """THRILL_TPU_WRITEBACK_QUEUE: max queued spill jobs (default 2 —
    at most depth+1 runs resident beyond the synchronous baseline)."""
    try:
        return max(1, int(os.environ.get("THRILL_TPU_WRITEBACK_QUEUE",
                                         "2") or 2))
    except ValueError:
        return 2


class AsyncWriter:
    """Single-threaded bounded write-behind queue (see module doc)."""

    def __init__(self, what: str, depth: Optional[int] = None,
                 sync: Optional[bool] = None, poison: bool = True,
                 on_error: Optional[Callable[[BaseException, Any],
                                             None]] = None) -> None:
        self.what = what
        self.sync = (not writeback_enabled()) if sync is None else sync
        self.depth = writeback_queue_depth() if depth is None else depth
        self.poison = poison
        self.on_error = on_error
        self._cv = threading.Condition()
        self._jobs: collections.deque = collections.deque()
        self._err: Optional[BaseException] = None
        self._closed = False
        self._idle = True
        self._t: Optional[threading.Thread] = None
        self.jobs_run = 0
        self.bytes_written = 0

    # -- writer thread --------------------------------------------------
    def _ensure_thread(self) -> None:
        if self._t is None:
            self._t = threading.Thread(target=self._run, daemon=True,
                                       name="thrill-tpu-writeback")
            self._t.start()

    def _run(self) -> None:
        while True:
            with self._cv:
                while not self._jobs and not self._closed:
                    self._idle = True
                    self._cv.notify_all()
                    self._cv.wait(0.1)
                if not self._jobs and self._closed:
                    self._idle = True
                    self._cv.notify_all()
                    return
                fn, tag = self._jobs.popleft()
                self._idle = False
                self._cv.notify_all()
            try:
                if faults.REGISTRY.active():
                    faults.check(_F_WRITEBACK, what=self.what, tag=tag)
                t0 = time.perf_counter()
                nbytes = int(fn() or 0)
                _IOSTATS.add(io_busy_s=time.perf_counter() - t0,
                             writeback_bytes=nbytes)
                with self._cv:
                    self.jobs_run += 1
                    self.bytes_written += nbytes
                    self._cv.notify_all()
            except BaseException as e:
                if self.poison:
                    # poison scope: drop the backlog (its files will
                    # never be read — the error surfaces first) and
                    # park the error for the submitting thread
                    with self._cv:
                        self._err = e
                        self._jobs.clear()
                        self._idle = True
                        self._cv.notify_all()
                    return
                faults.note("recovery", what=f"{self.what}.degraded",
                            error=repr(e)[:200])
                if self.on_error is not None:
                    try:
                        self.on_error(e, tag)
                    except Exception:
                        pass
                with self._cv:
                    self._cv.notify_all()

    # -- submitting side ------------------------------------------------
    def _raise_pending(self) -> None:
        if self._err is not None:
            err, self._err = self._err, None
            self._closed = True
            raise err

    def submit(self, fn: Callable[[], Any], tag: Any = None) -> None:
        """Queue one write job (``fn() -> bytes written``); runs inline
        in sync mode. Blocks (counted as ``io_wait_s``) only when the
        queue is ``depth`` jobs behind; re-raises a pending writer
        error instead of queueing behind a dead writer."""
        if self.sync:
            nbytes = int(fn() or 0)
            _IOSTATS.add(writeback_bytes=nbytes)
            with self._cv:
                self.jobs_run += 1
                self.bytes_written += nbytes
            return
        self._ensure_thread()
        t0 = None
        with self._cv:
            self._raise_pending()
            if self._closed:
                raise RuntimeError(f"{self.what}: writer is closed")
            while len(self._jobs) >= self.depth and self._err is None:
                if t0 is None:
                    t0 = time.perf_counter()
                self._cv.wait(0.1)
            self._raise_pending()
            self._jobs.append((fn, tag))
            depth_now = len(self._jobs) + (0 if self._idle else 1)
            self._cv.notify_all()
        if t0 is not None:
            _IOSTATS.add(io_wait_s=time.perf_counter() - t0)
        _IOSTATS.note_queue_depth(depth_now)

    def flush(self) -> None:
        """Barrier: every queued/in-flight job is durably done (or the
        writer's error re-raises here, before any consumer trusts the
        flushed data)."""
        if self.sync or self._t is None:
            self._raise_pending()
            return
        t0 = time.perf_counter()
        with self._cv:
            while (self._jobs or not self._idle) and self._err is None:
                self._cv.wait(0.1)
            dt = time.perf_counter() - t0
            self._raise_pending()
        if dt > 1e-4:
            _IOSTATS.add(io_wait_s=dt)

    def close(self, drain: bool = True) -> None:
        """Stop the writer. ``drain=True`` barriers first (and
        re-raises a pending error); ``drain=False`` abandons the
        backlog (abort paths — the job is already failing)."""
        if self._t is None:
            if drain:
                self._raise_pending()
            self._closed = True
            return
        try:
            if drain:
                self.flush()
        finally:
            with self._cv:
                self._closed = True
                if not drain:
                    self._jobs.clear()
                    self._err = None
                self._cv.notify_all()
            # the join must OUTLAST a slow in-flight job: callers free
            # the backing store right after close() (em_sort's finally
            # does pool.close()), so returning with the writer alive
            # would let the job write into freed memory. A genuinely
            # wedged disk therefore blocks close loudly rather than
            # corrupting — same contract as the native store's
            # destructor barrier.
            self._t.join(timeout=30)
            while self._t.is_alive():
                import sys
                print(f"thrill_tpu.writeback: {self.what} writer "
                      f"still flushing; waiting before teardown",
                      file=sys.stderr)
                self._t.join(timeout=30)

    def __enter__(self) -> "AsyncWriter":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        # on an exception the scope is already poisoned: don't let a
        # drain barrier (or its own error) mask the original
        self.close(drain=exc_type is None)


def make_readahead(depth: int, workers: int = 0
                   ) -> Optional[ThreadPoolExecutor]:
    """A bounded, short-lived block-readahead pool for one operation
    (k-way merge, spill restore), or None when prefetch is off
    (``depth`` <= 0). The CALLER shuts it down (``shutdown(wait=...)``)
    when the operation ends — readahead threads never outlive the work
    they overlap."""
    if depth <= 0:
        return None
    return ThreadPoolExecutor(
        max_workers=workers or max(2, min(depth, 8)),
        thread_name_prefix="thrill-tpu-readahead")


def readahead_get(fut, demand: Callable[[], Any], what: str) -> Any:
    """Consume one readahead future with the degrade contract: a
    background failure (injected ``vfs.prefetch`` or a real read
    error) falls back to the DEMAND read on the calling thread —
    slower, never wrong data. Readahead is OPPORTUNISTIC: a future
    still queued behind the pool (not yet started) is cancelled and
    the block demand-read instead — waiting on the backlog would turn
    a cheap RAM-resident get into a queue stall. Accounts
    hit/miss/wait like the vfs reader."""
    if fut is None:
        return demand()
    waited = False
    if fut.done():
        pass
    elif fut.cancel():
        # never started: the consumer outran the pool — demand-read
        _IOSTATS.add(prefetch_misses=1)
        return demand()
    else:
        # mid-flight: finishing the started read beats issuing a
        # second one for the same bytes
        t0 = time.perf_counter()
        try:
            fut.result()
        except BaseException:
            pass
        _IOSTATS.add(prefetch_misses=1,
                     io_wait_s=time.perf_counter() - t0)
        waited = True
    try:
        out = fut.result()
    except BaseException as e:
        # a completed-with-error future is a MISS (the hit-rate signal
        # must not rise when prefetch fails), then the degrade path
        if not waited:
            _IOSTATS.add(prefetch_misses=1)
        faults.note("recovery", what=f"{what}.prefetch_degraded",
                    error=repr(e)[:200])
        return demand()
    if not waited:
        _IOSTATS.add(prefetch_hits=1)
    return out


def overlapped_fetch(items, fetch: Callable[[Any], Any], what: str,
                     ra: Optional[ThreadPoolExecutor],
                     skip_fn: Optional[Callable[[Any], bool]] = None,
                     stats: Optional[dict] = None):
    """Yield ``(item, fetch(item))`` with the NEXT item's fetch already
    in flight behind the current item's consumption — THE one-ahead
    overlap loop (checkpoint shard restores, HBM spill restores), in
    one place so the degrade contract and hit/miss accounting cannot
    diverge between call sites. ``skip_fn`` marks items whose fetch is
    cheap inline (RAM-resident blocks — the surgical policy);
    ``stats["prefetched"]`` counts the fetches that actually rode the
    pool. ``ra=None`` degrades to plain sequential fetches."""
    items = list(items)
    fut = None
    for j, it in enumerate(items):
        nxt = None
        if ra is not None and j + 1 < len(items):
            nit = items[j + 1]
            if skip_fn is None or not skip_fn(nit):
                nxt = ra.submit(readahead_job(
                    lambda nit=nit: fetch(nit), what))
                if stats is not None:
                    stats["prefetched"] = stats.get("prefetched", 0) + 1
        out = readahead_get(fut, lambda it=it: fetch(it), what)
        fut = nxt
        yield it, out


def readahead_job(fn: Callable[[], Any],
                  what: str) -> Callable[[], Any]:
    """Wrap a block-load callable for the readahead pool: the
    ``vfs.prefetch`` injection gate plus busy-time accounting. Every
    wrap is one SUBMISSION (``prefetch_submits``) — with the spill
    store settled at the merge barrier this count is deterministic,
    which is what lets the perf sentinel contract it exactly."""
    _IOSTATS.add(prefetch_submits=1)

    def job():
        if faults.REGISTRY.active():
            faults.check("vfs.prefetch", what=what)
        t0 = time.perf_counter()
        try:
            return fn()
        finally:
            _IOSTATS.add(io_busy_s=time.perf_counter() - t0)
    return job
