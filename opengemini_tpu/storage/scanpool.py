"""Parallel chunk-decode pool: the host-side scan floor lifter.

Every query ultimately funnels through TSF chunk decode
(storage/encoding.py) — numpy/zlib/native-codec work that releases the
GIL — yet until this module the shard scan loops decoded one chunk at a
time on the query thread.  The 1B-row at-spec run measured ~4.7M rows/s
of serial decode: a floor that would starve any accelerator long before
the paper's >=8x target (the same lesson as near-data-processing and
compressed-GPU-analytics systems — the decode/marshal stage must be
parallel and overlapped with compute, or the device waits on the host).

Two primitives, both preserving submission order so results are
bit-identical to the serial path:

  map_ordered(jobs, est_bytes)
      Fan the decode jobs across a shared worker pool, yield results in
      submission order.  In-flight decoded bytes are bounded by a budget
      (backpressure: submission stalls until the consumer drains), so a
      million-chunk scan never materializes the whole file set at once.

  prefetch_ordered(thunks)
      Double-buffered pipeline: a dedicated producer thread runs thunk
      N+1 (e.g. the next shard's bulk read) while the consumer feeds
      thunk N's rows into the device batches.  Bounded queue = bounded
      look-ahead.

Kill semantics: both primitives capture the calling thread's query id
and re-check it on the helper threads, so KILL QUERY interrupts a scan
mid-decode exactly like the serial path (the existing per-chunk
TRACKER.check() cancellation points).

Knobs (documented in README.md):
  OGT_SCAN_WORKERS      decode worker threads; 0/unset = one per core
                        (capped at 16), 1 = serial decode (the old path)
  OGT_SCAN_INFLIGHT_MB  in-flight decoded-bytes budget (default 256)
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from opengemini_tpu.utils import lockdep
from collections import deque
from concurrent.futures import ThreadPoolExecutor

from opengemini_tpu.utils import tracing
from opengemini_tpu.utils.governor import InflightGauge
from opengemini_tpu.utils.querytracker import GLOBAL as _TRACKER
from opengemini_tpu.utils.stats import GLOBAL as _STATS


def _auto_workers() -> int:
    if hasattr(os, "sched_getaffinity"):
        n = len(os.sched_getaffinity(0))
    else:
        n = os.cpu_count() or 1
    return max(1, min(n, 16))


WORKERS = int(os.environ.get("OGT_SCAN_WORKERS", "0")) or _auto_workers()
INFLIGHT_BYTES = (int(os.environ.get("OGT_SCAN_INFLIGHT_MB", "0")) or 256) << 20
# below this many jobs the pool's dispatch overhead exceeds the decode
MIN_POOL_JOBS = 4

_pool: ThreadPoolExecutor | None = None
_pool_lock = lockdep.Lock()
# thread-local, NOT process-global: a bench/test A-B block must not
# degrade concurrent queries on other server threads to serial decode
_serial_local = threading.local()

# process-wide in-flight decoded-bytes gauge: every map_ordered pipeline
# contributes, so the resource governor's unified ledger
# (utils/governor.py) sees the scan stage's live memory footprint
_inflight = InflightGauge()
_note_inflight = _inflight.note


def total_inflight_bytes() -> int:
    """Estimated decoded bytes currently in flight across ALL scans.
    (Named to avoid shadowing by map_ordered's `inflight_bytes` cap
    parameter.)"""
    return _inflight.total()


def enabled() -> bool:
    return WORKERS >= 2 and not getattr(_serial_local, "forced", False)


@contextlib.contextmanager
def forced_serial():
    """Degrade the CALLING THREAD to the serial decode path (config/bench
    A-B knob; also the process-wide behavior when OGT_SCAN_WORKERS=1)."""
    prev = getattr(_serial_local, "forced", False)
    _serial_local.forced = True
    try:
        yield
    finally:
        _serial_local.forced = prev


def pool() -> ThreadPoolExecutor | None:
    global _pool
    if not enabled():
        return None
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                _pool = ThreadPoolExecutor(
                    max_workers=WORKERS, thread_name_prefix="ogt-scan")
    return _pool


def _timed(job):
    """Run one decode job and add its wall time to `scanpool/busy_ns`:
    summed over the workers (the caller, in the serial fallback), over a
    dispatcher's `decode` time it reads as the decode's parallelism."""
    t0 = time.perf_counter_ns()
    try:
        return job()
    finally:
        _STATS.incr("scanpool", "busy_ns", time.perf_counter_ns() - t0)


def map_ordered(jobs, est_bytes=None, inflight_bytes: int | None = None):
    """Run `jobs` (argless callables) on the pool; yield results in
    SUBMISSION order regardless of completion order.  `est_bytes[i]` is
    the estimated decoded size of job i — the sum over submitted-but-
    unconsumed jobs stays under the in-flight budget (a single oversized
    job is still admitted alone, so progress is always possible).

    Serial fallback (pool disabled or few jobs) executes inline with the
    same per-job kill checks — identical results either way, since every
    decode job is pure."""
    jobs = list(jobs)
    p = pool()
    if p is None or len(jobs) < MIN_POOL_JOBS:
        for job in jobs:
            _TRACKER.check()
            yield _timed(job)
        return
    budget = inflight_bytes if inflight_bytes is not None else INFLIGHT_BYTES
    if est_bytes is None:
        # no size info: bound by job count instead (2 jobs per worker)
        est = [1] * len(jobs)
        budget = 2 * WORKERS
    else:
        est = list(est_bytes)
        if len(est) != len(jobs):
            raise ValueError("est_bytes length must match jobs")
    qid = _TRACKER.current_qid()
    handed = tracing.handoff()

    def run(job):
        # worker-side cancellation: a killed query stops paying for
        # decodes whose results would be discarded anyway. Binding the
        # qid also attributes worker-side cache fills (colcache stage
        # time) to the owning query; the binding dies with the next task.
        # A traced query's worker spans parent under the dispatching span.
        _TRACKER.bind(qid)
        _TRACKER.raise_if_killed(qid)
        with tracing.adopt(handed):
            return _timed(job)

    pending: deque = deque()
    inflight = 0
    i = 0
    max_pending = 4 * WORKERS
    try:
        while i < len(jobs) or pending:
            while i < len(jobs) and (
                not pending
                or (inflight + est[i] <= budget and len(pending) < max_pending)
            ):
                _TRACKER.check()
                pending.append((p.submit(run, jobs[i]), est[i]))
                inflight += est[i]
                _note_inflight(est[i])
                i += 1
            fut, nb = pending.popleft()
            try:
                # the dispatcher's own stage: what is left of its `decode`
                # once the workers' jobs run beside it
                with tracing.span("pool_wait"):
                    out = fut.result()
            finally:
                inflight -= nb
                _note_inflight(-nb)
            _TRACKER.check()
            yield out
    finally:
        # consumer abandoned mid-scan (exception, KILL, early close):
        # cancel everything not yet running; running jobs finish into
        # discarded futures (their own kill check stops killed queries)
        for fut, nb in pending:
            fut.cancel()
            _note_inflight(-nb)


def prefetch_ordered(thunks, depth: int = 2):
    """Double-buffered pipeline over `thunks` (argless callables): a
    dedicated producer thread computes up to `depth` results ahead while
    the consumer processes the current one.  Results yield in order.

    The producer is NOT a shared-pool worker — thunks may themselves fan
    chunk decodes into the pool (map_ordered) without deadlock."""
    thunks = list(thunks)
    if not enabled() or len(thunks) < 2:
        for t in thunks:
            _TRACKER.check()
            yield t()
        return
    import queue

    q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    qid = _TRACKER.current_qid()
    handed = tracing.handoff()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def produce():
        _TRACKER.bind(qid)  # kill checks inside thunks fire here too
        try:
            # a traced query's spans from this thread (a bulk read's
            # `decode`) parent under the span that started the scan
            with tracing.adopt(handed):
                for t in thunks:
                    if stop.is_set() or _TRACKER.is_killed(qid):
                        break
                    if not put(("ok", t())):
                        return
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            put(("err", e))
            return
        put(("end", None))

    worker = threading.Thread(
        target=produce, name="ogt-scan-prefetch", daemon=True)
    worker.start()
    try:
        while True:
            kind, val = q.get()
            if kind == "end":
                break
            if kind == "err":
                raise val
            _TRACKER.check()
            yield val
    finally:
        stop.set()
        while True:  # drain so a blocked producer wakes and exits
            try:
                q.get_nowait()
            except queue.Empty:
                break
        worker.join(timeout=5.0)


def est_chunk_bytes(chunk, n_fields: int | None) -> int:
    """Decoded-size estimate of one TSF chunk from its metadata alone:
    rows x 9 bytes (8-byte value + mask bit) per column, +1 column for
    the time (and sid, when packed) arrays."""
    cols = (n_fields if n_fields is not None else max(len(chunk.cols), 1)) + 2
    return chunk.rows * 9 * cols


def _register_with_governor() -> None:
    # scan-stage in-flight bytes join the unified memory ledger
    from opengemini_tpu.utils.governor import GOVERNOR

    GOVERNOR.register_component("scanpool", total_inflight_bytes)


_register_with_governor()
