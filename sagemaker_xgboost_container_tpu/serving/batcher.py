"""Request coalescing for TPU serving.

The reference scales serving by forking a gunicorn worker per CPU, each with
its own model copy (serve.py:38-39, :92-107). On TPU one process owns the
chip, so throughput under concurrency comes from *batching*: concurrent
/invocations requests are coalesced into one padded forest-kernel dispatch
and the per-row results are scattered back to their callers.

A single daemon worker drains the queue; callers block on an Event with a
timeout. Batching is shape-safe: requests joining a batch must share the
feature width (they do — one model per endpoint); row counts concatenate and
the predict path's power-of-two bucketing keeps the jit cache small.
"""

import logging
import queue
import threading
import time

import numpy as np

from ..models.forest import _host_predict_rows
from ..telemetry import POW2_BUCKETS, REGISTRY, get_request_id, tracing
from ..utils.faults import fault_point
from . import lifecycle

logger = logging.getLogger(__name__)

# linger is bounded by max_wait_ms (default 2ms) — sub-ms buckets
_LINGER_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05)


class _Pending:
    __slots__ = ("features", "event", "result", "error", "ctx", "dispatched")

    def __init__(self, features):
        self.features = features
        self.event = threading.Event()
        self.result = None
        self.error = None
        # caller's trace context (SM_TRACE): carried across the queue so the
        # worker's dispatch span joins the request's trace tree
        self.ctx = tracing.current_context()
        # set by the worker when the batch holding this request starts its
        # dispatch: a deadline expiring after that is a `predict`-stage
        # expiry, before it a `queue`-stage one
        self.dispatched = False


class JobQueueFull(Exception):
    """Raised when the bounded job queue rejects a request (the MMS analog:
    SAGEMAKER_MODEL_JOB_QUEUE_SIZE, reference serving_mms.py:100 — MMS
    returns 503 when a model's job queue is exhausted)."""


class PredictBatcher:
    """Coalesce predict calls into batched kernel dispatches.

    ``predict_fn(features) -> np.ndarray`` must be thread-safe (ours is: a
    pure jitted kernel). ``max_batch_rows`` bounds padding waste;
    ``max_wait_ms`` bounds added latency under low load; ``max_queue``
    (None = unbounded) bounds in-flight requests, rejecting beyond it.

    Ordering note: requests are NOT strictly FIFO under light concurrency.
    The idle inline fast path runs small requests on the caller's thread
    under a non-blocking exec lock, so a new request can execute ahead of
    one the worker has already dequeued (held while parked on that lock).
    The reordering is bounded to a single overtaken request and is harmless
    for stateless prediction — but any future stateful use (sequence-
    sensitive accounting, streaming sessions) must not assume arrival-order
    execution.
    """

    def __init__(
        self,
        predict_fn,
        max_batch_rows=16384,
        max_wait_ms=2.0,
        max_queue=None,
        name="default",
        registry=None,
    ):
        self.predict_fn = predict_fn
        self.max_batch_rows = max_batch_rows
        self.max_wait_ms = max_wait_ms
        # metric identity is (name, labels). Live cardinality stays bounded:
        # MME unload/evict retires a model's series (mme._drop_batcher_metrics),
        # so churn through many model names cannot grow the registry forever.
        reg = registry or REGISTRY
        labels = {"batcher": name}
        self._m_requests = reg.counter(
            "batcher_requests_total", "Predict calls accepted", labels
        )
        self._m_inline = reg.counter(
            "batcher_inline_total", "Idle fast-path runs on the caller thread", labels
        )
        self._m_rejected = reg.counter(
            "batcher_rejected_total", "JobQueueFull rejections", labels
        )
        self._m_timeouts = reg.counter(
            "batcher_queue_timeout_total",
            "Callers that gave up waiting (zombie pendings: the worker may "
            "still dispatch their rows)",
            labels,
        )
        self._m_dispatch = reg.counter(
            "batcher_dispatch_total", "Kernel dispatches (batches executed)", labels
        )
        self._m_coalesced = reg.counter(
            "batcher_coalesced_requests_total",
            "Requests that shared a dispatch with at least one other "
            "(coalescing ratio = this / batcher_requests_total)",
            labels,
        )
        self._m_queue_depth = reg.gauge(
            "batcher_queue_depth", "Requests waiting in the coalescing queue", labels
        )
        self._m_batch_rows = reg.histogram(
            "batcher_batch_rows", "Rows per dispatched batch", labels, POW2_BUCKETS
        )
        self._m_batch_requests = reg.histogram(
            "batcher_batch_requests",
            "Requests coalesced per dispatched batch",
            labels,
            POW2_BUCKETS,
        )
        self._m_linger = reg.histogram(
            "batcher_linger_seconds",
            "Time spent collecting a batch before dispatch",
            labels,
            _LINGER_BUCKETS,
        )
        # test-and-set under a lock: a timeout storm expires many waiters at
        # the same instant, and the log-once guard must hold exactly then
        self._timeout_log_lock = threading.Lock()
        self._timeout_logged = False
        self._rejection_logged = False
        # bounded queue -> the limit is atomic (put_nowait raises Full);
        # a qsize() check-then-put would race under concurrent WSGI threads.
        # Clamped to >=1 when bounded: Queue(maxsize=0) means UNLIMITED in
        # Python, which would invert a SAGEMAKER_MODEL_JOB_QUEUE_SIZE=0 knob
        # into the unbounded queueing it exists to prevent.
        self.max_queue = None if max_queue is None else max(1, max_queue)
        self._queue = queue.Queue(maxsize=self.max_queue or 0)
        self._carry = None  # width-mismatched request deferred to next batch
        self._exec_lock = threading.Lock()  # held around every predict_fn run
        # current-dispatch bookkeeping for the predict watchdog
        # (lifecycle.PredictWatchdog): started timestamp + (requests, rows)
        # of the batch inside predict_fn right now, None/zeros when idle
        self._dispatch_lock = threading.Lock()
        self._dispatch_started = None
        self._dispatch_meta = (0, 0)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def dispatch_age_s(self):
        """Seconds the in-flight predict_fn run has been executing, or None
        when no dispatch is in flight (the predict-watchdog probe)."""
        with self._dispatch_lock:
            started = self._dispatch_started
        return None if started is None else time.monotonic() - started

    def dispatch_info(self):
        """-> (requests, rows) of the in-flight dispatch (0, 0 when idle)."""
        with self._dispatch_lock:
            return self._dispatch_meta

    def _dispatch_begin(self, requests, rows):
        with self._dispatch_lock:
            self._dispatch_started = time.monotonic()
            self._dispatch_meta = (requests, rows)

    def _dispatch_end(self):
        with self._dispatch_lock:
            self._dispatch_started = None
            self._dispatch_meta = (0, 0)

    def predict(self, features, timeout=60.0, deadline=None):
        feats = np.asarray(features, np.float32)
        # Idle fast path: nothing queued and the worker is not mid-batch ->
        # run predict_fn on the caller's thread, skipping the cross-thread
        # queue/Event handoff (~0.7 ms of condvar ping-pong per request on
        # a 1-core host). The exec lock keeps predict_fn single-flight:
        # under any concurrency the non-blocking acquire fails and requests
        # take the coalescing queue exactly as before. Restricted to
        # host-path-sized payloads: the numpy traversal cannot hang, so
        # forgoing the queue path's wait-timeout is safe — device-sized
        # payloads keep the worker handoff and its TimeoutError bound (a
        # stalled device dispatch).
        if (
            0 < feats.shape[0] <= _host_predict_rows()
            and self._queue.empty()
            and self._exec_lock.acquire(blocking=False)
        ):
            try:
                if self._queue.empty() and self._carry is None:
                    if deadline is not None:
                        deadline.check("predict")
                    self._m_requests.inc()
                    self._m_inline.inc()
                    with tracing.trace_span(
                        "batcher.inline",
                        attributes={"rows": int(feats.shape[0])},
                    ):
                        self._dispatch_begin(1, int(feats.shape[0]))
                        try:
                            return np.asarray(self.predict_fn(feats))
                        finally:
                            self._dispatch_end()
            finally:
                self._exec_lock.release()
        if deadline is not None:
            # a request whose budget is already gone must not take a queue
            # slot another request could use
            deadline.check("queue")
        pending = _Pending(feats)
        # the queue span covers enqueue -> (result | rejection | timeout) on
        # the caller's thread; the worker's dispatch span is its cross-thread
        # sibling in the same trace (joined via pending.ctx)
        qspan = tracing.start_span(
            "batcher.queue", attributes={"rows": int(feats.shape[0])}
        )
        try:
            try:
                self._queue.put_nowait(pending)
            except queue.Full:
                self._m_rejected.inc()
                with self._timeout_log_lock:
                    should_log, self._rejection_logged = not self._rejection_logged, True
                if should_log:
                    logger.warning(
                        "rejecting prediction (request %s): job queue full (%s "
                        "pending). Further rejections are counted in "
                        "batcher_rejected_total without logging.",
                        get_request_id() or "untracked",
                        self.max_queue,
                    )
                raise JobQueueFull(
                    "job queue full ({} pending)".format(self.max_queue)
                )
            self._m_requests.inc()
            self._m_queue_depth.set(self._queue.qsize())
            # SM_REQUEST_DEADLINE_S bounds queue wait PLUS dispatch: the
            # caller never blocks past the smaller of its legacy timeout and
            # the remaining request budget
            wait_s = timeout
            if deadline is not None:
                wait_s = min(timeout, deadline.remaining())
            if not pending.event.wait(wait_s):
                if deadline is not None and deadline.expired():
                    # same zombie accounting as the legacy timeout (the
                    # worker may still dispatch the abandoned rows), but
                    # attributed to the stage the budget died in
                    self._m_timeouts.inc()
                    lifecycle.expire(
                        "predict" if pending.dispatched else "queue",
                        deadline.budget_s,
                    )
                # zombie pending: this caller gives up, but the worker still
                # holds the _Pending and may dispatch its rows later — wasted
                # compute that a timeout storm multiplies. Count every one;
                # log the first at WARNING so the storm is visible without
                # flooding the log.
                self._m_timeouts.inc()
                with self._timeout_log_lock:
                    should_log, self._timeout_logged = not self._timeout_logged, True
                if should_log:
                    logger.warning(
                        "prediction (request %s) timed out after %.1fs in the "
                        "batch queue; the batch worker may still dispatch the "
                        "abandoned rows. Further timeouts are counted in "
                        "batcher_queue_timeout_total without logging.",
                        get_request_id() or "untracked",
                        timeout,
                    )
                raise TimeoutError("prediction timed out in the batch queue")
            if pending.error is not None:
                raise pending.error
            return pending.result
        finally:
            tracing.finish_span(qspan)

    # ------------------------------------------------------------------ int
    def _drain_batch(self, first, wait):
        """Collect a batch starting from ``first``.

        ``wait``: whether to linger max_wait_ms for stragglers. A lone
        request on an idle endpoint must NOT pay the linger (it would add
        max_wait_ms to every p50); under concurrency the queue accumulates
        while predict_fn runs, so coalescing happens even with wait=False.
        The worker passes wait=True only after a batch that actually
        coalesced — evidence of concurrent load.
        """
        batch = [first]
        rows = first.features.shape[0]
        # ONE deadline for the whole batch: re-arming the timeout per
        # straggler would let a trickle of arrivals defer dispatch unboundedly
        deadline = time.monotonic() + (self.max_wait_ms / 1000.0 if wait else 0.0)
        while rows < self.max_batch_rows:
            try:
                remaining = deadline - time.monotonic()
                if remaining > 0:
                    nxt = self._queue.get(timeout=remaining)
                else:
                    nxt = self._queue.get_nowait()
            except queue.Empty:
                break
            if nxt.features.shape[1] != first.features.shape[1]:
                # different width (e.g. mid-flight model swap): defer to its
                # own batch (re-putting could block on a bounded queue)
                # graftlint: disable=shared-state-unlocked — the only caller
                # (_worker) holds _exec_lock around every _drain_batch call
                self._carry = nxt
                break
            batch.append(nxt)
            rows += nxt.features.shape[0]
        return batch

    def _worker(self):
        loaded = False  # previous batch coalesced -> linger for stragglers
        while True:
            # swap the carry out UNDER the exec lock so the inline fast
            # path's `self._carry is None` check (made while holding it)
            # always observes a consistent value (graftlint
            # shared-state-unlocked). The lock is dropped before the drain
            # below, so an inline run may still execute between this swap
            # and the carried request's dispatch — that ordering was always
            # permitted; the lock only makes the state transition atomic.
            with self._exec_lock:
                first, self._carry = self._carry, None
            if first is None:
                first = self._queue.get()
            # drain INSIDE the exec lock: while an inline run holds it, the
            # worker must not vacuum the queue into a private batch — queued
            # requests have to keep counting against max_queue so the
            # JobQueueFull bound stays meaningful (at most one request — the
            # one just dequeued — sits outside the queue while blocked here)
            with self._exec_lock:
                drain_start = time.monotonic()
                batch = self._drain_batch(first, wait=loaded)
                loaded = len(batch) > 1
                self._m_linger.observe(time.monotonic() - drain_start)
                self._m_queue_depth.set(self._queue.qsize())
                self._m_dispatch.inc()
                self._m_batch_requests.observe(len(batch))
                self._m_batch_rows.observe(
                    sum(p.features.shape[0] for p in batch)
                )
                if len(batch) > 1:
                    self._m_coalesced.inc(len(batch))
                # worker-thread dispatch span, parented to the first traced
                # request in the batch so its trace id survives the thread
                # hop (coalesced peers are named in the args)
                ctx = next((p.ctx for p in batch if p.ctx is not None), None)
                with tracing.trace_span(
                    "batcher.dispatch",
                    parent=ctx,
                    attributes={
                        "requests": len(batch),
                        "rows": sum(p.features.shape[0] for p in batch),
                    },
                ):
                    self._dispatch_begin(
                        len(batch), sum(p.features.shape[0] for p in batch)
                    )
                    for pending in batch:
                        pending.dispatched = True
                    try:
                        # chaos hook: a sleep here wedges the dispatch worker
                        # (a stalled device dispatch), backing the queue up into
                        # JobQueueFull — the breaker drill's saturation source
                        fault_point("batcher.dispatch", requests=len(batch))
                        stacked = (
                            batch[0].features
                            if len(batch) == 1
                            else np.concatenate(
                                [p.features for p in batch], axis=0
                            )
                        )
                        out = np.asarray(self.predict_fn(stacked))
                        offset = 0
                        for pending in batch:
                            k = pending.features.shape[0]
                            pending.result = out[offset : offset + k]
                            offset += k
                            pending.event.set()
                    except Exception as e:  # propagate to every caller in batch
                        for pending in batch:
                            pending.error = e
                            pending.event.set()
                    finally:
                        self._dispatch_end()
