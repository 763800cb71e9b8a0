"""Dynamic batcher: coalesce single-example requests into bucketed batches.

The TPU economics this encodes: an XLA program is compiled per input shape,
so a server must never dispatch a never-seen batch size — it would eat a
multi-second jit pause mid-traffic. Requests therefore coalesce into a
SMALL, FIXED set of bucket sizes (default 1/8/32/128; every bucket is
warmed up front) and short rows are padded to the bucket. Under load the
largest bucket fills and the device sees big, efficient batches; under
trickle traffic the deadline (``max_delay_ms``) bounds added latency: a
lone request flushes at exactly one deadline, and even with the
arrival-quiescence linger extending a flush, the oldest request never
waits longer than TWO deadlines before a (padded) batch is released.

The queue is bounded — ``submit`` on a full queue raises ``QueueFull``,
which the HTTP layer maps to 429 (backpressure, not collapse).
"""
from __future__ import annotations

import threading
import time

import numpy as _np

from ..analysis import concurrency as _conc
from ..base import MXNetError
from ..telemetry import current_span as _current_span

__all__ = ["QueueFull", "BatcherClosed", "WorkItem", "Batch",
           "DynamicBatcher", "ContinuousBatcher", "pad_rows", "pick_bucket"]


class QueueFull(MXNetError):
    """Bounded request queue is full — shed load (HTTP 429)."""


class BatcherClosed(MXNetError):
    """Submit after close(): the session is draining."""


def pick_bucket(n, buckets):
    """Smallest bucket >= n; the largest bucket if n exceeds them all
    (the caller splits oversized requests across batches)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pad_rows(arr, bucket):
    """Pad ``arr`` along axis 0 to ``bucket`` rows with zeros. Zero rows
    are inert at inference: all row-wise heads (softmax, regression) and
    running-stat BatchNorm keep real rows byte-identical."""
    n = arr.shape[0]
    if n == bucket:
        return arr
    pad = _np.zeros((bucket - n,) + arr.shape[1:], dtype=arr.dtype)
    return _np.concatenate([arr, pad], axis=0)


class WorkItem:
    """One client request: a dict of arrays with a leading example dim
    (usually 1). Completed via an event; carries either results or an
    error. ``expire_at`` implements the per-request timeout — expired
    items are answered with TimeoutError and never dispatched. All
    deadline math uses the monotonic clock: a wall-clock (NTP/suspend)
    step must never mass-expire the queue or stall the flush."""

    __slots__ = ("inputs", "n", "event", "outputs", "error",
                 "t_enqueue", "expire_at", "span")

    def __init__(self, inputs, n, expire_at=None):
        self.inputs = inputs
        self.n = n
        self.event = threading.Event()
        self.outputs = None
        self.error = None
        self.t_enqueue = time.monotonic()
        self.expire_at = expire_at
        # the submitting thread's ambient telemetry span: the dispatcher
        # parents its batch span here, so one trace id follows a request
        # across the queue hop (client thread -> dispatch thread)
        self.span = _current_span()

    def finish(self, outputs):
        self.outputs = outputs
        self.event.set()

    def fail(self, exc):
        self.error = exc
        self.event.set()

    def wait(self, timeout=None):
        if not self.event.wait(timeout):
            raise TimeoutError("request did not complete in %.3fs" % timeout)
        if self.error is not None:
            raise self.error
        return self.outputs


class Batch:
    """Items glued into one padded device call."""

    def __init__(self, items, bucket, input_names):
        self.items = items
        self.bucket = bucket
        # why the batcher released this batch (full/watermark/deadline/
        # drain) — stamped per batch because N dispatcher threads share
        # one batcher, so a shared "last reason" field would race
        self.flush_reason = None
        self.n_valid = sum(it.n for it in items)
        self.inputs = {}
        for name in input_names:
            # mxtpu: allow-sync(request arrays are host JSON payloads,
            # never device buffers — this is assembly, not a transfer)
            rows = _np.concatenate([_np.asarray(it.inputs[name])
                                    for it in items], axis=0)
            self.inputs[name] = pad_rows(rows, bucket)

    def finish(self, outputs):
        """Slice output rows back to their items and complete them."""
        row = 0
        for it in self.items:
            it.finish([o[row:row + it.n] for o in outputs])
            row += it.n

    def fail(self, exc):
        for it in self.items:
            it.fail(exc)


class DynamicBatcher:
    """Thread-safe request queue with deadline-driven bucketed flushing.

    Producers call ``submit``; one or more consumer threads call
    ``next_batch`` in a loop. A batch is released as soon as (a) enough
    examples are pending to fill the LARGEST bucket, or (b) the oldest
    pending request has waited ``max_delay_ms`` and arrivals have paused
    for ``linger`` (hard cap: 2x ``max_delay_ms``), or (c) ``close()``
    was called and a partial tail needs draining.
    """

    def __init__(self, input_names, buckets=(1, 8, 32, 128),
                 max_delay_ms=5.0, max_queue=256, metrics=None,
                 linger_ms=None, example_shapes=None):
        if not buckets:
            raise MXNetError("DynamicBatcher needs at least one bucket")
        self.input_names = list(input_names)
        # per-example trailing shapes for submit-time validation: a
        # mis-shaped request must be rejected AT THE DOOR — once accepted
        # it would poison the np.concatenate of a whole batch
        self.example_shapes = {k: tuple(v)[1:] for k, v in
                               (example_shapes or {}).items()}
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.max_delay = max_delay_ms / 1000.0
        # Arrival-quiescence linger: when the deadline fires while requests
        # are STILL STREAMING IN (the resubmission wave right after a batch
        # completes), hold the flush until arrivals pause for ``linger`` —
        # hard-capped at 2x max_delay so the latency contract stays bounded.
        # A lone request sees no arrivals after it, so it still flushes at
        # exactly max_delay.
        self.linger = (linger_ms / 1000.0) if linger_ms is not None \
            else self.max_delay / 4.0
        self.max_queue = max_queue
        self._items = []
        self._pending_rows = 0
        self._last_enqueue = 0.0
        # tagged with the CONCRETE class (DynamicBatcher /
        # ContinuousBatcher) — both are declared at the batcher level;
        # the condition shares the lock, so it witnesses under one key
        self._lock = _conc.lock(type(self).__name__, "_lock")
        self._not_empty = _conc.condition(self._lock)
        self._closed = False
        self._metrics = metrics
        self._last_flush_reason = None

    # ---------------------------------------------------------- producer
    def submit(self, inputs, timeout=None):
        """Enqueue one request (dict name -> array with leading example
        dim). Returns a WorkItem future. Raises QueueFull / BatcherClosed."""
        arrs = {}
        n = None
        for name in self.input_names:
            if name not in inputs:
                raise MXNetError("missing serving input '%s'" % name)
            # mxtpu: allow-sync(door validation of host request arrays)
            a = _np.asarray(inputs[name])
            if a.ndim == 0:
                raise MXNetError(
                    "serving input '%s' must have a leading example dim"
                    % name)
            want = self.example_shapes.get(name)
            if want is not None and tuple(a.shape[1:]) != want:
                raise MXNetError(
                    "serving input '%s' shape %s does not match per-example"
                    " shape %s" % (name, a.shape[1:], want))
            if n is None:
                n = a.shape[0]
            elif a.shape[0] != n:
                raise MXNetError(
                    "inconsistent leading dims across serving inputs")
            arrs[name] = a
        if n > self.buckets[-1]:
            raise MXNetError(
                "request of %d examples exceeds the largest bucket %d"
                % (n, self.buckets[-1]))
        expire_at = time.monotonic() + timeout if timeout is not None else None
        item = WorkItem(arrs, n, expire_at=expire_at)
        with self._lock:
            if self._closed:
                raise BatcherClosed("serving session is draining")
            if len(self._items) >= self.max_queue:
                if self._metrics:
                    self._metrics.counter("requests_rejected").inc()
                raise QueueFull(
                    "serving queue full (%d requests)" % self.max_queue)
            self._items.append(item)
            self._pending_rows += n
            self._last_enqueue = time.monotonic()
            self._not_empty.notify()
        return item

    @property
    def depth(self):
        return len(self._items)

    @property
    def pending_rows(self):
        """Examples waiting in the queue (admission-control signal)."""
        return self._pending_rows

    # ---------------------------------------------------------- consumer
    def _reap_expired(self, now):
        """Fail timed-out items in place (caller holds the lock)."""
        live = []
        for it in self._items:
            if it.expire_at is not None and now > it.expire_at:
                self._pending_rows -= it.n
                if self._metrics:
                    self._metrics.counter("requests_timed_out").inc()
                it.fail(TimeoutError("request timed out in queue"))
            else:
                live.append(it)
        self._items = live

    def _take_locked(self):
        """Pop a prefix of items filling (at most) the largest bucket."""
        target = self.buckets[-1]
        take, rows = [], 0
        for it in self._items:
            if rows + it.n > target:
                break
            take.append(it)
            rows += it.n
        self._items = self._items[len(take):]
        self._pending_rows -= rows
        return take, rows

    def next_batch(self, timeout=None):
        """Block until a batch is ready; None on drain-complete or idle
        ``timeout`` (seconds) expiry. A batch whose arrays fail to
        assemble fails ITS items and the wait resumes — a poisoned
        request must never kill the consumer thread."""
        deadline = time.monotonic() + timeout if timeout is not None else None
        return self._next(deadline)

    def _next(self, deadline, ready_rows=None, use_linger=True):
        """Shared wait/assemble/fail loop behind ``next_batch`` and the
        continuous batcher's ``next_fill`` (one copy of the
        poisoned-batch handling, two flush policies)."""
        while True:
            got = self._form_batch(deadline, ready_rows=ready_rows,
                                   use_linger=use_linger)
            if got is None:
                return None
            take, rows, reason = got
            try:
                batch = self._assemble(take, rows)
                batch.flush_reason = reason
                return batch
            except Exception as exc:
                for it in take:
                    it.fail(MXNetError("batch assembly failed: %r" % exc))
                if self._metrics:
                    self._metrics.counter("requests_failed").inc(len(take))

    def _form_batch(self, deadline, ready_rows=None, use_linger=True):
        """Wait for and dequeue a batch-worth of items; None on idle
        timeout or drain-complete, else ``(items, rows, reason)`` where
        ``reason`` says why the flush fired (full/watermark/deadline/
        drain). ``ready_rows`` lowers the immediate-flush threshold
        below the largest bucket (the continuous batcher's refill
        watermark); ``use_linger=False`` flushes at exactly
        ``max_delay`` (a hungry device slot must not linger for an
        arrival wave)."""
        take, rows, reason = None, 0, None
        target = self.buckets[-1]
        with self._lock:
            while take is None:
                now = time.monotonic()
                self._reap_expired(now)
                if self._items:
                    age = now - self._items[0].t_enqueue
                    since_arrival = now - self._last_enqueue
                    full = self._pending_rows >= target
                    ready = ready_rows is not None \
                        and self._pending_rows >= ready_rows
                    due = age >= self.max_delay and \
                        (not use_linger or since_arrival >= self.linger or
                         age >= 2 * self.max_delay)
                    if full or ready or due or self._closed:
                        take, rows = self._take_locked()
                        if not take:
                            take = None
                            continue
                        reason = ("full" if full else
                                  "watermark" if ready else
                                  "deadline" if due else "drain")
                        self._last_flush_reason = reason
                        continue
                    if age < self.max_delay:
                        wait = self.max_delay - age
                    else:  # lingering for the arrival wave to quiesce
                        wait = min(self.linger - since_arrival,
                                   2 * self.max_delay - age)
                    wait = max(wait, 0.0005)
                elif self._closed:
                    return None
                else:
                    wait = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None
                    wait = remaining if wait is None else min(wait, remaining)
                self._not_empty.wait(wait)
        return take, rows, reason

    def _assemble(self, take, rows):
        # the numpy concatenate/pad is the expensive part; the items are
        # already dequeued, so build the Batch WITHOUT stalling producers
        bucket = pick_bucket(rows, self.buckets)
        if self._metrics:
            self._metrics.counter("batches_formed").inc()
            self._metrics.counter("batch_rows_valid").inc(rows)
            self._metrics.counter("batch_rows_padded").inc(bucket - rows)
        return Batch(take, bucket, self.input_names)

    def abort(self, exc):
        """Fail every queued request with ``exc`` and stop accepting —
        the non-drain shutdown path. Consumers wake and exit."""
        with self._lock:
            self._closed = True
            for it in self._items:
                it.fail(exc)
            self._items = []
            self._pending_rows = 0
            self._not_empty.notify_all()

    def close(self):
        """Stop accepting; wake consumers so they drain the tail."""
        with self._lock:
            self._closed = True
            self._not_empty.notify_all()


class ContinuousBatcher(DynamicBatcher):
    """DynamicBatcher for slot-driven (continuous-batching) consumers.

    The burst batcher optimizes FILL: it holds the queue until the
    largest bucket fills or a deadline expires, because its consumer
    blocks on the device between dispatches — each flush is expensive.
    The continuous dispatcher keeps K device batches in flight, so the
    moment a slot frees, dispatching *something* beats waiting: device
    idle time is pure loss, padding is merely cheap. ``next_fill``
    therefore releases a batch as soon as pending rows reach the
    **refill watermark** (no deadline wait), and when the deadline does
    fire it skips the arrival-quiescence linger — a hungry slot never
    waits for a wave to quiesce. With ``hungry=False`` (every slot
    occupied) it behaves exactly like the burst batcher: there is no
    point forming work the device cannot take.

    The watermark is the fill-vs-latency knob: raise it toward the
    largest bucket when per-row cost dominates (big models — prefer
    full batches), drop it toward 1 when dispatch overhead dominates
    (the device should never starve). It is a declared tunable
    (``serving.refill_watermark``, docs/tune.md): the environment can
    pin it, ``serving.admission.derive_knobs`` picks it from the
    measured per-bucket cost registry rows otherwise.
    """

    def __init__(self, input_names, refill_watermark=None, **kwargs):
        super().__init__(input_names, **kwargs)
        if refill_watermark is None:
            # a quarter of the largest bucket: enough rows that the
            # dispatch isn't overhead-bound, small enough that a freed
            # slot refills within one arrival burst
            refill_watermark = self.buckets[-1] // 4
        self.refill_watermark = max(1, min(int(refill_watermark),
                                           self.buckets[-1]))

    def next_fill(self, timeout=None, hungry=True):
        """Like ``next_batch`` but for a consumer with a free device
        slot: flush at the refill watermark, never linger. ``timeout=0``
        polls without blocking (the dispatcher has in-flight work to
        retire and must not park). Returns None on timeout or
        drain-complete; ``last_flush_reason`` says why the batch was
        released (full/watermark/deadline/drain)."""
        deadline = time.monotonic() + timeout if timeout is not None else None
        return self._next(deadline,
                          ready_rows=self.refill_watermark if hungry
                          else None,
                          use_linger=not hungry)

    @property
    def last_flush_reason(self):
        """Most recent flush reason — single-consumer convenience (tests,
        REPL). Multi-worker consumers must read ``batch.flush_reason``,
        which is stamped per batch and cannot race."""
        return self._last_flush_reason
