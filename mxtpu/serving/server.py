"""Serving front-ends: in-process ``ServingSession`` + stdlib HTTP server.

``ServingSession`` is the composition root: a batcher feeding an
``ExecutorPool`` through one dispatcher thread per replica, with a
``MetricsRegistry`` observing every stage. Two dispatch modes:

* ``continuous`` (default) — the dispatcher keeps up to K device
  batches in flight per replica and REFILLS a freed slot from the
  queue at the refill watermark (``ContinuousBatcher``): the dispatch
  of batch N+1 overlaps the device execution of batch N and the
  device→host materialization of batch N-1, so the device never idles
  between bursts. Signal-driven admission control
  (``serving.admission``) sheds with 429 before the queue-wait blows
  the latency budget or the device wedges. Versioned hot-swap
  (``swap_model``) pre-warms the incoming model in the process-wide
  warm cache, then flips the pool pointer atomically — in-flight
  batches on the old version drain to completion, zero requests fail.
* ``burst`` — the PR-1 loop (dispatch, block, respond, repeat), kept as
  the benchmark baseline and for single-tenant batch jobs where
  device idle between bursts is irrelevant.

The HTTP layer is a thin JSON veneer (stdlib ``ThreadingHTTPServer`` —
zero new dependencies) over the same session:

    POST /v1/predict     {"inputs": {"data": [[...]]}}  -> {"outputs": [...]}
    POST /v1/generate    {"prompt": [ids], ...} -> tokens (decode session;
                         ``?stream=1`` = chunked NDJSON token stream)
    GET  /v1/metrics     serving metrics JSON
    GET  /v1/version     active model version / generation / symbol hash
    POST /v1/admin/swap  {"symbol_file", "params_file", "version_tag"}
    GET  /healthz        liveness (200 while accepting)

Overload taxonomy: **429** = shed (admission policy or full queue —
back off and retry), **504** = the request out-waited its own deadline
in the queue, **503** = the session is draining (shutdown) — the only
window a healthy deploy ever serves it; a hot-swap flip is atomic and
serves no errors at all. Shutdown drains: the queue closes, in-flight
batches finish and answer, THEN workers exit.
"""
from __future__ import annotations

import json
import logging
import math
import os
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as _np

from .. import diagnostics as _diag
from .. import telemetry as _tel
from ..analysis import concurrency as _conc
from ..base import MXNetError, NativeError, NumericsError
from ..faults import RetryPolicy, env_attempts
from ..obs import corpus as _obs_corpus
from .admission import (ACCEPTING, AdmissionShed, AdmissionSignals,
                        SignalAdmissionPolicy, STATE_NAMES, derive_knobs,
                        mix_service_model)
from .batcher import (BatcherClosed, ContinuousBatcher, DynamicBatcher,
                      QueueFull)
from .metrics import MetricsRegistry
from .pool import ExecutorPool, warm_cache

__all__ = ["ServingSession", "ServingHTTPServer", "serve", "ReplicaCrash"]

log = logging.getLogger("mxtpu.serving")

DEFAULT_BUCKETS = (1, 8, 32, 128)


class ReplicaCrash(Exception):
    """A replica worker died with the batch's fate attached. A plain
    ``Exception`` (NOT MXNetError): the HTTP layer maps it to 500 and
    the forensics filter captures a postmortem — a dead replica is an
    infrastructure failure, never a client error."""


class _InFlight:
    """One dispatched-but-unretired batch in a worker's slot window."""

    __slots__ = ("batch", "handles", "rep", "t_dispatch")

    def __init__(self, batch, handles, rep, t_dispatch):
        self.batch = batch
        self.handles = handles
        self.rep = rep
        self.t_dispatch = t_dispatch


class ServingSession:
    """Batching inference service over one (hot-swappable) model.

    Parameters
    ----------
    symbol_json : str or Symbol — the inference graph
    params : dict or bytes — trained weights (``arg:``/``aux:`` convention)
    example_shapes : dict name -> per-request shape WITH leading dim 1
    buckets : allowed batch sizes (every one is warmed at startup)
    max_delay_ms : batching deadline — the latency budget donated to
        coalescing before a padded partial batch is flushed
    max_queue : bounded queue depth; beyond it ``predict`` raises QueueFull
    contexts : device contexts (default: one replica per local device)
    warmup : compile all (replica, bucket) programs before accepting
    mode : "continuous" (K-in-flight refilled dispatch, default) or
        "burst" (the PR-1 blocking loop)
    max_in_flight : device batches each dispatcher keeps in flight
        (continuous mode; default ``MXTPU_SERVING_INFLIGHT`` or 2)
    refill_watermark : pending rows that trigger an immediate refill of
        a freed slot; "auto" derives it from the warmup-measured
        per-bucket cost rows (``admission.derive_knobs``)
    admission : an ``AdmissionPolicy``, None (bounded queue only), or
        "auto" — SignalAdmissionPolicy in continuous mode, None in burst
    version_tag : names this weight set in the process-wide warm cache
        (hot-swap versions MUST use distinct tags)
    mem_budget_bytes : device-memory budget for the admission headroom
        signal (default ``MXTPU_SERVING_MEM_BUDGET``; unset = signal off)
    queue_wait_budget_ms : admission latency budget (default: half the
        ``default_timeout`` if set, else 1000ms)
    """

    def __init__(self, symbol_json, params, example_shapes,
                 buckets=DEFAULT_BUCKETS, max_delay_ms=None, max_queue=None,
                 contexts=None, cache_size=8, warmup=True,
                 default_timeout=None, mode="continuous", max_in_flight=None,
                 refill_watermark="auto", admission="auto",
                 version_tag="v0", mem_budget_bytes=None,
                 queue_wait_budget_ms=None):
        from .. import tune as _tune
        if mode not in ("continuous", "burst"):
            raise MXNetError("serving mode must be 'continuous' or "
                             "'burst', got %r" % (mode,))
        self.mode = mode
        self.metrics = MetricsRegistry()
        # materialize the engine singleton so its telemetry series exist
        # before the first /metrics scrape (they read zero until traffic)
        from .. import engine as _engine
        _engine.get()
        # hang watchdog + SIGUSR2 postmortem handler for the process
        _diag.on_session_start()
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.default_timeout = default_timeout
        # every constant resolves through the knob registry
        # (docs/tune.md): default < environment < the explicit
        # constructor arguments above
        self.max_in_flight = _tune.resolve_int(
            "serving.max_in_flight", explicit=max_in_flight, floor=1)
        max_queue = _tune.resolve_int("serving.max_queue",
                                      explicit=max_queue)
        max_delay_ms = _tune.resolve("serving.max_delay_ms",
                                     explicit=max_delay_ms)
        self.version_tag = version_tag
        self._generation = 0
        self._swap_seq = 0  # monotonic default-tag allocator (swap_model)
        self._mem_budget = _tune.resolve(
            "serving.mem_budget_bytes", explicit=mem_budget_bytes) or None
        # the per-replica executor LRU must hold every bucket or warmup
        # thrashes and evicted buckets re-compile mid-traffic
        self._cache_size = max(cache_size, len(self.buckets))
        self._pool = ExecutorPool(symbol_json, params, example_shapes,
                                  contexts=contexts,
                                  cache_size=self._cache_size,
                                  metrics=self.metrics,
                                  version_tag=version_tag)
        # resolved device list: a hot-swapped pool must recreate replicas
        # on exactly these devices (worker threads are pinned by index)
        self._contexts = [r.ctx for r in self._pool.replicas]
        # executor-layer seam: count every traced-program construction by
        # THIS session's executors (each costs an XLA compile on first
        # dispatch); installed BEFORE warmup so the deploy compiles are
        # attributed, after which the counter must stay flat under
        # traffic at warmed buckets. The listener holds the pools weakly
        # and closes over the counter — never the session — so an
        # un-close()d session is not pinned by the global seam, and
        # builds from unrelated executors (another session, a training
        # Module) are not attributed here.
        import weakref
        from .. import executor as _executor
        _builds = self.metrics.counter("program_builds")
        self._pool_ref = [weakref.ref(self._pool)]

        def _on_build(kind, ex, _c=_builds, _refs=self._pool_ref):
            for r in _refs:
                p = r()
                if p is not None and p.owns_executor(ex):
                    _c.inc()
                    return

        self._build_listener = _executor.add_build_listener(_on_build)
        if warmup:
            with self.metrics.span("warmup"):
                self._pool.warmup(self.buckets)
        # knob derivation from the measured cost rows (ISSUE: knobs come
        # from the registry, not hand-picking): refill watermark + the
        # admission policy's service-time prior both read bucket_costs
        knobs = derive_knobs(self._pool.bucket_costs(), self.buckets)
        if refill_watermark == "auto":
            # an environment value wins; otherwise fall through to the
            # cost-registry derivation (and its structural default)
            refill_watermark = _tune.resolve("serving.refill_watermark")
            if refill_watermark is None:
                refill_watermark = knobs["refill_watermark"]
        if mode == "continuous":
            self.batcher = ContinuousBatcher(
                list(example_shapes), buckets=self.buckets,
                max_delay_ms=max_delay_ms, max_queue=max_queue,
                metrics=self.metrics, example_shapes=example_shapes,
                refill_watermark=refill_watermark)
        else:
            self.batcher = DynamicBatcher(
                list(example_shapes), buckets=self.buckets,
                max_delay_ms=max_delay_ms, max_queue=max_queue,
                metrics=self.metrics, example_shapes=example_shapes)
        queue_wait_budget_ms = _tune.resolve(
            "serving.queue_wait_budget_ms", explicit=queue_wait_budget_ms)
        if queue_wait_budget_ms is None:
            queue_wait_budget_ms = 500.0 * default_timeout \
                if default_timeout else 1000.0
        if admission == "auto":
            admission = SignalAdmissionPolicy(
                queue_wait_budget_ms=queue_wait_budget_ms,
                watchdog_shed_s=_tune.resolve("serving.watchdog_shed_s"),
                min_mem_headroom=_tune.resolve("serving.min_mem_headroom"),
                queue_frac_shed=_tune.resolve("serving.queue_frac_shed"),
                degrade_frac=_tune.resolve("serving.degrade_frac")) \
                if mode == "continuous" else None
        if admission is not None and not hasattr(admission, "decide"):
            raise MXNetError("admission must be an AdmissionPolicy "
                             "(got %r)" % (admission,))
        self._admission = admission
        self._admission_state = ACCEPTING
        self._sheds_by_reason = {}
        self._last_shed_reason = None
        self._swap_lock = _conc.lock("ServingSession", "_swap_lock")
        self._inflight_n = [0] * len(self._pool.replicas)
        self._last_retire_t = [None] * len(self._pool.replicas)
        # per-WORKER per-bucket (count, sum_ms) service aggregates:
        # single writer each (its dispatcher thread), so the admission
        # reader merges them lock-free — the hot path must not scan the
        # metrics registry per request
        self._bucket_service = [{} for _ in self._pool.replicas]
        # graceful degradation: a worker that dies on an unexpected
        # exception quarantines its replica (capacity shrinks HONESTLY:
        # /healthz + admission see it) and is respawned off the hot path
        self._quarantined = [False] * len(self._pool.replicas)
        self.metrics.gauge("queue_depth", fn=lambda: self.batcher.depth)
        self.metrics.gauge("replicas", fn=lambda: len(self._pool))
        self.metrics.gauge("replicas_healthy",
                           fn=lambda: self.healthy_replicas())
        self.metrics.gauge("inflight_depth",
                           fn=lambda: sum(self._inflight_n))
        self.metrics.gauge("admission_state",
                           fn=lambda: self._admission_state)
        self._closed = False
        self._workers = [self._spawn_worker(i)
                         for i in range(len(self._pool.replicas))]

    # ------------------------------------------------------------- pool
    @property
    def pool(self):
        """The ACTIVE pool (hot-swap flips this pointer atomically)."""
        return self._pool

    # ---------------------------------------------------------- hot-swap
    def swap_model(self, symbol_json, params, version_tag=None,
                   warmup=True):
        """Zero-downtime model rollout: build + pre-warm the incoming
        version while the old one serves, then flip atomically.

        The new pool compiles every (replica, bucket) executable through
        the process-wide warm cache BEFORE the flip (a rollback to a
        tag the cache still holds adopts instantly — zero compiles).
        The flip itself is one pointer swap under ``_swap_lock``:
        batches dispatched before it complete on the old version,
        batches formed after it run the new one; no request ever fails
        and no 503 is served. The old pool drains naturally as its
        in-flight batches retire. Distinct weights MUST get distinct
        ``version_tag``s (default: ``v<generation+1>``)."""
        if self._closed:
            raise BatcherClosed("serving session is closed")
        if version_tag is None:
            # allocated under the swap lock: two concurrent default-tag
            # swaps must not register different weights under one tag
            # (the warm cache's distinct-weights/distinct-tags contract)
            with self._swap_lock:
                self._swap_seq += 1
                version_tag = "v%d" % self._swap_seq
        new_pool = ExecutorPool(symbol_json, params, self.example_shapes,
                                contexts=self._contexts,
                                cache_size=self._cache_size,
                                metrics=self.metrics,
                                version_tag=version_tag)
        if len(new_pool) != len(self._pool):
            raise MXNetError(
                "swap_model: replica count changed (%d -> %d); workers "
                "are pinned per replica" % (len(self._pool), len(new_pool)))
        if warmup:
            with self.metrics.span("swap_warmup"):
                new_pool.warmup(self.buckets)
        import weakref
        with self._swap_lock:
            old_pool = self._pool
            self._pool = new_pool
            self._generation += 1
            self.version_tag = version_tag
            # the new model has a new service profile: the mix-aware
            # admission estimate must re-learn from ITS batches, not
            # price them with the old model's lifetime history (the
            # cost-row prior of the new pool covers the relearn window;
            # old-pool in-flight tails retiring after the flip land in
            # the fresh dicts — a few rows of contamination, gone
            # within the first decay window)
            self._bucket_service = [{} for _ in new_pool.replicas]
            # the build listener must keep attributing the OLD pool's
            # tail (in-flight retires) AND the new pool's programs
            self._pool_ref.insert(0, weakref.ref(new_pool))
            del self._pool_ref[2:]
        self.metrics.counter("model_swaps").inc()
        del old_pool  # drains via worker in-flight refs, then GC
        return self.version_info()

    def version_info(self):
        return {"version": self.version_tag,
                "generation": self._generation,
                "symbol_hash": self._pool.symbol_hash,
                "mode": self.mode,
                "swaps": int(self.metrics.counter("model_swaps").value)}

    @property
    def example_shapes(self):
        return self._pool.example_shapes

    # --------------------------------------------------------- admission
    #: per-bucket observations before the aggregate halves: bounds how
    #: long a stale service profile can dominate the admission estimate
    #: (a traffic-mix or model change re-converges within ~one window)
    _SERVICE_WINDOW = 2048

    def _record_service(self, idx, bucket, service_ms):
        """Record one retired batch's marginal service time: into worker
        ``idx``'s per-bucket aggregate (the admission estimate's
        lock-free read) and the ``batch_service_ms`` telemetry series —
        unlabeled for the overall distribution, ``bucket=``-labeled for
        the dashboard view of the same per-bucket facts."""
        d = self._bucket_service[idx]
        n, s = d.get(bucket, (0, 0.0))
        if n >= self._SERVICE_WINDOW:
            # exponential forgetting: halve the weight of history so
            # the mean tracks drift instead of averaging over the
            # process lifetime
            n, s = n // 2, s / 2.0
        d[bucket] = (n + 1, s + service_ms)   # atomic slot replace
        self.metrics.histogram("batch_service_ms").observe(service_ms)
        self.metrics.histogram(
            "batch_service_ms",
            labels={"bucket": str(bucket)}).observe(service_ms)
        if _obs_corpus.enabled():
            # the measurement-corpus ledger: the same marginal service
            # fact the admission model learns from, persisted
            _obs_corpus.record_service("serving", service_ms,
                                       bucket=bucket)

    def _service_model(self):
        """The queue-drain model admission budgets with: mix-weighted
        per-batch service time AND rows-per-batch learned from the live
        per-bucket service aggregates (single-writer per worker, merged
        here without locks — this runs on every request's admit path),
        falling back to the warmup cost-registry rows before traffic
        (:func:`~mxtpu.serving.admission.mix_service_model`). Service
        time is the MARGINAL retire-to-retire cost, not
        ``batch_exec_ms`` (dispatch→retire): with K batches in flight
        the latter runs ~K× the true per-batch cost — budgeting with it
        would shed at a fraction of the configured latency budget."""
        merged = {}
        for d in self._bucket_service:
            for b, (n, s) in list(d.items()):
                pn, ps = merged.get(b, (0, 0.0))
                merged[b] = (pn + n, ps + s)
        live = {b: (n, s / n) for b, (n, s) in merged.items() if n}
        return mix_service_model(live, self._pool.bucket_costs(),
                                 self.buckets)

    def _est_batch_ms(self):
        """Per-batch service-time estimate (the ``_service_model``'s
        headline number; kept as the stable introspection surface)."""
        return self._service_model()["est_batch_ms"]

    def _signals(self):
        """Point-in-time :class:`AdmissionSignals` — lock-free reads of
        structures the hot path already maintains."""
        model = self._service_model()
        est = model["est_batch_ms"]
        pending = self.batcher.pending_rows
        rows_per_batch = max(1.0, model["est_rows_per_batch"])
        inflight = sum(self._inflight_n)
        # HEALTHY replicas, not configured ones: a quarantined replica
        # serves nothing, so the queue drains slower and the in-flight
        # ceiling is lower — est-wait must say so or admission admits
        # into a wait it cannot honor (degraded capacity stays honest)
        healthy = self.healthy_replicas()
        n_rep = max(1, healthy)
        batches_ahead = math.ceil(pending / rows_per_batch) + inflight
        age = _diag.progress_age_s()
        for w in _diag.active_waits():
            # a device wait (serving collect, fit pacing) older than the
            # watchdog's engine progress is the sharper wedge signal
            age = max(age, w["age_s"])
        mem = None
        if self._mem_budget:
            mem = max(0.0, 1.0 - _diag.ledger().live_bytes()
                      / self._mem_budget)
        return AdmissionSignals(
            queue_depth=self.batcher.depth,
            queue_limit=self.batcher.max_queue,
            pending_rows=pending,
            inflight_depth=inflight,
            inflight_limit=self.max_in_flight * healthy,
            replicas=healthy,
            est_batch_ms=est,
            est_queue_wait_ms=est * batches_ahead / n_rep,
            watchdog_age_s=age,
            mem_headroom_frac=mem)

    def _admit(self):
        pol = self._admission
        if pol is None:
            return
        decision = pol.decide(self._signals())
        self._admission_state = decision.state
        if not decision.admit:
            reason_key = decision.reason.split(":")[0]
            self.metrics.counter("requests_shed",
                                 labels={"reason": reason_key}).inc()
            self._sheds_by_reason[reason_key] = \
                self._sheds_by_reason.get(reason_key, 0) + 1
            self._last_shed_reason = decision.reason
            raise AdmissionShed("admission control: %s" % decision.reason)

    def admission_snapshot(self):
        """The ``/debug/state`` admission block: current state, shed
        tallies by reason, and the live signal values."""
        return {"state": STATE_NAMES.get(self._admission_state,
                                         self._admission_state),
                "policy": type(self._admission).__name__
                if self._admission is not None else None,
                "sheds_by_reason": dict(self._sheds_by_reason),
                "last_shed_reason": self._last_shed_reason,
                "service_model": self._service_model(),
                "signals": self._signals().to_dict()}

    # ------------------------------------------------------------ workers
    def _spawn_worker(self, idx):
        t = threading.Thread(target=self._worker_main, args=(idx,),
                             daemon=True, name="mxtpu-serving-%d" % idx)
        t.start()
        return t

    def healthy_replicas(self):
        """Replica slots with a live (non-quarantined) worker."""
        return sum(1 for q in self._quarantined if not q)

    def _worker_main(self, idx):
        """The worker's outermost frame: a loop that exits normally is
        a drain; ANYTHING else (including a ``BaseException`` like an
        injected kill) is a worker death and takes the quarantine/
        respawn path instead of silently shrinking capacity."""
        inflight = deque()
        loop = self._continuous_loop if self.mode == "continuous" \
            else self._burst_loop
        try:
            loop(idx, inflight)
        except BaseException as exc:
            # shutdown unwinding is not a death — but its waiters must
            # still be answered, never left to hit their own timeouts
            self._on_worker_death(idx, inflight, exc,
                                  respawn=not self._closed)

    def _on_worker_death(self, idx, inflight, exc, respawn=True):
        """Quarantine replica ``idx``: answer every in-flight waiter
        with 500 (a dead worker must NEVER leave a waiter hung),
        shrink the advertised capacity, and start the off-hot-path
        rebuild+respawn. Runs on the dying worker thread.
        ``respawn=False`` (session closing) only answers the waiters."""
        crash = ReplicaCrash("serving replica %d died: %s: %s"
                             % (idx, type(exc).__name__, exc))
        while inflight:
            self._fail_batch(inflight.popleft().batch, crash)
        self._inflight_n[idx] = 0
        if not respawn:
            return
        self._quarantined[idx] = True
        self.metrics.counter(
            "replica_quarantined").inc()
        _diag.record("serving", "replica_quarantined", idx)
        log.error("serving: worker %d died (%s: %s) — replica "
                  "quarantined, capacity %d/%d, respawning",
                  idx, type(exc).__name__, exc,
                  self.healthy_replicas(), len(self._pool.replicas))
        threading.Thread(target=self._respawn_replica, args=(idx,),
                         daemon=True,
                         name="mxtpu-serving-respawn-%d" % idx).start()

    def _respawn_replica(self, idx):
        """Rebuild the dead replica's predictor (fresh — its cached
        state is not trusted), re-warm its buckets so the revived
        worker never compiles mid-traffic, clear the quarantine, and
        start a new worker thread. All off the hot path; bounded by
        the shared RetryPolicy. A rebuild that exhausts its retries
        leaves the replica quarantined — capacity stays honest."""
        from ..compile import pipeline as _pipeline

        def rebuild():
            pool = self._pool
            rep = pool.rebuild_replica(idx % len(pool.replicas))
            with _pipeline.prewarm_scope():
                pool._warmup_replica(rep, self.buckets)

        try:
            # constructed INSIDE the guarded region: a bad env value
            # must land in the failed-outcome path below, not kill the
            # respawn thread above its own failure handling
            # (MXTPU_SERVING_RESPAWN_RETRIES = retries after the first
            # attempt; tolerant parse via env_attempts)
            policy = RetryPolicy(
                "serving.respawn",
                max_attempts=env_attempts(
                    "MXTPU_SERVING_RESPAWN_RETRIES", 1),
                backoff_s=0.2, backoff_cap_s=5.0, retryable=Exception,
                logger=log)
            policy.call(rebuild)
        except BaseException as rebuild_exc:
            # BaseException on purpose: a kill-mode fault (FaultKill)
            # firing inside the re-warm must land in the SAME failed
            # outcome — a respawn thread dying silently would leave the
            # replica quarantined with no counter and no log, the exact
            # silent capacity shrink this path exists to eliminate
            self.metrics.counter("replica_respawned",
                                 labels={"outcome": "failed"}).inc()
            log.error("serving: replica %d rebuild failed (%r) — "
                      "staying quarantined at capacity %d/%d", idx,
                      rebuild_exc, self.healthy_replicas(),
                      len(self._pool.replicas))
            return
        if self._closed:
            return
        self._last_retire_t[idx] = None
        self._quarantined[idx] = False
        self._workers[idx] = self._spawn_worker(idx)
        self.metrics.counter("replica_respawned",
                             labels={"outcome": "ok"}).inc()
        _diag.record("serving", "replica_respawned", idx)
        log.warning("serving: replica %d respawned — capacity %d/%d",
                    idx, self.healthy_replicas(),
                    len(self._pool.replicas))

    def _fail_batch(self, batch, exc):
        """Answer a batch's requests with ``exc``; never kill the worker.
        Backend failures (XLA error, OOM, nonzero native return) capture
        a postmortem; usage errors and sanitizer trips (which dump their
        own, source=sanitizer) stay quiet."""
        batch.fail(exc)
        self.metrics.counter("requests_failed").inc(len(batch.items))
        if not isinstance(exc, MXNetError) or isinstance(exc, NativeError):
            _diag.postmortem("serving_batch_exception", exc=exc,
                             source="serving")

    def _retire(self, inf, idx):
        """Materialize one in-flight batch's outputs (the single bulk
        device→host transfer) and answer its requests. The batch is
        already out of the worker's in-flight window, so even a
        ``BaseException`` (kill at the collect seam) must answer its
        waiters before unwinding the thread."""
        batch = inf.batch
        try:
            outs = inf.rep.collect(inf.handles)
            batch.finish(outs)
            now = time.monotonic()
            self.metrics.counter("requests_completed").inc(len(batch.items))
            self.metrics.histogram("batch_exec_ms").observe(
                (now - inf.t_dispatch) * 1e3)
            # marginal service time: since the PREVIOUS retire if this
            # batch overlapped it on device, since its own dispatch
            # otherwise — the admission estimate's rate basis (the raw
            # dispatch→retire span above includes pipeline wait)
            prev = self._last_retire_t[idx]
            base = prev if prev is not None and prev > inf.t_dispatch \
                else inf.t_dispatch
            self._record_service(idx, batch.bucket, (now - base) * 1e3)
            self._last_retire_t[idx] = now
            for it in batch.items:
                self.metrics.histogram("request_latency_ms").observe(
                    (now - it.t_enqueue) * 1e3)
        except Exception as exc:
            self._fail_batch(batch, exc)
        except BaseException as exc:
            self._fail_batch(batch, ReplicaCrash(
                "serving replica died retiring a batch: %s: %s"
                % (type(exc).__name__, exc)))
            raise

    def _continuous_loop(self, idx, inflight):
        """One per replica slot-window: keep up to K batches in flight,
        refill a freed slot from the queue within one dispatch cycle.
        The only blocking host sync is the retire of the OLDEST batch —
        by then the device is already executing the newer ones, so
        device idle between bursts collapses to the refill latency.
        ``inflight`` is owned by ``_worker_main`` so a worker death can
        fail the window's waiters instead of stranding them."""
        t_slot_free = None    # a retire freed a slot at this time
        t_device_idle = None  # nothing in flight since this time
        while True:
            k = max(1, self.max_in_flight)
            if len(inflight) >= k:
                self._retire(inflight.popleft(), idx)
                self._inflight_n[idx] = len(inflight)
                t_slot_free = time.monotonic()
                if not inflight:
                    t_device_idle = t_slot_free
                continue
            # with work in flight, poll the queue (timeout=0): sitting
            # in a wait would delay the retire of completed batches
            batch = self.batcher.next_fill(
                timeout=0.0 if inflight else 0.25, hungry=True)
            if batch is None:
                if inflight:
                    self._retire(inflight.popleft(), idx)
                    self._inflight_n[idx] = len(inflight)
                    t_slot_free = time.monotonic()
                    if not inflight:
                        t_device_idle = t_slot_free
                    continue
                if self.batcher._closed and self.batcher.depth == 0:
                    return
                continue
            now = time.monotonic()
            if t_slot_free is not None:
                self.metrics.histogram("refill_latency_ms").observe(
                    (now - t_slot_free) * 1e3)
                t_slot_free = None
            if t_device_idle is not None:
                self.metrics.histogram("dispatch_idle_gap_ms").observe(
                    (now - t_device_idle) * 1e3)
                t_device_idle = None
            if batch.flush_reason == "watermark":
                self.metrics.counter("batches_refilled").inc()
            pool = self._pool  # volatile read: hot-swap flips this
            rep = pool.replicas[idx % len(pool.replicas)]
            try:
                # parent the batch span on the first request's submitting
                # span: the trace id crosses the queue hop, so a request
                # trace shows submit -> batch -> pool.dispatch -> executor
                with _tel.span("batch[%d]" % batch.bucket,
                               category="serving",
                               parent=batch.items[0].span,
                               tags={"n_valid": batch.n_valid}):
                    with self.metrics.span("pool.dispatch"):
                        handles = rep.dispatch(batch.inputs)
            except Exception as exc:
                self._fail_batch(batch, exc)
                continue
            except BaseException as exc:
                # worker death mid-dispatch (injected kill, real crash
                # unwinding): this batch is not yet in the in-flight
                # window _worker_main rescues — answer its waiters
                # before the thread dies
                self._fail_batch(batch, ReplicaCrash(
                    "serving replica %d died dispatching: %s: %s"
                    % (idx, type(exc).__name__, exc)))
                raise
            inflight.append(_InFlight(batch, handles, rep, now))
            self._inflight_n[idx] = len(inflight)

    def _burst_loop(self, idx, inflight):
        """The PR-1 loop: pull a batch, run it to completion, answer its
        requests. The device idles from the end of each batch until the
        next dispatch (response slicing + queue wait) — the gap the
        continuous mode exists to close; ``dispatch_idle_gap_ms`` makes
        that cost visible in both modes. ``inflight`` stays empty (one
        batch at a time, failed in-line) — the parameter keeps the
        worker-main contract uniform across modes."""
        del inflight
        t_idle = None
        while True:
            batch = self.batcher.next_batch(timeout=0.25)
            if batch is None:
                if self.batcher._closed and self.batcher.depth == 0:
                    return
                continue
            t0 = time.monotonic()
            if t_idle is not None:
                self.metrics.histogram("dispatch_idle_gap_ms").observe(
                    (t0 - t_idle) * 1e3)
            pool = self._pool
            replica = pool.replicas[idx % len(pool.replicas)]
            try:
                with _tel.span("batch[%d]" % batch.bucket,
                               category="serving",
                               parent=batch.items[0].span,
                               tags={"n_valid": batch.n_valid}):
                    outs = pool.run(batch.inputs, replica=replica)
                batch.finish(outs)
                self.metrics.counter("requests_completed").inc(
                    len(batch.items))
                done = time.monotonic()
                self.metrics.histogram("batch_exec_ms").observe(
                    (done - t0) * 1e3)
                # burst runs one batch at a time: the marginal service
                # time IS the dispatch→answer span
                self._record_service(idx, batch.bucket, (done - t0) * 1e3)
                for it in batch.items:
                    self.metrics.histogram("request_latency_ms").observe(
                        (done - it.t_enqueue) * 1e3)
            except Exception as exc:  # answer, don't kill the worker
                self._fail_batch(batch, exc)
            except BaseException as exc:
                # worker death: answer before the thread unwinds
                self._fail_batch(batch, ReplicaCrash(
                    "serving replica %d died mid-batch: %s: %s"
                    % (idx, type(exc).__name__, exc)))
                raise
            t_idle = time.monotonic()

    # ------------------------------------------------------------ client
    def predict(self, inputs, timeout=None):
        """Synchronous single-request inference: dict of arrays (leading
        dim = #examples, usually 1) -> list of numpy outputs. Raises
        AdmissionShed/QueueFull under backpressure (HTTP 429),
        TimeoutError past ``timeout`` (504)."""
        if self._closed:
            raise BatcherClosed("serving session is closed")
        timeout = timeout if timeout is not None else self.default_timeout
        self.metrics.counter("requests_received").inc()
        self._admit()
        with self.metrics.span("serving.request"):
            item = self.batcher.submit(inputs, timeout=timeout)
            return item.wait(timeout)

    def predict_async(self, inputs, timeout=None):
        """Enqueue and return the WorkItem future (``.wait(timeout)``)."""
        if self._closed:
            raise BatcherClosed("serving session is closed")
        self.metrics.counter("requests_received").inc()
        self._admit()
        return self.batcher.submit(inputs, timeout=timeout)

    def stats(self):
        return self.metrics.to_dict()

    @property
    def closed(self):
        return self._closed

    def close(self, drain=True):
        """Graceful shutdown: refuse new work, flush the queue, retire
        every in-flight batch, join the dispatchers. With
        ``drain=False`` pending requests are failed instead."""
        if self._closed:
            return
        self._closed = True
        from .. import executor as _executor
        _executor.remove_build_listener(self._build_listener)
        if not drain:
            self.batcher.abort(BatcherClosed("serving session shut down"))
        self.batcher.close()
        for w in self._workers:
            w.join(timeout=30)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


# ---------------------------------------------------------------- HTTP
class _Handler(BaseHTTPRequestHandler):
    server_version = "mxtpu-serving/2.0"

    def _json(self, code, payload):
        self._text(code, json.dumps(payload), "application/json")

    def _text(self, code, body, content_type):
        body = body.encode() if isinstance(body, str) else body
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):  # quiet by default; metrics carry the signal
        pass

    def do_GET(self):
        session = self.server.session
        decode = self.server.decode
        path, _, query = self.path.partition("?")
        if path in ("/healthz", "/"):
            # a combined server drains when EITHER attached session is
            # closed — the balancer must stop routing the moment one of
            # the two route families starts answering 503
            closed = any(s.closed for s in (session, decode)
                         if s is not None)
            if closed:
                self._json(503, {"status": "draining"})
                return
            if session is not None:
                healthy = session.healthy_replicas()
                total = len(session.pool)
                body = {"status": "degraded" if healthy < total
                        else "ok",
                        "replicas": total,
                        "healthy_replicas": healthy,
                        "degraded": healthy < total,
                        "buckets": list(session.buckets),
                        "mode": session.mode,
                        "version": session.version_tag,
                        "admission": STATE_NAMES.get(
                            session._admission_state, "?")}
            else:
                body = {"status": "ok", "mode": "decode",
                        "buckets": list(decode.buckets),
                        "version": decode.version_tag,
                        "admission": STATE_NAMES.get(
                            decode._admission_state, "?")}
            if decode is not None and session is not None:
                body["decode"] = {
                    "buckets": list(decode.buckets),
                    "version": decode.version_tag,
                    "admission": STATE_NAMES.get(
                        decode._admission_state, "?")}
            self._json(200, body)
        elif path == "/v1/version":
            owner = session if session is not None else decode
            body = owner.version_info()
            if session is not None and decode is not None:
                body["decode"] = decode.version_info()
            self._json(200, body)
        elif path == "/v1/metrics":
            # legacy flat-JSON contract: this session's serving stats
            # (+ the decode session's under "decode" when both attached)
            owner = session if session is not None else decode
            body = owner.stats()
            if session is not None and decode is not None:
                body["decode"] = decode.stats()
            self._json(200, body)
        elif path == "/metrics":
            # the full pane: process-wide registry (engine, executor,
            # fit, kvstore, io) + every attached session registry.
            # Prometheus text by default; ?format=json for the same data
            regs = (_tel.registry(),)
            if session is not None:
                regs += (session.metrics,)
            if decode is not None:
                regs += (decode.metrics,)
            if "format=json" in query:
                self._json(200, _tel.json_snapshot(*regs))
            else:
                self._text(200, _tel.prometheus_text(*regs),
                           _tel.PROMETHEUS_CONTENT_TYPE)
        elif path == "/debug/state":
            # live debug snapshot: buffer ledger, program cost table,
            # flight-recorder ring, engine state, active device waits —
            # what a postmortem dumps, served on demand; plus the serving
            # panels mxtpu_top renders (admission, version, warm cache,
            # decode slots)
            state = _diag.debug_state()
            if session is not None:
                state["serving"] = session.stats()
                state["serving_admission"] = session.admission_snapshot()
                state["serving_version"] = session.version_info()
            if decode is not None:
                state["decode"] = decode.debug_panel()
            state["serving_warm_cache"] = warm_cache().manifest()
            self._json(200, state)
        elif path == "/debug/trace":
            # the whole captured timeline as Chrome trace-event JSON:
            # span ring as duration slices on per-thread tracks, flight
            # ring as instants, cross-thread parent links as flow
            # events. Load the body straight into Perfetto / chrome
            # about:tracing, or fetch via `mxtpu_top --trace-out`.
            from ..obs import trace_export as _trace_export
            self._text(200, _trace_export.dumps(), "application/json")
        else:
            self._json(404, {"error": "unknown path %s" % self.path})

    def do_POST(self):
        session = self.server.session
        path, _, query = self.path.partition("?")
        if path in ("/v1/admin/swap",):
            self._do_swap()
            return
        if path in ("/v1/generate",):
            self._do_generate(self.server.decode, query)
            return
        if path not in ("/v1/predict", "/predict"):
            self._json(404, {"error": "unknown path %s" % self.path})
            return
        if session is None:
            self._json(404, {"error": "no predict session attached "
                             "(decode-only server; POST /v1/generate)"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(payload, dict) or \
                    not isinstance(payload.get("inputs"), dict):
                raise ValueError("body must be {\"inputs\": {name: array}}")
            raw = payload["inputs"]
            # mxtpu: allow-sync(JSON body decode — host data by nature)
            inputs = {k: _np.asarray(v, dtype=_np.float32)
                      for k, v in raw.items()}
            timeout = payload.get("timeout_sec",
                                  self.server.request_timeout)
            if timeout is not None:
                timeout = float(timeout)
        except (ValueError, TypeError, KeyError, IndexError) as exc:
            self._json(400, {"error": str(exc)})
            return
        try:
            outs = session.predict(inputs, timeout=timeout)
            self._json(200, {"outputs": [o.tolist() for o in outs]})
        except AdmissionShed as exc:
            # policy shed: same backpressure status as a full queue, but
            # the body names the signal so clients/dashboards can tell
            self._json(429, {"error": str(exc), "shed": True})
        except QueueFull as exc:
            self._json(429, {"error": str(exc)})
        except TimeoutError as exc:
            self._json(504, {"error": str(exc)})
        except BatcherClosed as exc:
            self._json(503, {"error": str(exc)})
        except NumericsError as exc:
            # the sanitizer tripped on the model's outputs: the server's
            # numerics are at fault, not the request — 500, and the
            # sanitizer already dumped its postmortem (source=sanitizer)
            self._json(500, {"error": str(exc)})
        except MXNetError as exc:
            self._json(400, {"error": str(exc)})
        except Exception as exc:  # backend failure (XLA error, OOM, ...)
            # the client must get a JSON 500, never a reset socket
            self._json(500, {"error": "%s: %s"
                             % (type(exc).__name__, exc)})

    def _do_generate(self, decode, query=""):
        """POST /v1/generate {"prompt": [token ids], "max_new_tokens"?,
        "eos_id"?, "seed"?, "temperature"?, "timeout_sec"?} -> token ids
        (and text when the session holds a vocab map). Same overload
        taxonomy as predict: 429 shed/full, 504 deadline, 503 drain.
        With ``?stream=1`` the response is a chunked NDJSON stream
        (:meth:`_stream_generate`) — tokens as they retire."""
        if decode is None:
            self._json(404, {"error": "no decode session attached "
                             "(pass decode= to ServingHTTPServer)"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            if not isinstance(payload, dict) or \
                    not isinstance(payload.get("prompt"), list):
                raise ValueError(
                    "body must be {\"prompt\": [token ids], ...}")
            prompt = [int(t) for t in payload["prompt"]]
            kwargs = {}
            if payload.get("max_new_tokens") is not None:
                kwargs["max_new_tokens"] = int(payload["max_new_tokens"])
            if payload.get("eos_id") is not None:
                kwargs["eos_id"] = int(payload["eos_id"])
            kwargs["seed"] = int(payload.get("seed", 0))
            kwargs["temperature"] = float(payload.get("temperature", 0.0))
            timeout = payload.get("timeout_sec",
                                  self.server.request_timeout)
            if timeout is not None:
                timeout = float(timeout)
        except (ValueError, TypeError, KeyError) as exc:
            self._json(400, {"error": str(exc)})
            return
        if query and "stream=1" in query.split("&"):
            self._stream_generate(decode, prompt, timeout, kwargs)
            return
        try:
            result = decode.generate(prompt, timeout=timeout, **kwargs)
            self._json(200, result)
        except AdmissionShed as exc:
            self._json(429, {"error": str(exc), "shed": True})
        except QueueFull as exc:
            self._json(429, {"error": str(exc)})
        except TimeoutError as exc:
            self._json(504, {"error": str(exc)})
        except BatcherClosed as exc:
            self._json(503, {"error": str(exc)})
        except NumericsError as exc:
            self._json(500, {"error": str(exc)})
        except MXNetError as exc:
            self._json(400, {"error": str(exc)})
        except Exception as exc:  # backend failure / worker crash
            self._json(500, {"error": "%s: %s"
                             % (type(exc).__name__, exc)})

    def _write_stream_event(self, event):
        """One NDJSON line as one HTTP/1.1 chunk (manual hex-size
        framing — ``http.server`` has no chunked writer)."""
        body = (json.dumps(event) + "\n").encode()
        self.wfile.write(b"%x\r\n" % len(body) + body + b"\r\n")

    def _stream_generate(self, decode, prompt, timeout, kwargs):
        """``POST /v1/generate?stream=1``: chunked ``application/
        x-ndjson``, one event per line as the session retires tokens —
        ``{"token", "index"}`` each, then a terminal ``{"done": result}``
        or ``{"error", "type"}``. Errors BEFORE the stream commits keep
        the ordinary JSON status taxonomy (429/504/503/400/500); once
        the 200 header is out, every failure — including a mid-stream
        deadline — arrives as a clean terminal error event followed by
        the last-chunk marker, never a reset socket."""
        try:
            item = decode.generate_async(prompt, timeout=timeout,
                                         stream=True, **kwargs)
        except AdmissionShed as exc:
            self._json(429, {"error": str(exc), "shed": True})
            return
        except QueueFull as exc:
            self._json(429, {"error": str(exc)})
            return
        except BatcherClosed as exc:
            self._json(503, {"error": str(exc)})
            return
        except MXNetError as exc:
            self._json(400, {"error": str(exc)})
            return
        except Exception as exc:
            self._json(500, {"error": "%s: %s"
                             % (type(exc).__name__, exc)})
            return
        # committed: chunked transfer needs HTTP/1.1 on the status line;
        # one response per connection (the chunked tail is the terminator)
        self.protocol_version = "HTTP/1.1"
        self.close_connection = True
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.send_header("Cache-Control", "no-cache")
        self.end_headers()
        # per-event wait: the request deadline plus margin (the SESSION
        # enforces the deadline and pushes the terminal error event; this
        # bound only catches a wedged producer)
        wait_s = (timeout + 5.0) if timeout is not None \
            else (self.server.request_timeout or 30.0)
        try:
            while True:
                try:
                    ev = item.stream.get(wait_s)
                except TimeoutError as exc:
                    self._write_stream_event(
                        {"error": str(exc), "type": "TimeoutError"})
                    break
                if ev is None:
                    break
                self._write_stream_event(ev)
                if "done" in ev or "error" in ev:
                    break
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            # client went away mid-stream: the sequence finishes (or
            # deadlines) server-side; events drop at the closed socket
            pass

    def _do_swap(self):
        """POST /v1/admin/swap {"symbol_file", "params_file",
        "version_tag"?, "target"?}: hot-swap from checkpoint files on
        the server's filesystem (the rollout surface; in-process callers
        use ``session.swap_model`` directly). On a combined server
        ``"target": "predict"|"decode"`` names which session to roll
        (default: the predict session when attached, else decode) — a
        decode checkpoint must never land on the predict pool by
        routing accident.

        Control-plane gating: predict is the open data plane, but a
        model mutation that opens server-side file paths must not be —
        the endpoint answers 403 unless the server was given an admin
        token (``admin_token=`` / ``MXTPU_SERVING_ADMIN_TOKEN``) and the
        request carries it in ``X-Admin-Token``."""
        import hmac
        from .. import ndarray as _nd
        token = self.server.admin_token
        if not token:
            self._json(403, {"error": "admin API disabled: pass "
                             "admin_token= to ServingHTTPServer or set "
                             "MXTPU_SERVING_ADMIN_TOKEN"})
            return
        sent = self.headers.get("X-Admin-Token", "")
        if not hmac.compare_digest(sent, token):
            self._json(403, {"error": "admin token mismatch"})
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(length) or b"{}")
            symbol_file = payload["symbol_file"]
            params_file = payload["params_file"]
            tag = payload.get("version_tag")
            target = payload.get("target")
            if target is None:
                target = "predict" if self.server.session is not None \
                    else "decode"
            if target not in ("predict", "decode"):
                raise ValueError("target must be 'predict' or 'decode' "
                                 "(got %r)" % (target,))
            session = self.server.session if target == "predict" \
                else self.server.decode
            if session is None:
                raise ValueError("no %s session attached" % target)
            with open(symbol_file) as f:
                symbol_json = f.read()
            params = _nd.load(params_file)
        except (KeyError, ValueError, TypeError, OSError) as exc:
            self._json(400, {"error": "swap request: %s" % exc})
            return
        try:
            info = session.swap_model(symbol_json, params, version_tag=tag)
            self._json(200, info)
        except BatcherClosed as exc:
            self._json(503, {"error": str(exc)})
        except MXNetError as exc:
            self._json(400, {"error": str(exc)})
        except Exception as exc:
            self._json(500, {"error": "%s: %s"
                             % (type(exc).__name__, exc)})


class ServingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer bound to a ServingSession. ``shutdown`` drains
    the session before the socket closes."""

    daemon_threads = True

    def __init__(self, session, host="127.0.0.1", port=0,
                 request_timeout=30.0, admin_token=None, decode=None):
        import os
        if session is None and decode is None:
            raise MXNetError("ServingHTTPServer needs a ServingSession, "
                             "a DecodeSession (decode=), or both")
        super().__init__((host, port), _Handler)
        self.session = session
        # a DecodeSession (mxtpu.serving.decode) answering /v1/generate;
        # may ride alongside the predict session or alone
        self.decode = decode
        self.request_timeout = request_timeout
        # gates POST /v1/admin/swap; None (and no env) disables it
        self.admin_token = admin_token if admin_token is not None \
            else os.environ.get("MXTPU_SERVING_ADMIN_TOKEN") or None

    @property
    def endpoint(self):
        return "http://%s:%d" % self.server_address[:2]

    def shutdown(self):
        if self.session is not None:
            self.session.close(drain=True)
        if self.decode is not None:
            self.decode.close(drain=True)
        super().shutdown()


def serve(symbol_json, params, example_shapes, host="127.0.0.1", port=8080,
          block=True, **session_kwargs):
    """One-call entry point: build the session, bind the socket, serve.
    With ``block=False`` returns the running server (serving on a daemon
    thread); call ``server.shutdown()`` to drain and stop."""
    session = ServingSession(symbol_json, params, example_shapes,
                             **session_kwargs)
    server = ServingHTTPServer(session, host=host, port=port)
    if not block:
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        return server
    try:
        server.serve_forever()
    finally:
        server.shutdown()
    return server
