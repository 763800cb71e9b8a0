"""Executor pool: N Predictor replicas over a process-wide warm cache.

One replica per device (``jax.local_devices()``); on a CPU-only host the
same scheme degrades gracefully to thread-level replicas over the host
devices (the forced-8-device test mesh exercises the true multi-replica
path). Each replica owns the model weights ON ITS DEVICE once, and an LRU
of bound executors keyed ``(symbol-json hash, bucket shape, dtype)`` —
the serving analogue of TVM's ahead-of-time module table: every shape the
batcher can emit is compiled exactly once per replica (``warmup``), after
which dispatch never traces.

New in the continuous-batching rework: the per-replica Predictors are
registered in a **process-wide** :class:`WarmExecutableCache` keyed
``(symbol hash, version tag, ctx)``. Pools for the same (model, version,
weights) ADOPT the cached predictor — its warmed bind cache and compiled
executables included — so a hot-swap back to a previous version
(rollback) costs zero compiles, and :func:`prewarm` can compile a whole
deploy manifest (every ctx x bucket) before the first session exists.
Warmup measures a steady-state per-bucket batch time and attaches the
PR-4 cost-registry row (flops/bytes) to it; the admission policy and
``derive_knobs`` read those rows instead of hand-picked constants.
"""
from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict

import jax

from .. import diagnostics as _diag
from ..analysis import concurrency as _conc
from ..base import MXNetError
from ..context import Context
from ..faults import injection as _faults
from ..predict import Predictor

__all__ = ["ExecutorPool", "WarmExecutableCache", "warm_cache", "prewarm",
           "default_contexts", "symbol_json_hash", "params_token"]


def default_contexts(max_replicas=None):
    """One Context per local jax device (cpu(i) on CPU hosts, gpu(i) —
    the accelerator alias — otherwise)."""
    devs = jax.local_devices()
    kind = "cpu" if devs[0].platform == "cpu" else "gpu"
    n = len(devs) if max_replicas is None else min(len(devs), max_replicas)
    return [Context(kind, i) for i in range(n)]


def symbol_json_hash(symbol_json):
    """Stable 16-hex digest of a graph (str or Symbol) — the model half
    of every executable-cache key (matches ``Predictor.symbol_hash``)."""
    if not isinstance(symbol_json, str):
        symbol_json = symbol_json.tojson()
    return hashlib.sha1(symbol_json.encode()).hexdigest()[:16]


def params_token(params):
    """Identity token of a weight set: (name, buffer-id) pairs plus the
    referenced objects themselves. Object identity — not content hash —
    keeps pool construction instant (hashing gigabytes of weights would
    defeat the instant-adopt point), but an id is only meaningful while
    its referent is alive: on a device context the predictor keeps its
    OWN copies (``as_in_context``), not the caller's arrays, so the
    cache entry must pin the token's referents itself or a freed-then-
    reallocated array at a recycled id could adopt stale weights.
    Returns ``(token, pin)`` — store ``pin`` alongside the token."""
    toks, pin = [], []
    for k in sorted(params or {}):
        v = params[k]
        data = getattr(v, "_data", None)
        ref = data if data is not None else v
        toks.append((k, id(ref)))
        pin.append(ref)
    return tuple(toks), pin


class WarmExecutableCache:
    """Process-wide warm-predictor cache keyed (symbol hash, version tag).

    Each version entry holds one Predictor per ctx (weights on device +
    the shape-keyed bind cache of compiled executables), the
    ``params_token`` that built it, and the per-bucket cost rows warmup
    measured. ``adopt`` is the zero-compile path: a new pool for a
    (model, version) the process has already served gets the live
    predictors back instantly — the hot-swap rollback and the
    multi-session-same-model cases. A token mismatch under the same tag
    (same name, DIFFERENT weights) evicts the stale entry rather than
    ever serving old weights. LRU over whole versions, capped at
    ``MXTPU_SERVING_WARM_VERSIONS`` (default 4).
    """

    def __init__(self, max_versions=None):
        self._lock = _conc.lock("WarmExecutableCache", "_lock")
        self._versions = OrderedDict()  # (hash, tag) -> entry dict
        self._max_versions = int(max_versions) \
            if max_versions is not None else None

    @property
    def max_versions(self):
        """The retention cap. Resolved LIVE through the knob registry
        when not pinned at construction: the singleton cache is built at
        import, and ``MXTPU_SERVING_WARM_VERSIONS`` set later must still
        apply — eviction is a deploy-time path, so the per-register
        resolve costs nothing that matters."""
        if self._max_versions is not None:
            return self._max_versions
        from ..tune import registry as _knobs
        return _knobs.resolve_int("serving.warm_versions")

    @max_versions.setter
    def max_versions(self, v):
        self._max_versions = int(v)

    def adopt(self, sym_hash, tag, ctx, token):
        """The cached predictor for (model, version, ctx), or None.
        Drops the whole version when ``token`` shows the caller's
        weights are not the ones the entry was built from. The entry's
        ``pin`` list keeps the original token referents alive, so id
        equality here really does mean the very same arrays — ids of
        dead objects can be recycled."""
        key = (sym_hash, tag)
        with self._lock:
            v = self._versions.get(key)
            if v is None:
                return None
            if v["token"] != token:
                del self._versions[key]  # stale weights: never serve them
                return None
            self._versions.move_to_end(key)
            return v["replicas"].get(str(ctx))

    def register(self, sym_hash, tag, ctx, token, predictor, pin=()):
        key = (sym_hash, tag)
        with self._lock:
            v = self._versions.get(key)
            if v is None or v["token"] != token:
                v = {"token": token, "pin": list(pin), "replicas": {},
                     "costs": {}, "created": time.time()}
                self._versions[key] = v
            v["replicas"][str(ctx)] = predictor
            self._versions.move_to_end(key)
            while len(self._versions) > self.max_versions:
                self._versions.popitem(last=False)

    @staticmethod
    def _cost_key(bucket, pipeline=None):
        """Cost rows are keyed (bucket, compile-pipeline config): the
        same (symbol, version) serves very different exec_ms once a
        rewrite (bf16, quant) is in play, and a quantized swap-in must
        not inherit the f32 service model and mis-derive the admission
        watermark. ``pipeline=None`` stamps the CURRENT config."""
        if pipeline is None:
            from ..compile import pipeline as _pipeline
            pipeline = _pipeline.configured()
        return (int(bucket), tuple(pipeline))

    def record_cost(self, sym_hash, tag, bucket, cost, pipeline=None):
        key = self._cost_key(bucket, pipeline)
        with self._lock:
            v = self._versions.get((sym_hash, tag))
            if v is not None:
                v["costs"][key] = dict(cost)

    def costs_for(self, sym_hash, tag, pipeline=None):
        """The version's measured rows for ONE pipeline config (default:
        the current one), in the ``{bucket: cost}`` shape the admission
        policy and ``derive_knobs`` consume."""
        want = self._cost_key(0, pipeline)[1]
        with self._lock:
            v = self._versions.get((sym_hash, tag))
            if v is None:
                return {}
            return {b: dict(c) for (b, cfg), c in v["costs"].items()
                    if cfg == want}

    def evict(self, sym_hash=None, tag=None):
        """Drop matching versions (both None = clear). Returns #evicted."""
        with self._lock:
            keys = [k for k in self._versions
                    if (sym_hash is None or k[0] == sym_hash)
                    and (tag is None or k[1] == tag)]
            for k in keys:
                del self._versions[k]
            return len(keys)

    def __len__(self):
        with self._lock:
            return len(self._versions)

    def manifest(self):
        """JSON-ready inventory (the ``/debug/state`` warm-cache block):
        per version, which ctxs hold predictors, which buckets are
        compiled, and the measured cost rows. The per-version dicts are
        snapshotted UNDER the lock — register()/record_cost() mutate
        them during a hot-swap warmup, and a concurrent /debug/state
        scrape must not crash on a resizing dict."""
        with self._lock:
            items = [((key, dict(v["replicas"]), dict(v["costs"]),
                       v["created"]))
                     for key, v in self._versions.items()]
        out = []
        for (sym_hash, tag), replicas, costs, created in items:
            ctxs = {}
            for ctx, pred in replicas.items():
                # list() is one atomic C-level copy: a concurrent rebind
                # on the serving thread must not break the snapshot
                keys = list(pred._bind_cache)
                ctxs[ctx] = sorted({shapes[0][1][0] for shapes in keys})
            out.append({"symbol_hash": sym_hash, "version": tag,
                        "created": created, "replicas": ctxs,
                        # "8" for pipeline-less rows, "8@bf16,quant"
                        # for rows measured under a rewrite config
                        "bucket_costs": {
                            "%d@%s" % (b, ",".join(cfg)) if cfg
                            else str(b): c
                            for (b, cfg), c in costs.items()}})
        return out


_WARM_CACHE = WarmExecutableCache()


def warm_cache():
    """The process-wide :class:`WarmExecutableCache` singleton."""
    return _WARM_CACHE


class _Replica:
    """One device's predictor: ONE weight copy + the shape-keyed executor
    LRU that Predictor itself maintains (``_bind_cache``). The effective
    cache identity is (symbol-json hash, bucket shapes, dtype): the symbol
    hash and the float32 request dtype are fixed per replica, so the bind
    cache's shape key carries the varying part. The dispatch lock lives
    ON the predictor (``_serving_lock``): two pools that adopt the same
    cached predictor across a rapid double hot-swap must serialize on
    one lock, not one each."""

    def __init__(self, symbol_json, params, example_shapes, ctx, cache_size,
                 metrics=None, record_executor=None, version_tag="v0",
                 shared_cache=None):
        self.ctx = ctx
        self.metrics = metrics
        self._record = record_executor or (lambda ex: None)
        self.sym_hash = symbol_json_hash(symbol_json)
        self.version_tag = version_tag
        token, pin = params_token(params)
        base = shared_cache.adopt(self.sym_hash, version_tag, ctx, token) \
            if shared_cache is not None else None
        self.adopted = base is not None
        if base is not None:
            base._max_cached_binds = max(base._max_cached_binds, cache_size)
            if metrics:
                metrics.counter("warm_cache_adoptions").inc()
        else:
            # every buffer the replica's executors bind lands in the
            # memory ledger under the pool's own origin (outermost
            # attribution wins over the inner 'executor' tagging)
            with _diag.alloc_origin("serving_pool"):
                base = Predictor(symbol_json, params, ctx=ctx,
                                 input_shapes=example_shapes,
                                 max_cached_binds=cache_size)
            if shared_cache is not None:
                shared_cache.register(self.sym_hash, version_tag, ctx,
                                      token, base, pin=pin)
        self.base = base
        if getattr(base, "_serving_lock", None) is None:
            base._serving_lock = _conc.lock("_Replica", "lock")
        self.lock = base._serving_lock
        self._record(self.base._executor)

    def predictor_for(self, shapes):
        """The replica predictor bound to exact input ``shapes`` (cached
        executor reuse; caller must hold ``self.lock``)."""
        key = Predictor.shape_key(shapes)
        cache = self.base._bind_cache
        hit = key in cache
        before = len(cache)
        with _diag.alloc_origin("serving_pool"):
            self.base.reshape(shapes)
        self._record(self.base._executor)
        if self.metrics:
            self.metrics.counter(
                "executor_cache_hits" if hit
                else "executor_cache_misses").inc()
            if not hit and len(cache) == before:
                # the miss inserted one entry yet the cache didn't grow:
                # the LRU evicted a compiled executable
                self.metrics.counter("executor_cache_evictions").inc()
        return self.base

    def dispatch(self, inputs):
        """Issue one already-padded batch WITHOUT waiting for results:
        returns the raw device output arrays (jax dispatch is async).
        The lock covers only bind + issue, so the expensive
        device->host materialization of a PREVIOUS batch never blocks
        the next dispatch — the continuous-batching hot path."""
        _faults.point("serving.replica.dispatch")
        shapes = {k: tuple(v.shape) for k, v in inputs.items()}
        with self.lock:
            pred = self.predictor_for(shapes)
            pred.forward(**inputs)
            return [o._data for o in pred._executor.outputs]

    def collect(self, handles):
        """Materialize dispatched outputs: ONE bulk device->host
        transfer, off the dispatch lock. Registers with the watchdog
        wait table so a wedged device shows up in postmortems."""
        _diag.wait_begin("serving_collect")
        try:
            _faults.point("serving.replica.collect")
            # mxtpu: allow-sync(response materialization — the single
            # bulk transfer at the end of the request path, deliberately
            # outside the dispatch lock)
            return jax.device_get(handles)
        finally:
            _diag.wait_end()

    def run(self, inputs):
        """Forward one padded batch synchronously (warmup, burst mode);
        returns list of np outputs."""
        return self.collect(self.dispatch(inputs))


class ExecutorPool:
    """Round-robin scheduler over device replicas.

    ``example_shapes`` are per-request input shapes with a leading batch
    dim of 1 (e.g. ``{"data": (1, 3, 32, 32)}``); bucketed batch shapes
    substitute the bucket size for that leading 1. ``version_tag`` names
    this pool's weight set in the process-wide warm cache — distinct
    weights MUST get distinct tags (the hot-swap contract; a reused tag
    with different weights is detected by ``params_token`` and rebuilt,
    never served stale).
    """

    def __init__(self, symbol_json, params, example_shapes, contexts=None,
                 cache_size=8, metrics=None, version_tag="v0",
                 shared_cache=None, bucket_axes=None):
        if not example_shapes:
            raise MXNetError("ExecutorPool requires example_shapes")
        self.example_shapes = {k: tuple(v) for k, v in example_shapes.items()}
        # which axes of each input the bucket size substitutes into:
        # default (0,) — the classic leading batch dim. () pins the
        # example shape (fixed-side inputs, e.g. a single sequence's KV
        # view under a token-bucketed prefill program); (0, 1) covers
        # square masks whose both sides are the bucket.
        self.bucket_axes = {
            k: tuple(int(a) for a in (bucket_axes or {}).get(k, (0,)))
            for k in self.example_shapes}
        for k, axes in self.bucket_axes.items():
            for a in axes:
                if not 0 <= a < len(self.example_shapes[k]):
                    raise MXNetError(
                        "bucket_axes[%r]=%r out of range for example "
                        "shape %r" % (k, axes, self.example_shapes[k]))
        contexts = contexts or default_contexts()
        self.metrics = metrics
        self.version_tag = version_tag
        # kept for replica REBUILD (quarantine/respawn): a fresh
        # predictor needs the graph and the weights the pool was built
        # from (the weights are pinned by the live predictors anyway)
        self._symbol_json = symbol_json if isinstance(symbol_json, str) \
            else symbol_json.tojson()
        self._params = params
        self._cache_size = cache_size
        self._shared = warm_cache() if shared_cache is None else shared_cache
        # executor ownership registry for the build-listener seam: ids are
        # recorded under this dedicated lock at bind time, so membership
        # checks never touch a replica's bind cache (no lock-ordering
        # hazard with in-flight rebinds). Stale ids of evicted executors
        # linger harmlessly — a metrics counter tolerates that.
        self._owned_ids = set()
        self._owned_lock = _conc.lock("ExecutorPool", "_owned_lock")

        def _record(ex):
            with self._owned_lock:
                self._owned_ids.add(id(ex))

        self._record_executor = _record
        self.replicas = [
            _Replica(symbol_json, params, self.example_shapes, ctx,
                     cache_size, metrics=metrics, record_executor=_record,
                     version_tag=version_tag, shared_cache=self._shared)
            for ctx in contexts
        ]
        # adopted replicas bring the cost rows their builder measured
        self._bucket_costs = self._shared.costs_for(
            self.symbol_hash, version_tag) if self._shared else {}
        self._rr = 0
        self._rr_lock = _conc.lock("ExecutorPool", "_rr_lock")

    def __len__(self):
        return len(self.replicas)

    @property
    def symbol_hash(self):
        return self.replicas[0].sym_hash

    @property
    def adopted(self):
        """True when every replica came warm out of the process cache."""
        return all(r.adopted for r in self.replicas)

    def owns_executor(self, executor):
        """True iff ``executor`` was bound by one of this pool's replicas
        (scopes the executor build-listener seam to this pool)."""
        with self._owned_lock:
            return id(executor) in self._owned_ids

    def bucket_shapes(self, bucket):
        """Batch shapes at ``bucket``: the bucket size substituted at
        each input's declared ``bucket_axes`` (default: leading axis)."""
        out = {}
        for k, s in self.example_shapes.items():
            shape = list(s)
            for a in self.bucket_axes[k]:
                shape[a] = int(bucket)
            out[k] = tuple(shape)
        return out

    def bucket_costs(self):
        """Measured per-bucket cost rows ``{bucket: {exec_ms, flops,
        bytes_accessed, compile_ms}}`` — the admission policy's and
        ``derive_knobs``'s deterministic basis. Populated by warmup (or
        inherited from the warm-cache entry on adoption)."""
        return dict(self._bucket_costs)

    def next_replica(self):
        with self._rr_lock:
            r = self.replicas[self._rr % len(self.replicas)]
            self._rr += 1
            return r

    def rebuild_replica(self, idx):
        """Replace replica ``idx`` with a FRESH predictor (quarantine
        recovery): built without warm-cache adoption — a replica that
        just died may have left its cached predictor's bind state
        poisoned, so the cache entry is replaced, never trusted. The
        fresh predictor is then registered OVER the cached one, so
        future adopters (hot-swap rollback, new sessions) get the
        rebuilt replica too. The list-slot assignment is atomic under
        the GIL; dispatchers read ``replicas[idx]`` per batch."""
        old = self.replicas[idx]
        rep = _Replica(self._symbol_json, self._params,
                       self.example_shapes, old.ctx, self._cache_size,
                       metrics=self.metrics,
                       record_executor=self._record_executor,
                       version_tag=self.version_tag, shared_cache=None)
        token, pin = params_token(self._params)
        self._shared.register(rep.sym_hash, self.version_tag, old.ctx,
                              token, rep.base, pin=pin)
        self.replicas[idx] = rep
        return rep

    def run(self, inputs, replica=None):
        """Dispatch one padded batch round-robin (or to ``replica``)."""
        rep = replica if replica is not None else self.next_replica()
        if self.metrics:
            with self.metrics.span("pool.run", category="serving"):
                return rep.run(inputs)
        return rep.run(inputs)

    def warmup(self, buckets):
        """Compile every (replica, bucket) executable up front so traffic
        never pays a jit pause, measuring a steady-state batch time and
        attaching the cost-registry row per bucket. Runs inside the
        compile pipeline's ``prewarm_scope`` so these builds count as
        deploy-time, not mid-traffic misses. Buckets a replica adopted
        warm are skipped (their cost rows rode in with the cache entry).
        Returns the number of programs built."""
        from ..compile import pipeline as _pipeline
        built = 0
        with _pipeline.prewarm_scope():
            for rep in self.replicas:
                built += self._warmup_replica(rep, buckets)
        if self.metrics:
            self.metrics.counter("warmup_programs").inc(built)
        return built

    def _warmup_replica(self, rep, buckets):
        """Warm ONE replica's bucket executables (warmup's inner loop;
        also the quarantine-respawn path, which rebuilds and re-warms a
        single replica off the hot path). Caller wraps in
        ``prewarm_scope`` when the builds should count as deploy-time."""
        import numpy as _np
        built = 0
        for b in buckets:
            shapes = self.bucket_shapes(b)
            key = Predictor.shape_key(shapes)
            if (rep.adopted and key in rep.base._bind_cache
                    and b in self._bucket_costs):
                # adopted warm WITH a cost row for the current pipeline
                # config: compiled AND executed by its builder (a fresh
                # replica's construction bind is only traced lazily — it
                # still needs the first-call compile below). When the
                # config changed since the builder measured (f32 rows,
                # quant config live), _bucket_costs came back empty for
                # this config and the bucket falls through: the forward
                # below rebuilds under the new config and measures it.
                continue
            dummy = {k: _np.zeros(s, dtype=_np.float32)
                     for k, s in shapes.items()}
            with rep.lock:
                pred = rep.predictor_for(shapes)
                # first call pays trace + XLA compile...
                pred.forward(**dummy)
                pred.get_outputs()
                # ...second call is the steady-state batch time
                # the admission policy budgets with
                t0 = time.perf_counter()
                pred.forward(**dummy)
                pred.get_outputs()
                exec_ms = (time.perf_counter() - t0) * 1e3
            if b not in self._bucket_costs:
                rec = _diag.latest_record("fwd_eval")
                cost = {"exec_ms": round(exec_ms, 3),
                        "flops": rec.flops if rec else 0.0,
                        "bytes_accessed":
                            rec.bytes_accessed if rec else 0.0,
                        "compile_ms":
                            rec.compile_ms if rec else 0.0}
                self._bucket_costs[b] = cost
                if self._shared is not None:
                    self._shared.record_cost(
                        rep.sym_hash, rep.version_tag, b, cost)
            built += 1
        return built


def prewarm(symbol_json, params, example_shapes, buckets, contexts=None,
            version_tag="v0", cache_size=8, metrics=None):
    """Deploy-time pre-warm from a bucket-shape manifest: build weights +
    compile every (ctx, bucket) executable into the process-wide warm
    cache BEFORE any session exists. A ``ServingSession`` constructed
    afterward with the same symbol, the same weight arrays and the same
    ``version_tag`` adopts everything — zero compiles on its startup
    path, which is how a hot-swap pre-warms the incoming version while
    the old one still serves. Returns the number of programs built."""
    pool = ExecutorPool(symbol_json, params, example_shapes,
                        contexts=contexts,
                        cache_size=max(cache_size, len(tuple(buckets))),
                        metrics=metrics, version_tag=version_tag)
    return pool.warmup(tuple(buckets))
