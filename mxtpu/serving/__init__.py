"""mxtpu.serving — continuous-batching inference runtime.

The deployment layer above the single-request predict API: compiled
Predictors become a high-throughput multi-replica service that holds
p99 under open-loop load. Pieces:

  * ``batcher``   — thread-safe queue coalescing requests into shape
                    buckets; ``ContinuousBatcher`` adds the refill
                    watermark for slot-driven K-in-flight dispatch
  * ``pool``      — per-device Predictor replicas over a process-wide
                    ``WarmExecutableCache`` (symbol hash x version x
                    ctx), pre-warmable at deploy from a bucket manifest
  * ``admission`` — signal-driven admission control: shed with 429 off
                    queue-wait estimates (PR-4 cost-registry rows),
                    watchdog age and memory-ledger headroom
  * ``server``    — in-process ``ServingSession`` (continuous or burst
                    dispatch, versioned hot-swap with graceful drain) +
                    stdlib JSON-over-HTTP front-end
  * ``metrics``   — qps / shed-rate / batch-fill / in-flight depth /
                    refill latency / latency-percentile observability
                    over ``mxtpu.telemetry``
  * ``decode``    — stateful autoregressive decode serving (and, v2,
                    the paged KV-cache arena + attention decode +
                    chunked prefill + token streaming): device-
                    resident per-sequence state (``SequenceSlotArena``)
                    riding step-granularity continuous batching
                    (``DecodeSession``, ``POST /v1/generate``) with
                    length-aware admission — docs/decode.md

See docs/serving.md for architecture and tuning; docs/observability.md
for the framework-wide telemetry layer this plugs into;
``benchmark/loadgen.py`` for the open-loop (Poisson) load generator.
"""
from .admission import (ACCEPTING, DEGRADED, SHEDDING, AdmissionPolicy,
                        AdmissionShed, AdmissionSignals, Decision,
                        DecodeAdmissionPolicy, SignalAdmissionPolicy,
                        derive_knobs)
from .batcher import (BatcherClosed, ContinuousBatcher, DynamicBatcher,
                      QueueFull, WorkItem, pad_rows, pick_bucket)
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .pool import (ExecutorPool, WarmExecutableCache, default_contexts,
                   prewarm, warm_cache)
from .server import (DEFAULT_BUCKETS, ReplicaCrash, ServingHTTPServer,
                     ServingSession, serve)
from .decode import (DecodeResult, DecodeSession, DecodeWorkerCrash,
                     PagedArena, SequenceSlotArena, TokenStream,
                     serve_decode)

__all__ = [
    "ACCEPTING", "DEGRADED", "SHEDDING", "AdmissionPolicy", "AdmissionShed",
    "AdmissionSignals", "Decision", "DecodeAdmissionPolicy",
    "SignalAdmissionPolicy", "derive_knobs",
    "BatcherClosed", "ContinuousBatcher", "DynamicBatcher", "QueueFull",
    "WorkItem", "pad_rows", "pick_bucket",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "ExecutorPool", "WarmExecutableCache", "default_contexts", "prewarm",
    "warm_cache",
    "DEFAULT_BUCKETS", "ReplicaCrash", "ServingHTTPServer",
    "ServingSession", "serve",
    "DecodeSession", "DecodeResult", "DecodeWorkerCrash",
    "PagedArena", "SequenceSlotArena", "TokenStream", "serve_decode",
]
