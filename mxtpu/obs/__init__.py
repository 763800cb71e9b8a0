"""mxtpu.obs — the exported observability surface.

PR 2 (telemetry) and PR 4 (diagnostics) made the process legible
*in-process*: correlated spans, series, the flight ring, the program
cost registry. This package is the export layer on top of them, in
three coupled pieces:

  * :mod:`~mxtpu.obs.trace` + :mod:`~mxtpu.obs.trace_export` — a
    bounded lock-free ring of finished spans (armed as
    ``tracing.set_span_sink``) and a Chrome trace-event / Perfetto
    exporter merging it with the diagnostics flight ring onto named
    per-thread tracks with flow events. Served at ``GET /debug/trace``;
    fetched by ``mxtpu_top --trace-out``.
  * :mod:`~mxtpu.obs.sampler` — the seeded deterministic per-request
    exemplar sampler (``MXTPU_TRACE_SAMPLE``) the decode session uses,
    so gates assert *exactly which* requests carry traces.
  * :mod:`~mxtpu.obs.corpus` — the append-only JSONL measurement
    corpus (``MXTPU_CORPUS_DIR``): program-build features + measured
    service ms, crash-safe, with a ``load()/summarize()`` reader.
  * :mod:`~mxtpu.obs.health` + :mod:`~mxtpu.obs.detectors` —
    device-resident per-layer training-health statistics over the
    fused train step, riding the metric-sync cadence, with a
    deterministic anomaly-detector suite and the divergence
    auto-rollback policy (``MXTPU_HEALTH`` / ``fit(health=True)``).

See docs/observability.md (trace contract, span inventory, training
health, the measurement corpus).
"""
from __future__ import annotations

from . import corpus, sampler, trace, trace_export
from .sampler import TraceSampler
from .trace import SpanRing, install, ring, set_trace_enabled, trace_enabled

__all__ = [
    "trace", "trace_export", "sampler", "corpus", "health", "detectors",
    "SpanRing", "ring", "install", "set_trace_enabled", "trace_enabled",
    "TraceSampler",
]


def __getattr__(name):
    # health/detectors resolve lazily: diagnostics imports obs.trace at
    # import time, and obs.health imports diagnostics — an eager import
    # here would close the cycle during ``import mxtpu.diagnostics``
    if name in ("health", "detectors"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))
