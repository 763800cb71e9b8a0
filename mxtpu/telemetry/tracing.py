"""Correlated tracing: span IDs flowing across threads and subsystems.

Every ``span()`` gets a process-unique ``span_id``, inherits the ambient
span as ``parent_id`` (contextvar — survives generators and nested
calls), and carries the root's ``trace_id``. Cross-thread hops — engine
``push`` -> native worker dispatch, serving ``submit`` -> dispatcher
batch — capture the submitting span with ``current_span()`` and restore
it on the far side with ``parent=``, so one trace id threads engine push
-> executor run -> kvstore push/pull -> serving request.

Every span is also an event of the JAX profiler's trace: it enters a
``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` when given a
``step_num``), so whoever records a trace — ``mx.profiler``, the
benchmark, an operator's ``jax.profiler.start_trace`` — finds the
program's host phases on the device's clock, on the line of the thread
that ran them. With no trace being recorded the annotation costs about
a microsecond.

Spans are emitted on exit into every armed sink:
  * into ``mxtpu.profiler`` as a chrome://tracing event whose ``args``
    carry trace/span/parent ids (only while the profiler runs);
  * into the telemetry registry as an observation on the labeled
    histogram ``span_ms{span=<name>}`` (always, unless telemetry is
    disabled) — the substrate for the profiler's aggregate_stats tables
    and for Prometheus latency series without a profiler session;
  * into the ``mxtpu.obs`` span ring via ``set_span_sink`` (when armed)
    — the bounded capture the Perfetto timeline exporter reads.
"""
from __future__ import annotations

import contextvars
import itertools
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["Span", "span", "current_span", "trace_id"]

_ids = itertools.count(1)  # itertools.count.__next__ is atomic (CPython)
_current = contextvars.ContextVar("mxtpu_telemetry_span", default=None)

# flight-recorder hook (mxtpu.diagnostics.flight): every span start/end
# also lands in the lock-free event ring, so a postmortem shows what the
# process was doing just before a wedge. One global read per span when
# unset; set_flight_recorder is called by the diagnostics package.
_flight = None

# span-sink hook (mxtpu.obs.trace): every FINISHED span — with its
# wall-clock endpoints (integer nanoseconds) and correlation ids — lands in the bounded span
# ring the timeline exporter reads. Same one-global-read-when-unset
# contract as the flight hook; set_span_sink is called by mxtpu.obs.
_sink = None


def set_flight_recorder(rec):
    global _flight
    _flight = rec


def set_span_sink(fn):
    """Install ``fn(span)`` to receive every finished span (None
    unhooks). The callee must be lock-free and allocation-light — it
    runs inside ``Span.__exit__`` on every instrumented region."""
    global _sink
    _sink = fn


class Span:
    """One timed region. Use via the ``span()`` context manager.

    ``t0_ns``/``t1_ns`` are wall-clock nanoseconds (``time.time_ns`` at
    entry; the end is the start plus the monotonic duration, so an NTP
    step cannot produce a negative latency). Each clock is read once an
    end."""

    __slots__ = ("name", "category", "span_id", "parent_id", "trace_id",
                 "tags", "t0_ns", "t1_ns", "_token", "_p0_ns", "_ann")

    def __init__(self, name, category="default", parent=None, tags=None,
                 step_num=None):
        self.name = name
        self.category = category
        self.span_id = next(_ids)
        if parent is not None:
            self.parent_id = parent.span_id
            self.trace_id = parent.trace_id
        else:
            self.parent_id = 0
            self.trace_id = self.span_id
        self.tags = tags or {}
        self.t0_ns = self.t1_ns = 0
        self._token = None
        # the ids ride as the annotation's arguments (shown on click in
        # Perfetto/xprof); they are only formatted while a trace records
        args = dict(self.tags, category=category, span_id=self.span_id,
                    parent_id=self.parent_id, trace_id=self.trace_id)
        self._ann = TraceAnnotation(name, **args) if step_num is None \
            else StepTraceAnnotation(name, step_num=step_num, **args)

    @property
    def duration_ms(self):
        return (self.t1_ns - self.t0_ns) / 1e6

    def __enter__(self):
        self._token = _current.set(self)
        f = _flight
        if f is not None:
            f.record("span_start", self.name, self.span_id)
        self._ann.__enter__()
        self.t0_ns = time.time_ns()
        self._p0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.t1_ns = self.t0_ns + (time.perf_counter_ns() - self._p0_ns)
        self._ann.__exit__(*exc)
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        f = _flight
        if f is not None:
            # (span id, ns): the ring's reader formats it, not the span
            f.record("span_end", self.name,
                     (self.span_id, self.t1_ns - self.t0_ns))
        k = _sink
        if k is not None:
            k(self)
        self._emit()
        return False

    def _emit(self):
        from . import _emit_span  # late: avoids import cycle at module load
        _emit_span(self)

    def __repr__(self):
        return "Span(%s id=%d parent=%d trace=%d)" % (
            self.name, self.span_id, self.parent_id, self.trace_id)


class _NullSpan:
    """No-op stand-in returned while telemetry is disabled."""

    span_id = parent_id = trace_id = 0
    duration_ms = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


def span(name, category="default", parent=None, tags=None, step_num=None,
         always=False):
    """Open a correlated span. ``parent`` overrides the ambient span —
    pass a captured ``current_span()`` when crossing a thread boundary;
    by default the span nests under whatever is ambient on THIS thread.
    ``step_num`` marks a training step for xprof's step view
    (``StepTraceAnnotation``).

    Returns a no-op span only when BOTH sinks are off: telemetry disabled
    AND no profiler session running — an explicitly started profiler
    keeps receiving trace spans under ``MXTPU_TELEMETRY=0``. ``always``
    is for a caller whose own behaviour reads the span's duration (the
    decode loop's admission model reads ``decode_step_ms``): the span is
    the region's one timing, so it cannot be the no-op."""
    from . import enabled, _profiler_running
    if not always and not enabled() and not _profiler_running():
        return _NULL
    if parent is None:
        parent = _current.get()
    return Span(name, category=category, parent=parent, tags=tags,
                step_num=step_num)


def current_span():
    """The ambient span on this thread/context (None outside any span).
    Capture it before handing work to another thread, then pass it as
    ``span(..., parent=captured)`` on the far side."""
    return _current.get()


def trace_id():
    """Trace id of the ambient span, 0 when outside any span."""
    s = _current.get()
    return s.trace_id if s is not None else 0
