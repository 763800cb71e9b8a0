"""mxtpu.diagnostics — memory accounting, cost introspection, flight
recorder, hang watchdog.

PR 2's telemetry answers "how fast"; this package answers the two
questions an operator asks when a TPU session misbehaves: **where did
the HBM go** and **why is nothing moving**.

  * ``ledger``   — process-wide device-byte accounting per (ctx, origin)
                   with a ``jax.live_arrays()`` drift check
                   (``mem_live_bytes{ctx,origin}`` / ``mem_peak_bytes``)
  * ``programs`` — per-program ``cost_analysis``/``memory_analysis``
                   captured at the executor build seam
                   (``diagnostics.program_table()``)
  * ``flight``   — lock-free ring of recent events (spans, engine
                   pushes) readable from a signal handler
  * ``watchdog`` — no-progress detection over the engine queue and
                   ``device_wait``; emits a structured postmortem

Postmortems fire on watchdog detection, on ``SIGUSR2``, on fatal
exceptions escaping ``Module.fit`` or a serving dispatch, and on demand
(``GET /debug/state`` on the serving server, or ``dump_state()`` here).
See docs/diagnostics.md.
"""
from __future__ import annotations

import json as _json
import logging as _logging
import os as _os
import threading as _threading
import time as _time

from .. import telemetry as _tel
from ..analysis import concurrency as _conc
from . import ledger as ledger_mod  # module alias BEFORE the function
# import below shadows the package attribute 'ledger' — hot call sites
# that need the module's flag/globals use ledger_mod
from .ledger import (DeviceMemoryLedger, alloc_origin, current_origin,
                     device_label, ledger, mem_enabled, set_mem_enabled)
from . import opscopes
from .programs import (ProgramRecord, cost_enabled, keep_scopes,
                       latest_record, owner_name, program_table, programs, record_program,
                       set_cost_enabled, summarize_precision,
                       summarize_shardings)
from .flight import (FlightRecorder, flight_enabled, record, recorder,
                     set_flight_enabled)
# importing mxtpu.obs.trace ARMS the span ring (tracing.set_span_sink)
# alongside the flight hook above — every process that diagnoses also
# captures an exportable timeline (MXTPU_TRACE=0 opts out)
from ..obs import trace as _obs_trace
from .watchdog import (Watchdog, active_waits, add_action, ensure_watchdog,
                       fire_actions, progress_age_s, remove_action,
                       stop_watchdog, wait_begin, wait_end)

__all__ = [
    "DeviceMemoryLedger", "ledger", "alloc_origin", "current_origin",
    "device_label", "mem_enabled", "set_mem_enabled", "reconcile",
    "ProgramRecord", "programs", "program_table", "record_program",
    "latest_record", "keep_scopes", "opscopes", "cost_enabled",
    "set_cost_enabled",
    "summarize_shardings", "summarize_precision",
    "FlightRecorder", "recorder", "record", "flight_enabled",
    "set_flight_enabled",
    "Watchdog", "ensure_watchdog", "stop_watchdog", "active_waits",
    "wait_begin", "wait_end", "add_action", "remove_action",
    "fire_actions", "progress_age_s",
    "debug_state", "postmortem", "last_postmortem", "dump_state",
    "install_signal_handler", "set_enabled",
]

_log = _logging.getLogger("mxtpu.diagnostics")

_LAST_POSTMORTEM = None
_LAST_DUMP_T = 0.0
_LAST_CAPTURE_T = 0.0   # separate clock: throttles full state CAPTURE
                        # for per-event sources, not just file writes
_DUMP_MIN_INTERVAL_S = float(_os.environ.get("MXTPU_DIAG_DUMP_MIN_S", "5"))
_CAPTURE_THROTTLED_SOURCES = ("serving",)
_PM_LOCK = _conc.lock("diagnostics", "_PM_LOCK")


def set_enabled(flag):
    """Master runtime toggle for the per-event costs (ledger seams +
    flight ring). Cost capture is a build-time event and keeps its own
    flag; the watchdog keeps running — it is the point of the package."""
    set_mem_enabled(flag)
    set_flight_enabled(flag)
    _obs_trace.set_trace_enabled(flag)


def reconcile():
    """Ledger vs ``jax.live_arrays()`` drift check (see ledger.py)."""
    return ledger().reconcile()


def _engine_state():
    """Engine snapshot WITHOUT instantiating an engine (a debug read must
    not decide which engine the process runs)."""
    from .. import engine as _engine
    e = _engine._ENGINE
    reg = _tel.registry()
    state = {
        "type": type(e).__name__ if e is not None else None,
        "queue_depth": _engine._singleton_queue_depth(),
        "workers": _engine._singleton_workers(),
        "ops_dispatched": _engine._M_DISPATCHED.value,
        "ops_completed": _engine._M_COMPLETED.value,
        "queue_wait_ms_p99": round(
            reg.histogram("engine_queue_wait_ms").percentile(99), 4),
    }
    return state


def debug_state(flight_limit=256):
    """The live-session debug snapshot: buffer ledger, program table,
    flight-recorder ring, engine state, active device waits. JSON-ready —
    this is the body of the serving ``GET /debug/state`` endpoint and of
    every postmortem."""
    rec = recorder()
    state = {
        "time": round(_time.time(), 3),
        "pid": _os.getpid(),
        "ledger": ledger().snapshot(),
        "programs": programs(),
        "flight": rec.snapshot(limit=flight_limit) if rec is not None else [],
        "engine": _engine_state(),
        "waits": active_waits(),
        # armed flag + observed lock graph summary (armed witness only)
        "concurrency": _conc.state(),
        # span-ring fill level: how much timeline GET /debug/trace holds
        "trace": {
            "enabled": _obs_trace.trace_enabled(),
            "spans": len(_obs_trace.ring())
                     if _obs_trace.ring() is not None else 0,
            "capacity": _obs_trace.ring().capacity
                        if _obs_trace.ring() is not None else 0,
        },
    }
    try:
        state["reconcile"] = reconcile()
    except Exception:
        pass  # jax not importable / backend not initialized: skip the check
    try:
        # lazy: obs.health imports diagnostics — the panel accessor is
        # reached only at snapshot time, never at import time
        from ..obs import health as _health
        hp = _health.panel()
        if hp is not None:
            state["training_health"] = hp
    except Exception:
        pass  # a debug read must never fail because a panel source did
    return state


def postmortem(reason, exc=None, source="manual", path=None):
    """Build a structured postmortem (debug_state + reason), remember it,
    log it, and — when ``path`` is given or ``MXTPU_DIAG_DUMP_DIR`` is
    set — write it as JSON (rate-limited to one file per
    ``MXTPU_DIAG_DUMP_MIN_S``). Returns the dump dict."""
    global _LAST_POSTMORTEM, _LAST_DUMP_T, _LAST_CAPTURE_T
    dump = {"reason": str(reason), "source": source}
    if exc is not None:
        dump["exception"] = "%s: %s" % (type(exc).__name__, exc)
    # per-EVENT sources (a failing serving batch) can storm: the full
    # debug_state walk (ledger snapshot + live_arrays reconcile) is
    # itself rate-limited for them. Operator-driven and one-per-wedge
    # sources always capture.
    capture = True
    if source in _CAPTURE_THROTTLED_SOURCES:
        with _PM_LOCK:
            now = _time.monotonic()
            if now - _LAST_CAPTURE_T < _DUMP_MIN_INTERVAL_S:
                capture = False
            else:
                _LAST_CAPTURE_T = now
    if capture:
        try:
            dump.update(debug_state())
        except Exception as state_exc:  # never let the dump kill the dumper
            dump["state_error"] = repr(state_exc)
    else:
        dump["throttled"] = True
    out_dir = path or _os.environ.get("MXTPU_DIAG_DUMP_DIR")
    with _PM_LOCK:
        _LAST_POSTMORTEM = dump
        _tel.registry().counter(
            "diag_postmortems", labels={"source": source},
            help="structured postmortem dumps emitted").inc()
        # rate-limit FILE writes only (in-memory dumps always land): the
        # clock must not advance for memory-only postmortems or they
        # would throttle a later on-demand SIGUSR2 dump
        throttled = False
        if out_dir:
            now = _time.monotonic()
            throttled = now - _LAST_DUMP_T < _DUMP_MIN_INTERVAL_S
            if not throttled:
                _LAST_DUMP_T = now
    _log.error("mxtpu postmortem (%s): %s | live=%dB queue=%d programs=%d "
               "flight=%d", source, reason,
               dump.get("ledger", {}).get("live_bytes_total", 0),
               dump.get("engine", {}).get("queue_depth", 0),
               len(dump.get("programs", ())), len(dump.get("flight", ())))
    if out_dir and not throttled:
        try:
            if _os.path.isdir(out_dir):
                fname = _os.path.join(
                    out_dir, "mxtpu_postmortem_%d_%d.json"
                    % (_os.getpid(), int(_time.time() * 1e3)))
            else:
                fname = out_dir
            with open(fname, "w") as f:
                _json.dump(dump, f, indent=2, default=str)
            dump["dump_path"] = fname
            _log.error("postmortem written to %s", fname)
        except Exception as io_exc:
            _log.error("postmortem write failed: %r", io_exc)
    return dump


def last_postmortem():
    """The most recent postmortem dict (None if none fired)."""
    return _LAST_POSTMORTEM


def dump_state(path, fmt="json"):
    """Write ``debug_state()`` to ``path`` on demand (no wedge needed)."""
    state = debug_state()
    with open(path, "w") as f:
        if fmt == "json":
            _json.dump(state, f, indent=2, default=str)
        else:
            raise ValueError("dump_state: fmt must be 'json'")
    return path


_SIGNAL_INSTALLED = False


def install_signal_handler(signum=None):
    """Install the ``SIGUSR2`` -> postmortem handler (main thread only —
    returns False elsewhere, or where signals are unavailable). Called
    automatically by ``ensure_watchdog`` users (Module.fit, serving).
    Declines (returns False) when the signal already has a non-default
    disposition — a user's own USR2 handler (py-spy-style stack dumper)
    or an explicit SIG_IGN must win over our convenience install; call
    with an explicit ``signum`` to claim a different signal instead.
    ``MXTPU_DIAG_SIGNAL=0`` opts out entirely."""
    global _SIGNAL_INSTALLED
    if _SIGNAL_INSTALLED:
        return True
    if _os.environ.get("MXTPU_DIAG_SIGNAL", "1") == "0":
        return False
    try:
        import signal

        signum = signum if signum is not None else signal.SIGUSR2
        if signal.getsignal(signum) is not signal.SIG_DFL:
            return False

        def _handler(sig, frame):
            # NEVER dump inline: the handler interrupts the main thread
            # between bytecodes, which may be inside the (non-reentrant)
            # ledger lock, _PM_LOCK, or a logging handler lock — an
            # inline debug_state() would self-deadlock. Hand off.
            _threading.Thread(
                target=postmortem, args=("signal %d" % sig,),
                kwargs={"source": "signal"}, daemon=True,
                name="mxtpu-diag-sigdump").start()

        signal.signal(signum, _handler)
        _SIGNAL_INSTALLED = True
        return True
    except (ValueError, AttributeError, OSError):
        return False  # non-main thread, or platform without SIGUSR2


def on_session_start():
    """One call wired into Module.fit and ServingSession: arm the
    watchdog and the signal handler for this process."""
    install_signal_handler()
    return ensure_watchdog()
