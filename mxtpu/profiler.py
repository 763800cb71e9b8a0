"""Profiler (parity: python/mxnet/profiler.py + src/engine/profiler.{h,cc}).

TPU-native three-tier design:
  1. every graph node is traced under ``jax.named_scope(layer_name)``
     (executor.py) and the rest of the fused step under scopes of its own
     (module/fused.py), so every instruction of a compiled step names the
     node it came from — the fused-program analogue of the engine's per-op
     OprExecStat stamps (src/engine/threaded_engine.h:314-325). Its reader:
     each step program's record keeps a table from instruction to node
     (``diagnostics.ProgramRecord.op_scopes``); on ``set_state('stop')``
     the trace of tier 3 is read back, every instant of device time goes
     to the innermost operation running, and ``dumps()`` /
     ``device_rows()`` give device time by node, forward, backward and
     update, for the programs that really ran;
  2. with the profiler running in an operator mode, the Executor switches to
     node-at-a-time execution with a device sync per node, recording true
     per-layer wall times as chrome://tracing spans (DumpProfile parity,
     profiler.cc:152 EmitPid/EmitEvent): the only per-operator timing where
     the trace has no device plane (the CPU);
  3. ``profiler_set_state('run')`` also starts a jax xplane trace
     (``xplane_dir()``, beside the configured filename) for xprof /
     Perfetto; every telemetry span is a ``TraceAnnotation`` in it, so
     the host's phases stand above the device's operations.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import threading
import time

import jax

from .analysis import concurrency as _conc

_state = {"mode": "symbolic", "filename": "profile.json", "running": False,
          "jax_trace": False, "aggregate_stats": False}
_events = []
_agg = {}  # name -> telemetry Histogram of span ms (aggregate_stats mode)
# tier 1's reading of the last trace: {program: {"runs", "chips", "ns":
# {instruction: exclusive ns over all runs and chips}}} (set_state('stop'))
_device = {}
_lock = _conc.lock("profiler", "_lock")

_OP_MODES = ("symbolic", "imperative", "operator", "all")


def profiler_set_config(mode="symbolic", filename="profile.json",
                        aggregate_stats=False, **kwargs):
    """Parity MXSetProfilerConfig(kwargs): mode 'symbolic'|'imperative'|
    'operator'|'api'|'all', output filename, optional aggregate stats.

    With ``aggregate_stats=True`` every span is ALSO folded, at record
    time, into a per-name fixed-bucket histogram (mxtpu.telemetry) —
    O(1) memory per layer, so ``dumps()`` keeps its per-layer table even
    after the raw event list is dumped or truncated (the reference's
    MXAggregateProfileStats contract)."""
    _state["mode"] = mode
    _state["filename"] = filename
    _state["aggregate_stats"] = bool(aggregate_stats)
    if _state["aggregate_stats"]:
        with _lock:
            _agg.clear()  # fresh aggregation session


def profiler_set_state(state="stop"):
    """Parity MXSetProfilerState: 'run' | 'stop'."""
    if state == "run":
        _state["running"] = True
        try:
            jax.profiler.start_trace(xplane_dir())
            _state["jax_trace"] = True
        except Exception:
            _state["jax_trace"] = False
    else:
        if _state.get("jax_trace"):
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            _state["jax_trace"] = False
            try:
                found = device_time(device_events(_newest_xplane()))
            except Exception:
                # mxtpu: allow-swallow(the table is a reading of the trace:
                # a trace that cannot be read back leaves none)
                found = {}
            with _lock:
                _device.clear()
                _device.update(found)
        _state["running"] = False


def xplane_dir():
    """Where ``set_state('run')`` writes the JAX profiler's trace: beside
    the configured ``filename``, ``<filename without extension>_xplane``.
    It holds the device's operations and, on the host threads' lines,
    every telemetry span (docs/observability.md, "One timeline")."""
    return os.path.splitext(_state["filename"])[0] + "_xplane"


def _newest_xplane():
    paths = sorted(glob.glob(os.path.join(
        xplane_dir(), "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(xplane_dir())
    return paths[-1]


def device_events(path):
    """``{plane: {"ops": [(name, start_ns, end_ns)], "modules": [...]}}`` of
    the device planes of a ``.xplane.pb``: a chip's ``XLA Ops`` line (one
    event an HLO operation, named by its HLO text) and ``XLA Modules``
    line (one event a run of a compiled program). A CPU trace has none."""
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        dev = {}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is not None:
                dev[key] = [(e.name, int(e.start_ns),
                             int(e.start_ns) + int(e.duration_ns))
                            for e in line.events]
        if len(dev) == 2:
            out[plane.name] = dev
    return out


def exclusive_ns(events):
    """{name: ns} with every instant counted once, to the innermost event
    running: the one that started last (a ``while`` event encloses its
    body's operations, a conditional its branch's). The sum is the length
    of the events' union."""
    acc, stack, now = {}, [], 0
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])) \
            + [("", float("inf"), float("inf"))]:
        while stack and now < start:
            top, top_end = stack[-1]
            if top_end > now:
                upto = min(top_end, start)
                acc[top] = acc.get(top, 0) + upto - now
                now = upto
            if top_end <= now:
                stack.pop()
        now = max(now, start) if stack else start
        stack.append((name, end))
    return acc


def device_time(devices):
    """{program: {"runs", "chips", "ns": {instruction: exclusive ns}}} over
    every chip's whole runs of each program: the operations between a run's
    start and end, named as ``ProgramRecord.op_scopes`` names them."""
    from .diagnostics import opscopes
    out = {}
    for dev in devices.values():
        ops = sorted(dev["ops"], key=lambda e: e[1])
        starts = [e[1] for e in ops]
        ran = set()
        for name, lo, hi in dev["modules"]:
            name = name.split("(")[0]
            ran.add(name)
            got = out.setdefault(name, {"runs": 0, "chips": 0, "ns": {}})
            got["runs"] += 1
            inside = [e for e in ops[bisect.bisect_left(starts, lo):
                                     bisect.bisect_right(starts, hi)]
                      if e[2] <= hi]
            for op, ns in exclusive_ns(inside).items():
                op = opscopes.instruction_name(op)
                got["ns"][op] = got["ns"].get(op, 0) + ns
        for name in ran:
            out[name]["chips"] += 1
    return out


def device_rows():
    """Device time by graph node, from the last trace that
    ``set_state('stop')`` read: one dict a node of every program that a
    ``ProgramRecord`` with a scope table names, ``{"program", "node",
    "operator", "block", "runs", "forward_ms", "backward_ms", "update_ms",
    "total_ms"}`` in ms a run, sorted by time within a program, then the
    program's ``unscoped`` row (operations whose metadata names no node).
    A program's rows add to its operations' time in the trace."""
    from . import diagnostics as _diag
    with _lock:
        found = dict(_device)
    rows = []
    for program in sorted(found, key=lambda p: -sum(found[p]["ns"].values())):
        rec = _diag.latest_record(name=program)
        table = rec.op_scopes() if rec is not None else None
        if table is None:
            continue
        got = found[program]
        per_run = 1e-6 / got["runs"]
        by_node, unscoped = {}, 0.0
        for op, ns in got["ns"].items():
            sc = table.get(op)
            if sc is None or sc.phase == "unscoped":
                unscoped += ns * per_run
                continue
            row = by_node.setdefault(sc.node, {
                "program": program, "node": sc.node, "operator": sc.operator,
                "block": sc.block, "runs": got["runs"] // got["chips"],
                "forward_ms": 0.0, "backward_ms": 0.0, "update_ms": 0.0})
            row[sc.phase + "_ms"] += ns * per_run
        for row in by_node.values():
            row["total_ms"] = row["forward_ms"] + row["backward_ms"] \
                + row["update_ms"]
        rows += sorted(by_node.values(), key=lambda r: -r["total_ms"])
        rows.append({"program": program, "node": "unscoped", "operator": "",
                     "block": "", "runs": got["runs"] // got["chips"],
                     "forward_ms": 0.0, "backward_ms": 0.0, "update_ms": 0.0,
                     "total_ms": unscoped})
    return rows


def _device_table():
    """The device table as text, or "" where the last trace held no run of
    a program with a scope table."""
    rows = device_rows()
    if not rows:
        return ""
    lines = ["Device time by graph node: the jax trace under %s, each "
             "instant to the innermost XLA operation running, joined with "
             "the program's scope table; ms a run" % xplane_dir()]
    program = None
    for r in rows:
        if r["program"] != program:
            program = r["program"]
            total = sum(x["total_ms"] for x in rows
                        if x["program"] == program)
            lines.append("%s: %d run(s), %.3f ms of operations a run"
                         % (program, r["runs"], total))
            lines.append("%-40s %-24s %-12s %6s %12s %12s %12s %12s" % (
                "Node", "Operator", "Block", "Runs", "Forward(ms)",
                "Backward(ms)", "Update(ms)", "Total(ms)"))
        lines.append("%-40s %-24s %-12s %6d %12.3f %12.3f %12.3f %12.3f" % (
            r["node"][:40], r["operator"][:24], r["block"][:12], r["runs"],
            r["forward_ms"], r["backward_ms"], r["update_ms"],
            r["total_ms"]))
    return "\n".join(lines)


# aliases matching python/mxnet/profiler.py's public names
set_config = profiler_set_config
set_state = profiler_set_state


def is_running():
    return _state["running"]


def ops_enabled():
    """True when executors should run node-at-a-time with per-op spans."""
    return _state["running"] and _state["mode"] in _OP_MODES


_tids = {}


def _thread_tid():
    """Small stable tid for the calling thread (chrome://tracing lanes).
    Multi-threaded callers (the serving dispatchers) get one lane each, so
    concurrent spans don't corrupt B/E pairing in ``dumps()``."""
    ident = threading.get_ident()
    with _lock:
        tid = _tids.get(ident)
        if tid is None:
            tid = _tids[ident] = len(_tids)
        return tid


def record_span(name, begin_us, end_us, category="operator", tid=None,
                args=None):
    """Record one op-level span (called by instrumented paths). ``tid``
    defaults to a per-thread lane. ``args`` (e.g. telemetry trace/span
    ids) ride on the B event — chrome://tracing shows them on click, so
    correlated spans can be followed across thread lanes."""
    if not _state["running"]:
        return
    if tid is None:
        tid = _thread_tid()
    begin = {"name": name, "cat": category, "ph": "B",
             "ts": begin_us, "pid": 0, "tid": tid}
    if args:
        begin["args"] = dict(args)
    with _lock:
        _events.append(begin)
        _events.append({"name": name, "cat": category, "ph": "E",
                        "ts": end_us, "pid": 0, "tid": tid})
        if _state["aggregate_stats"]:
            h = _agg.get(name)
            if h is None:
                from .telemetry.metrics import Histogram
                h = _agg[name] = Histogram(name)
            h.observe((end_us - begin_us) / 1e3)


class scope:
    """Context manager: time a region into the trace."""

    def __init__(self, name, category="operator"):
        self.name = name
        self.category = category

    def __enter__(self):
        self.t0 = time.time() * 1e6
        return self

    def __exit__(self, *a):
        record_span(self.name, self.t0, time.time() * 1e6, self.category)


def dump_profile(finished=True):
    """Parity MXDumpProfile: write chrome://tracing JSON."""
    with _lock:
        payload = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
    with open(_state["filename"], "w") as f:
        json.dump(payload, f)
    return _state["filename"]


dump = dump_profile


def dumps(reset=False):
    """The profiler's statistics as text: two tables, each under a line
    that says where its numbers come from.

    First, where the last trace held runs of a step program, device time
    by graph node (tier 1, ``device_rows()``): the program that really
    ran, forward, backward and update milliseconds a run.

    Then the aggregate per-op statistics of the operator spans (tier 2;
    parity MXAggregateProfileStatsToString: name, count, total/avg/min/max
    ms). With ``aggregate_stats`` configured, that table is served from the
    standing per-layer histograms — it survives ``dump_profile`` and event
    truncation, and gains P50/P90/P99 columns. Otherwise it is recomputed
    from the raw in-memory events."""
    device = _device_table()
    lines = ([device, ""] if device else []) + [
        "Host spans: the host's clock around each span (the program's "
        "phases; in an operator mode also every graph node, run one at a "
        "time with a device sync each, not the fused program)"]
    if _state["aggregate_stats"]:
        with _lock:
            hists = dict(_agg)
            if reset:
                _agg.clear()
                _events.clear()
                _device.clear()
        lines.append("%-40s %8s %12s %12s %12s %12s %12s %12s %12s" %
                     ("Name", "Count", "Total(ms)", "Avg(ms)", "Min(ms)",
                      "Max(ms)", "P50(ms)", "P90(ms)", "P99(ms)"))
        for name in sorted(hists, key=lambda n: -hists[n].sum):
            h = hists[name]
            lines.append(
                "%-40s %8d %12.3f %12.3f %12.3f %12.3f %12.3f %12.3f %12.3f"
                % (name[:40], h.count, h.sum, h.mean, h.min, h.max,
                   h.percentile(50), h.percentile(90), h.percentile(99)))
        return "\n".join(lines)
    stats = {}
    with _lock:
        spans = {}
        for ev in _events:
            key = (ev["name"], ev["tid"])
            if ev["ph"] == "B":
                spans[key] = ev["ts"]
            elif ev["ph"] == "E" and key in spans:
                dur = (ev["ts"] - spans.pop(key)) / 1e3  # ms
                s = stats.setdefault(ev["name"],
                                     [0, 0.0, float("inf"), 0.0])
                s[0] += 1
                s[1] += dur
                s[2] = min(s[2], dur)
                s[3] = max(s[3], dur)
        if reset:
            _events.clear()
            _device.clear()
    lines.append("%-40s %8s %12s %12s %12s %12s" %
                 ("Name", "Count", "Total(ms)", "Avg(ms)", "Min(ms)",
                  "Max(ms)"))
    for name in sorted(stats, key=lambda n: -stats[n][1]):
        c, tot, lo, hi = stats[name]
        lines.append("%-40s %8d %12.3f %12.3f %12.3f %12.3f" %
                     (name[:40], c, tot, tot / c, lo, hi))
    return "\n".join(lines)


def aggregate_stats_snapshot():
    """The standing per-layer histograms of aggregate_stats mode
    (name -> telemetry Histogram); empty dict when not configured."""
    with _lock:
        return dict(_agg)


def clear():
    with _lock:
        _events.clear()
        _agg.clear()
        _device.clear()
