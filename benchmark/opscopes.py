"""The fused step's device time by the program's own graph nodes: the
trace's operations, each instant counted once, joined with the scope table
the program keeps with its compiled step (`mxtpu.diagnostics`'
`ProgramRecord.op_scopes()`: HLO instruction -> node, operator, model block,
phase), so that a block's share follows the block whatever XLA calls its
operations.

What is read: the first chip's `XLA Ops` events that lie inside whole runs
of the fused step (`XLA Modules` events named `jit_mxtpu_fused_step(...)`)
inside the traced window. Time is exclusive: a `while` event encloses its
body's operations and a conditional its branch's, and every instant goes to
the innermost operation running (the one that started last), so the
operations' times add to the device's busy time inside those runs and every
share below is of that sum.

The table is read in process after the Module is gone: the record holds it
on the host. A program without it (the parent of the PR that brought it)
gives `load` nothing to read: it returns None and every reader built on it
does. So does a run without a device plane (a CPU rehearsal).
"""
import bisect
import re
import sys

from benchmark import trace

STEP = "jit_mxtpu_fused_step"
_STEP_RUN = re.compile(r"^%s\(" % STEP)
UNSCOPED = ("", "", "", "unscoped", False)


def exclusive(events):
    """{name: ns} of (name, start, end) events with every instant counted
    once, to the event that started last among those running. The values
    add to the length of the events' union."""
    acc, stack, now = {}, [], 0
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    for name, start, end in events + [("", float("inf"), float("inf"))]:
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


def step_ops(tr):
    """({HLO text: exclusive ns}, runs, ns of the runs) of the first
    chip's operations inside whole runs of the fused step inside the
    window; None where the trace has no such run."""
    if tr is None or not tr.devices:
        return None
    dev = tr.devices[sorted(tr.devices)[0]]
    lo, hi = tr.window
    runs = [(s, e) for name, s, e in dev["modules"]
            if _STEP_RUN.match(name) and s >= lo and e <= hi]
    if not runs:
        return None
    ops = sorted(dev["ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    acc = {}
    for s, e in runs:
        inside = [ev for ev in ops[bisect.bisect_left(starts, s):
                                   bisect.bisect_right(starts, e)]
                  if ev[2] <= e]
        for name, ns in exclusive(inside).items():
            acc[name] = acc.get(name, 0) + ns
    return acc, len(runs), sum(e - s for s, e in runs)


def scope_table():
    """The newest fused step's `{instruction: (node, operator, block, phase,
    mixed)}`, or None where the program keeps none."""
    try:
        from mxtpu import diagnostics as diag
    except ImportError:
        return None
    if not hasattr(diag.ProgramRecord, "op_scopes"):
        return None
    rec = diag.latest_record(name=STEP)
    return rec.op_scopes() if rec is not None else None


class Scoped:
    """The step's operations with their scopes: `rows` holds (instruction,
    ns, (node, operator, block, phase, mixed), is a custom call)."""

    def __init__(self, rows, runs, runs_ns):
        self.rows, self.runs, self.runs_ns = rows, runs, runs_ns
        self.total_ns = sum(r[1] for r in rows)

    def share(self, keep):
        """Percent of the step's operations' time in the scopes that
        `keep(node, operator, block, phase)` takes."""
        return 100.0 * sum(ns for _, ns, sc, _ in self.rows
                           if keep(*sc[:4])) / self.total_ns


def build(tr, table):
    """`Scoped` of a `trace.Trace` and a scope table (or what returns one:
    asked only where the trace has runs of the step), or None."""
    got = step_ops(tr)
    if got is None:
        return None
    if callable(table):
        table = table()
    if table is None:
        return None
    acc, runs, runs_ns = got
    rows = []
    for text, ns in acc.items():
        name = trace.op_name(text)[0]
        rows.append((name, ns, tuple(table.get(name, UNSCOPED)),
                     " custom-call(" in text))
    return Scoped(rows, runs, runs_ns) if rows else None


def load(facts):
    """The run's `Scoped` (built once a run), or None."""
    if "op_scopes" not in facts:
        sc = build(facts.get("trace"), scope_table)
        facts["op_scopes"] = sc
        if sc is not None:
            sys.stderr.write("opscopes %s\n" % summary(sc))
    return facts["op_scopes"]


def _sums(pairs):
    acc = {}
    for key, ns in pairs:
        acc[key] = acc.get(key, 0) + ns
    return sorted(acc.items(), key=lambda kv: -kv[1])


def summary(sc):
    """One line for the run's log, times in ns over the window's runs: the
    shares by block (they add to 100 with `unscoped`), every (block,
    operator, phase), the share of time in fusions of more than one node,
    the ten largest unscoped operations, and the custom calls by scope and
    by the name of their kernel: what PRs 31, 33 and 35 listed by hand."""
    pct = 100.0 / sc.total_ns
    scoped = [r for r in sc.rows if r[2][3] != "unscoped"]
    parts = ["runs=%d" % sc.runs, "runs_ns=%d" % sc.runs_ns,
             "ops_ns=%d" % sc.total_ns,
             "mixed_share=%.4f" % (pct * sum(r[1] for r in scoped if r[2][4]))]
    parts.append("blocks " + " ".join(
        "%s=%.4f" % (block or "-", pct * ns) for block, ns in _sums(
            (r[2][2], r[1]) for r in scoped)))
    parts.append("unscoped=%.4f" % (pct * (sc.total_ns - sum(
        r[1] for r in scoped))))
    parts.append("rows " + " ".join(
        "%s/%s/%s=%d" % (b or "-", o or n, p, ns) for (b, o, n, p), ns in
        _sums(((r[2][2], r[2][1], "" if r[2][1] else r[2][0], r[2][3]), r[1])
              for r in scoped)))
    parts.append("unscoped_top " + " ".join("%s=%d" % (n, ns) for n, ns in sorted(
        ((r[0], r[1]) for r in sc.rows if r[2][3] == "unscoped"),
        key=lambda kv: -kv[1])[:10]))
    calls = [r for r in sc.rows if r[3]]
    parts.append("custom_calls_by_scope " + " ".join(
        "%s/%s=%d" % (b or "-", o or "-", ns) for (b, o), ns in _sums(
            ((r[2][2], r[2][1]), r[1]) for r in calls)))
    parts.append("custom_calls_by_name " + " ".join(
        "%s=%d" % (n, ns) for n, ns in _sums(
            (re.sub(r"[.0-9]+$", "", r[0]), r[1]) for r in calls)))
    return " ".join(parts)
