"""The program's own spans (`mxtpu.telemetry.span`, kept in the ring of
`mxtpu.obs.trace`) brought onto the clock of the profiler's trace, and the
device's idle time cut by them.

`trace.Trace.from_file` keeps only the host's `bench.*` annotations, so the
program's annotations are not read out of the trace; the ring's records are,
on the wall clock. `ProfileData`'s clock counts from the session's start, so
no clock Python can read is the trace's: `offset` = wall - trace is bounded
by nestings that hold in every fit cell. The benchmark's `bench.fit`
annotation encloses the program's outer `fit` span of the same call, and each
`fit.input` span encloses one `bench.input_next`. Every pair gives `offset` a
lower and an upper limit, and the middle between them is taken. The two
clocks may part by microseconds over a window (`DRIFTS`), which is as much
as a pair leaves, so a limit counts for less the further off it was taken.

A program without these spans (the parent of the PR that brought them) gives
`load` nothing to read: it returns None and every reader built on it does.
"""
import sys

from benchmark import trace

#: fit's phases: the host is in at most one of them at a time
PHASES = ("fit.input", "fit.step", "fit.pace", "fit.metric_sync",
          "fit.callbacks", "fit.eval")
MAX_WIDTH_NS = 200_000
#: how fast the wall clock may run against the trace's, tried in this order.
#: Both follow the kernel's clock, the profiler's by the cycle counter
#: between samples some seconds apart, so they part by microseconds while
#: the kernel's clock is being disciplined; it is slewed by 500 ppm at most.
DRIFTS = (0.0, 5e-6, 50e-6, 500e-6)


class Offset:
    """wall - trace as the nestings bound it. A ring span (w0, w1) on the
    wall clock that encloses a trace span (s, e) gives a lower limit w0 - s
    at w0 and an upper one w1 - e at w1; a ring span inside a trace span
    gives the upper limit w0 - s at w0 and the lower w1 - e at w1. A limit
    taken at t holds at t' less `drift` * |t' - t|. Limits are kept less
    `base` (a float has not the digits for ns since 1970)."""

    def __init__(self, enclosed_by_trace, enclosing_trace, drift=0.0):
        self.drift = drift
        lowers, uppers = [], []
        for (w0, w1), (s, e) in enclosed_by_trace:
            uppers.append((w0, w0 - s))
            lowers.append((w1, w1 - e))
        for (w0, w1), (s, e) in enclosing_trace:
            lowers.append((w0, w0 - s))
            uppers.append((w1, w1 - e))
        self.base = lowers[0][1]
        self.lowers = [(t, v - self.base) for t, v in lowers]
        self.uppers = [(t, v - self.base) for t, v in uppers]

    def bounds(self, t):
        """(lo, hi) of wall - trace - base at the wall's instant t."""
        d = self.drift
        return (max(v - d * abs(t - at) for at, v in self.lowers),
                min(v + d * abs(t - at) for at, v in self.uppers))

    def widths(self):
        """hi - lo at every instant a limit was taken at; where limits
        cross, they do so at one of these."""
        return [hi - lo for lo, hi in
                (self.bounds(t) for t, _ in self.lowers + self.uppers)]

    def to_trace(self, t):
        lo, hi = self.bounds(t)
        return t - self.base - int((lo + hi) // 2)


def offset(enclosed_by_trace, enclosing_trace):
    """The `Offset` under the smallest drift of `DRIFTS` at which no two
    limits cross; under the largest if they cross at each."""
    for drift in DRIFTS:
        off = Offset(enclosed_by_trace, enclosing_trace, drift)
        if min(off.widths()) >= 0:
            break
    return off


def overlap(a, b):
    """Parts of the merged intervals `a` that the merged `b` covers."""
    return trace.subtract(a, trace.subtract(a, b))


class Spans:
    """The spans of the thread that ran `fit`, on the trace's clock and
    clipped to the window, with the first chip's idle intervals."""

    def __init__(self, rows, window, idle, width_ns, drift, fit_cover):
        self.rows = rows            # [(name, start, end, span_id, parent_id)]
        self.window = window
        self.idle = idle            # merged, inside the window; None: no chip
        self.width_ns = width_ns    # of the offset's interval, at its widest
        self.drift = drift          # the one of `DRIFTS` it was taken under
        self.fit_cover = fit_cover  # the program's `fit` over `bench.fit`

    def window_ns(self):
        return self.window[1] - self.window[0]

    def named(self, *names):
        """Merged intervals of the spans of these names."""
        return trace.union((s, e) for n, s, e, _, _ in self.rows
                           if n in names)

    def covered_ns(self, *names):
        return trace.total(self.named(*names))

    def self_ns(self, name):
        """Time inside the spans of `name` that none of their children
        covers."""
        ids = {i for n, _, _, i, _ in self.rows if n == name}
        kids = trace.union((s, e) for _, s, e, _, p in self.rows if p in ids)
        return trace.total(trace.subtract(self.named(name), kids))

    def idle_ns(self):
        return None if self.idle is None else trace.total(self.idle)

    def idle_in_ns(self, *names):
        """Chip idle while the host is in a span of these names."""
        if self.idle is None:
            return None
        return trace.total(overlap(self.idle, self.named(*names)))

    def idle_unnamed_ns(self):
        """Chip idle while the host is in none of fit's phases."""
        if self.idle is None:
            return None
        return trace.total(trace.subtract(self.idle, self.named(*PHASES)))

    def share(self, ns):
        """Percent of the window."""
        return None if ns is None else 100.0 * ns / self.window_ns()


def _ring_rows():
    try:
        from mxtpu.obs import trace as obs_trace
    except ImportError:
        return None, 0
    ring = obs_trace.ring()
    if ring is None:
        return None, 0
    return ring.snapshot(), ring.capacity


def build(tr, rows, capacity=0):
    """`Spans` from a `trace.Trace` and the ring's records, or None where
    the program has no `fit` / `fit.input` spans. Raises ValueError, naming
    the numbers, when the nestings' limits cross under every drift of
    `DRIFTS` or lie more than 200 us apart, or when the ring no longer
    reaches back to the window."""
    if tr is None or not rows or "t0_ns" not in rows[0]:
        return None
    fits = [r for r in rows if r["name"] == "fit"]
    bench_fits = [s for s in tr.spans if s[0] == "bench.fit"]
    if not fits or not bench_fits:
        return None
    fit, bf = fits[-1], bench_fits[-1]
    mine = [r for r in rows if r["thread"] == fit["thread"]
            and r["t0_ns"] >= fit["t0_ns"] and r["t1_ns"] <= fit["t1_ns"]]
    inputs = sorted((r["t0_ns"], r["t1_ns"]) for r in mine
                    if r["name"] == "fit.input")
    if not inputs:
        return None
    inside = [((fit["t0_ns"], fit["t1_ns"]), (bf[1], bf[2]))]
    # the k-th `fit.input` of the call holds its k-th `bench.input_next`
    # (one `next()` each, the last one's StopIteration included)
    nexts = sorted((s, e) for name, s, e in tr.spans
                   if name == "bench.input_next" and s >= bf[1] and e <= bf[2])
    around = list(zip(inputs, nexts)) if len(inputs) == len(nexts) else []
    off = offset(inside, around)
    widths = off.widths()
    if min(widths) < 0 or max(widths) > MAX_WIDTH_NS:
        raise ValueError(
            "spans: `fit` %d ns in `bench.fit` %d ns, %d `fit.input` spans, "
            "%d `bench.input_next`; with the clocks parting by %g ppm at "
            "most, %d nestings bound wall - trace %s" % (
                fit["t1_ns"] - fit["t0_ns"], bf[2] - bf[1], len(inputs),
                len(nexts), off.drift * 1e6, len(around) + 1,
                "to nothing: limits cross by %d ns" % round(-min(widths))
                if min(widths) < 0 else
                "to %d ns, over %d" % (round(max(widths)), MAX_WIDTH_NS)))
    w_lo, w_hi = tr.window
    if capacity and len(rows) >= capacity and \
            off.to_trace(rows[0]["t0_ns"]) > w_lo:
        raise ValueError(
            "spans: the ring's %d slots reach back to %d ns, the window "
            "opens at %d ns" % (capacity, off.to_trace(rows[0]["t0_ns"]),
                                w_lo))
    out = []
    for r in mine:
        s, e = off.to_trace(r["t0_ns"]), off.to_trace(r["t1_ns"])
        if e > w_lo and s < w_hi:
            out.append((r["name"], max(s, w_lo), min(e, w_hi),
                        r["span_id"], r["parent_id"]))
    idle = None
    if tr.devices:      # the first chip's, as `Trace.idle_gaps` takes them
        busy = tr._busy(tr.devices[sorted(tr.devices)[0]])
        idle = trace.subtract([(w_lo, w_hi)], busy)
    return Spans(out, (w_lo, w_hi), idle, round(max(widths)), off.drift,
                 (fit["t1_ns"] - fit["t0_ns"]) / max(1, bf[2] - bf[1]))


def load(facts):
    """The run's `Spans` (built once a run), or None."""
    if "program_spans" not in facts:
        rows, capacity = _ring_rows()
        sp = build(facts.get("trace"), rows, capacity)
        facts["program_spans"] = sp
        if sp is not None:
            sys.stderr.write("spans %s\n" % summary(sp))
    return facts["program_spans"]


def summary(sp):
    """One line for the run's log: the offset's width, how much of
    `bench.fit` the program's `fit` covers, and the window and the chip's
    idle time by phase, in ns."""
    parts = ["offset_width_ns=%d" % sp.width_ns,
             "offset_drift_ppm=%g" % (sp.drift * 1e6),
             "fit_over_bench_fit=%.6f" % sp.fit_cover,
             "window_ns=%d" % sp.window_ns(),
             "fit_ns=%d" % sp.covered_ns("fit"),
             "fit.epoch_ns=%d" % sp.covered_ns("fit.epoch"),
             "fit.epoch_self_ns=%d" % sp.self_ns("fit.epoch")]
    parts += ["%s_ns=%d" % (p, sp.covered_ns(p)) for p in PHASES]
    if sp.idle is not None:
        parts.append("idle_ns=%d" % sp.idle_ns())
        parts += ["idle_in_%s_ns=%d" % (p, sp.idle_in_ns(p)) for p in PHASES]
        parts.append("idle_unnamed_ns=%d" % sp.idle_unnamed_ns())
    return " ".join(parts)
