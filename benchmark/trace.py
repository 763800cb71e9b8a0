"""Reduction of a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read: device busy time, idle gaps named by the benchmark's
own host spans, time per kernel and per module.

Layout of a TPU trace as jax 0.9 writes it (looked at by hand, PR 24): one
plane `/device:TPU:<n>` per chip with the lines `XLA Modules` (one event per
run of a compiled program, named `jit_<fn>(<hash>)`), `XLA Ops` (one event per
HLO operation, named by its HLO text `%name = type[shape] op(...)`) and
`Async XLA Ops` (not read); one plane `/host:CPU` whose thread lines hold the
`jax.profiler.TraceAnnotation` spans by name. All on one clock, nanoseconds.
"""
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OUTSIDE = "host_outside_benchmark_spans"
_SAFE = re.compile(r"[^A-Za-z0-9_.-]+")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def op_name(hlo_text):
    """`%fusion.61 = bf16[50272,2048]{...} fusion(...)` -> (`fusion.61`,
    `bf16[50272,2048]`, `fusion`). Names that are not HLO text come back as
    they are."""
    m = re.match(r"%?([^\s=]+) = (\(?[a-z0-9]+\[[^\]]*\])?[^ ]* ?([a-z-]+)?",
                 hlo_text)
    if not m:
        return hlo_text.lstrip("%"), "", ""
    return m.group(1), m.group(2) or "", m.group(3) or ""


def label(hlo_text):
    """Short name for the breakdown: op name and result shape, with only
    letters, digits, `_`, `.` and `-`."""
    name, shape, _ = op_name(hlo_text)
    return _SAFE.sub("_", (name + " " + shape).strip()).strip("_")[:64]


def union(intervals):
    """Merged, sorted copy of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b):
    """Parts of the merged intervals `a` not covered by the merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Trace:
    """Events of one trace: `devices` maps a chip's plane name to its
    `ops` and `modules` [(name, start_ns, end_ns)], `spans` are the host's
    `bench.*` annotations."""

    def __init__(self, devices, spans):
        self.devices = devices
        self.spans = spans
        wins = [s for s in spans if s[0] == WINDOW_SPAN]
        if wins:
            self.window = (min(s[1] for s in wins), max(s[2] for s in wins))
        else:
            ev = [e for d in devices.values() for e in d["ops"]]
            self.window = (min(e[1] for e in ev), max(e[2] for e in ev)) \
                if ev else (0, 0)

    @classmethod
    def from_file(cls, path):
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, spans = {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:"):
                dev = {"ops": [], "modules": []}
                for line in plane.lines:
                    key = {"XLA Ops": "ops",
                           "XLA Modules": "modules"}.get(line.name)
                    if key is None:
                        continue
                    for e in line.events:
                        s = int(e.start_ns)
                        dev[key].append((e.name, s, s + int(e.duration_ns)))
                devices[plane.name] = dev
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            s = int(e.start_ns)
                            spans.append((e.name, s, s + int(e.duration_ns)))
        return cls(devices, spans)

    # ---------------------------------------------------------- reductions
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, dev):
        lo, hi = self.window
        return clip(union((s, e) for _, s, e in dev["ops"]), lo, hi)

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(total(self._busy(d)) for d in self.devices.values()) \
            / len(self.devices) / 1e9

    def idle_share(self):
        w = self.window_s()
        return None if w <= 0 or not self.devices else 1.0 - self.busy_s() / w

    def span_at(self, t):
        """Innermost benchmark span (other than the window) that holds t."""
        best = None
        for name, s, e in self.spans:
            if name != WINDOW_SPAN and s <= t < e:
                if best is None or e - s < best[2] - best[1]:
                    best = (name, s, e)
        return best[0] if best else OUTSIDE

    def idle_gaps(self, n=10):
        """The n longest idle gaps of the first chip inside the window, each
        named by what the host was in at the gap's middle."""
        if not self.devices:
            return []
        dev = self.devices[sorted(self.devices)[0]]
        lo, hi = self.window
        gaps = subtract([(lo, hi)], self._busy(dev))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[_SAFE.sub("_", self.span_at((s + e) // 2)), (e - s) / 1e9]
                for s, e in gaps[:n]]

    def _first_chip(self, line, pattern=None, whole=False):
        """{name: (ns inside the window, runs)} of the first chip's events of
        `line` ("ops" or "modules") whose name matches; `whole` keeps only
        runs that lie inside the window, else a run is clipped to it."""
        if not self.devices:
            return {}
        rx = re.compile(pattern) if pattern else None
        lo, hi = self.window
        acc = {}
        for name, s, e in self.devices[sorted(self.devices)[0]][line]:
            inside = (s >= lo and e <= hi) if whole else (e > lo and s < hi)
            if inside and (rx is None or rx.search(name)):
                t, k = acc.get(name, (0, 0))
                acc[name] = (t + min(e, hi) - max(s, lo), k + 1)
        return acc

    def top_ops(self, n=10):
        """The n operations with most device time in the window (summed over
        their runs, first chip)."""
        acc = self._first_chip("ops")
        top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
        return [[label(k), t / 1e9] for k, (t, _) in top]

    def top_op_kinds(self, n=10):
        """[[kind and result shape, seconds, runs]] of the first chip's
        operations inside the window, summed over every operation of one
        kind and shape (`copy.1658` and `copy.1661` of one shape are one row):
        what a step of many like layers spends its time on."""
        acc = {}
        for name, (t, k) in self._first_chip("ops").items():
            stem, shape, _ = op_name(name)
            key = _SAFE.sub("_", (re.sub(r"[.0-9]+$", "", stem) + " "
                                  + shape).strip()).strip("_")[:64]
            t0, k0 = acc.get(key, (0, 0))
            acc[key] = (t0 + t, k0 + k)
        top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
        return [[key, t / 1e9, k] for key, (t, k) in top]

    def op_time(self, pattern):
        """(seconds, runs) of the first chip's operations whose HLO text
        matches `pattern`, whole runs inside the window."""
        acc = self._first_chip("ops", pattern, whole=True)
        return (sum(t for t, _ in acc.values()) / 1e9,
                sum(k for _, k in acc.values()))

    def module_time(self, pattern=None, by="time"):
        """(name, seconds per run, runs) of the program with most device
        time (or, `by="runs"`, most runs) among those whose name matches,
        whole runs inside the window."""
        acc = self._first_chip("modules", pattern, whole=True)
        if not acc:
            return None
        pick = (lambda kv: kv[1][1]) if by == "runs" else (lambda kv: kv[1][0])
        name, (t, n) = max(acc.items(), key=pick)
        return name, t / n / 1e9, n

    def top_programs(self, n=6):
        """[[name, seconds per run, runs]] of the programs with most device
        time inside the window (first chip)."""
        acc = self._first_chip("modules", whole=True)
        top = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
        return [[name, t / k / 1e9, k] for name, (t, k) in top]
