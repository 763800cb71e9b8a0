#!/usr/bin/env python
"""CI check: every telemetry series mxtpu emits is documented.

Scans ``mxtpu/`` for literal series names passed to
``counter(...)`` / ``gauge(...)`` / ``histogram(...)`` call sites (both
the module-level helpers and registry methods) and fails when any name
is missing from the series inventory in ``docs/observability.md``.

A new series without a doc entry is how dashboards rot: the emitting
code outlives the engineer who knew what it meant. This check is wired
into the test suite (tests/test_diagnostics.py) so it runs with tier-1.

Dynamic names the regex cannot see (the non-first branch of a
conditional expression, names built from constants) are declared in
``EXTRA_EMITTED`` below — keep it short and commented. Derived
exposition-only series (``*_p50/90/99``, serving ``qps`` etc.) are
documented as patterns and listed in ``DERIVED_OK``.

The same gate covers trace spans (PR 17): every literal name passed to
``span(...)`` must appear, backticked, in the docs' "## Span inventory"
section — a span on the exported timeline that no document explains is
the same dashboard rot one abstraction up. Dynamic span names go in
``EXTRA_SPANS`` with the placeholder spelling the docs use.

Usage: python tools/check_series_documented.py [--docs docs/observability.md]
"""
from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: literal first-string-arg of counter/gauge/histogram calls
_CALL_RE = re.compile(
    r"\b(?:counter|gauge|histogram)\(\s*(?:name=)?\"([a-z][a-z0-9_]+)\"")

#: emitted names the regex cannot extract from source
EXTRA_EMITTED = [
    "executor_cache_misses",   # else-branch of a conditional expression
    "span_ms",                 # emitted via the SPAN_HISTOGRAM constant
    # concurrency-witness counters emitted through a (name, labels,
    # help) tuple (analysis/concurrency.py _record_finding)
    "lock_order_violations",
    "lock_blocking_under_lock",
]

#: names matched by _CALL_RE that are NOT series (or are doc'd as a
#: pattern): derived exposition gauges and adapter-internal keys
DERIVED_OK = {
    "qps", "batch_fill_ratio", "executor_cache_hit_rate",
}

#: literal first-string-arg of span(...) calls (telemetry.span,
#: tracing.span, metrics.span, the decode session's _span / _unit — the
#: name is always the first string).
#: Dotted names allowed; a name with format placeholders ("batch[%d]")
#: deliberately fails the closing-quote match and is declared below.
_SPAN_RE = re.compile(
    r"(?:\b|_)(?:span|unit)\(\s*(?:name=)?\"([a-z][a-z0-9_.]+)\"")

#: dynamic span names, spelled the way the docs' span inventory does
EXTRA_SPANS = [
    "batch[N]",   # _tel.span("batch[%d]" % bucket) — serving/server.py
]


def emitted_series(pkg_dir):
    names = set(EXTRA_EMITTED)
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                src = f.read()
            names.update(_CALL_RE.findall(src))
    return names - DERIVED_OK


def emitted_spans(pkg_dir):
    names = set(EXTRA_SPANS)
    for dirpath, dirnames, filenames in os.walk(pkg_dir):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                src = f.read()
            names.update(_SPAN_RE.findall(src))
    return names


def span_inventory(doc_text):
    """Backticked span names inside the "## Span inventory" section
    ONLY — a prose mention elsewhere is not an inventory entry."""
    names = set()
    in_section = False
    for line in doc_text.splitlines():
        if line.startswith("#"):
            in_section = line.strip().lower().lstrip("# ") \
                == "span inventory"
            continue
        if in_section:
            names.update(re.findall(r"`([a-z][a-z0-9_.\[\]N]+)`", line))
    return names


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", default=os.path.join(ROOT, "docs",
                                                   "observability.md"))
    ap.add_argument("--pkg", default=os.path.join(ROOT, "mxtpu"))
    args = ap.parse_args(argv)
    with open(args.docs) as f:
        doc_text = f.read()
    # exact backtick-quoted names from INVENTORY TABLE ROWS only: a raw
    # substring test would let `fit_samples` ride on the
    # `fit_samples_per_sec` row (prefix holes), and a prose mention is
    # not an inventory entry — the table is the CI contract
    doc_names = set()
    for line in doc_text.splitlines():
        if line.lstrip().startswith("|"):
            doc_names.update(re.findall(r"`([a-z][a-z0-9_]+)`", line))
    names = emitted_series(args.pkg)
    missing = sorted(n for n in names if n not in doc_names)
    if missing:
        print("check_series_documented: %d emitted series missing from %s:"
              % (len(missing), os.path.relpath(args.docs, ROOT)))
        for n in missing:
            print("  - %s" % n)
        print("add them to the series inventory table (or, for derived/"
              "non-series names, to DERIVED_OK in this tool).")
        return 1
    spans = emitted_spans(args.pkg)
    doc_spans = span_inventory(doc_text)
    missing_spans = sorted(s for s in spans if s not in doc_spans)
    if missing_spans:
        print("check_series_documented: %d emitted spans missing from the "
              "'## Span inventory' section of %s:"
              % (len(missing_spans), os.path.relpath(args.docs, ROOT)))
        for s in missing_spans:
            print("  - %s" % s)
        print("every span lands on the exported timeline "
              "(/debug/trace) — document it, or declare a dynamic "
              "name's doc spelling in EXTRA_SPANS.")
        return 1
    print("check_series_documented: %d series + %d spans, all documented."
          % (len(names), len(spans)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
