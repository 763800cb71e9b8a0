#!/usr/bin/env python
"""CI check: every BENCH_*.json that claims a performance verdict
carries a deterministic measurement basis.

The repo's bench files are the PR-by-PR perf record. A verdict key
("pass", "speedup", "acceptance", ...) without a recorded *basis* — the
deterministic counts the verdict was computed from (crossings per step,
ns per call, bytes moved, noise floor) — is an unfalsifiable claim: the
next session cannot re-derive it, and on a noisy shared host a bare
wall-clock ratio is folklore the day it lands. This gate makes the
convention from BENCH_faults/BENCH_telemetry mandatory: verdict ⇒ basis,
anywhere in the same file.

Two shapes are exempt:

  * raw run logs — transcripts of a command (``cmd`` + ``rc`` keys),
    not verdicts; they assert nothing;
  * files with no verdict marker at all (pure measurement dumps).

Usage: python tools/check_bench_basis.py [--root DIR]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: keys (at any depth) that assert a perf verdict
_VERDICT_KEYS = {"pass", "verdict", "speedup", "best_speedup",
                 "acceptance"}
_VERDICT_SUFFIXES = ("_verdict", "_beats_default", "_improves")

#: keys (at any depth) that record a deterministic basis for a verdict:
#: explicit basis blocks, recorded caveats, noise floors, and
#: per-operation deterministic counts
_BASIS_KEYS = {"basis", "verdict_basis", "basis_note", "caveat",
               "wall_clock_caveat", "host_cost_caveat",
               "deterministic_microbench", "host_noise_floor_pct",
               "provenance"}


def _walk_keys(obj):
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield k
            for sub in _walk_keys(v):
                yield sub
    elif isinstance(obj, list):
        for v in obj:
            for sub in _walk_keys(v):
                yield sub


def _is_verdict_key(k):
    return k in _VERDICT_KEYS or any(k.endswith(s)
                                     for s in _VERDICT_SUFFIXES)


def check_file(path):
    """(status, detail): status is 'ok', 'exempt', 'no-verdict' or
    'missing-basis'."""
    with open(path) as f:
        data = json.load(f)
    top = set(data.keys()) if isinstance(data, dict) else set()
    if "cmd" in top and "rc" in top:
        return "exempt", "raw run log (cmd+rc)"
    keys = list(_walk_keys(data))
    verdicts = sorted({k for k in keys if _is_verdict_key(k)})
    if not verdicts:
        return "no-verdict", "measurement dump, asserts nothing"
    basis = sorted({k for k in keys if k in _BASIS_KEYS})
    if not basis:
        return "missing-basis", "verdict keys %s" % verdicts
    return "ok", "verdicts %s <- basis %s" % (verdicts, basis)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    paths = sorted(glob.glob(os.path.join(args.root, "BENCH_*.json")))
    if not paths:
        print("check_bench_basis: no BENCH_*.json under %s" % args.root)
        return 0
    failures = []
    for path in paths:
        name = os.path.basename(path)
        try:
            status, detail = check_file(path)
        except ValueError as exc:
            failures.append((name, "unparsable JSON: %s" % exc))
            continue
        if status == "missing-basis":
            failures.append((name, detail))
        elif args.verbose:
            print("  %-24s %-12s %s" % (name, status, detail))
    if failures:
        print("check_bench_basis: %d bench file(s) claim a perf verdict "
              "without a deterministic basis:" % len(failures))
        for name, detail in failures:
            print("  - %s: %s" % (name, detail))
        print("record HOW the verdict was computed (a 'basis'/"
              "'verdict_basis' block with deterministic counts, or a "
              "recorded caveat) next to the claim.")
        return 1
    print("check_bench_basis: %d bench files, every verdict carries a "
          "basis." % len(paths))
    return 0


if __name__ == "__main__":
    sys.exit(main())
