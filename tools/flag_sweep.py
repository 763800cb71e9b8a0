#!/usr/bin/env python
"""Sweep XLA TPU flag combinations over the ResNet-50 fused-step bench.

Thin CLI wrapper: the sweep/probe implementation moved into
``mxtpu.tune.sweep`` (one subprocess-bench driver shared with the
autotuner; the combo list and ranking live there). This script keeps
the historical entry point and stays import-light — it loads the sweep
module by file path so the PARENT process never initializes jax: a chip
belongs to one process, and every child bench needs it.

Usage: python tools/flag_sweep.py [iters] [--tuned artifact.json]
       (needs the accelerator)
"""
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_sweep():
    spec = importlib.util.spec_from_file_location(
        "mxtpu_tune_sweep", os.path.join(REPO, "mxtpu", "tune", "sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main():
    argv = sys.argv[1:]
    tuned = None
    if "--tuned" in argv:
        i = argv.index("--tuned")
        if i + 1 >= len(argv):
            sys.stderr.write("flag_sweep: --tuned needs an artifact path\n")
            sys.exit(2)
        tuned = argv[i + 1]
        del argv[i:i + 2]
    iters = argv[0] if argv else "40"
    sweep = _load_sweep()
    sweep.run_flag_sweep(iters=iters, tuned=tuned)


if __name__ == "__main__":
    main()
