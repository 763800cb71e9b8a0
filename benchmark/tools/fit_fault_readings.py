#!/usr/bin/env python3
"""Readings of a planted fault that is a configuration's own, many seeds in
one process:

    python3 benchmark/tools/fit_fault_readings.py --workload <cell> \
        --fault chunk_reset --seeds 1,2,3 --out chiprun_out/readings/<x>.json

For every seed the plain reference's first three steps, and the same with
the fault planted in the reference's copy (`reference.block_loss(cfg, None,
fault=<name>)`; for `olmo-hybrid-7b-train`, `chunk_reset`: the recurrent
state set to 0 at every chunk boundary), put through the harness's own
comparison under the cell's limits: `judged` says whether the faulted copy
came out correct. No program runs, so nothing of mxtpu is imported.
`fit_readings.py` reads the program, the control and the half batch. Needs
the chip; with `--rehearse` it runs the tiny sizes on the CPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell, seed, fault):
    """(values compared, judged) of the reference with `fault` planted
    against the sound reference, on one seed."""
    from benchmark import compare
    from benchmark.references import common
    gen = cell.generator()
    built = gen.prepare(cell, seed, cell.chips)
    ref = gen.reference_readings(built, cell, keep_first=True)
    mod, cfg = built["reference"], cell.config
    rows = mod.split_rows(*(built["drawn"][n] for n in built["names"]))
    bad = common.follow(
        mod.block_loss(cfg, None, fault=fault), built["make_params"], rows,
        dict(built["opt"]), cfg["param_dtypes"], steps=3,
        rows_per_block=cfg.get("reference_rows_per_block"),
        items_per_row=built["items_per_row"], against=ref.pop("first_grad"))
    values = compare.training(bad, ref, bad["grad_cos_gap"])[0]
    return values, compare.judge(values, cell.limits)[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    rows = {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        values, judged = readings(cell, seed, args.fault)
        rows[str(seed)] = {args.fault + "_compared": values,
                           args.fault + "_judged": judged}
        print(seed, json.dumps(rows[str(seed)]), flush=True)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
