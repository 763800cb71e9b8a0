#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from, many seeds in
one process (set-up is long, so the contract allows it):

    python3 benchmark/tools/fit_readings.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2 --out chiprun_out/readings/<cell>.json

For every seed: the program's first three steps through `Module.fit` (the
generator's own `build` and `first_steps`, no measured window) against the
plain reference. For the control seeds also the control (the reference in
the precision below the configuration's, `control_precision` of its file)
and the planted fault "half of the batch left out", each put through the
harness's own comparison (`compare.judge` under the cell's limits): `judged`
says whether it came out correct. Everything per leaf goes to `--out`; the
last lines print the numbers compared. Needs the chip; with `--rehearse` it
runs the tiny sizes on the CPU.
"""
import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, compare, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    import mxtpu  # noqa: F401
    gen = cell.generator()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = {}
    for seed in seeds:
        built = gen.build(cell, seed, cell.chips)
        it = gen.DeviceBatchIter(built["batch_obj"], built["pdata"],
                                 built["plabel"],
                                 lambda n: contextlib.nullcontext())
        prog = gen.first_steps(built, it)
        built["mod"] = built["batch_obj"] = it = None
        built["fit_kw"]["eval_metric"] = None
        gc.collect()
        ref = gen.reference_readings(built, cell, keep_first=True,
                                     against=prog.pop("first_grad"))
        first = ref.pop("first_grad")

        def compared(side, gaps):
            values = compare.training(side, ref, gaps)[0]
            return values, compare.judge(values, cell.limits)[1]

        row = {"program": prog, "reference": ref}
        row["compared"], row["judged"] = compared(prog, ref["grad_cos_gap"])
        if seed in control:
            ctl = gen.reference_readings(
                built, cell, quant=cell.config["control_precision"],
                against=first)
            half = gen.reference_readings(built, cell, keep_one_in=2,
                                          against=first)
            row.update(control=ctl, half_batch=half)
            row["control_compared"], row["control_judged"] = compared(
                ctl, ctl["grad_cos_gap"])
            row["half_batch_compared"], row["half_batch_judged"] = compared(
                half, half["grad_cos_gap"])
        del first
        rows[str(seed)] = row
        print(seed, json.dumps({k: v for k, v in row.items()
                                if k.endswith(("compared", "judged"))}),
              flush=True)
        del built
        gc.collect()
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
