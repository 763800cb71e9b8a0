#!/usr/bin/env python3
"""Readings that the limit of a serving cell is set from, several seeds in
one process:

    python3 benchmark/tools/serve_readings.py --workload <cell> \
        --seeds 1,2,3 --control-seeds 1,2 --seconds 15

For every seed: a server from the seed's weights, a short window at the
cell's own load, and the plain reference over the sampled finished requests:
the widest gap of a served token's logit below the reference's best. For the
control seeds also the control: at each position of the same prompts and
tokens, the gap of the token that the reference computed in
`control_precision` puts first.
"""
import argparse
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
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    import mxtpu  # noqa: F401
    gen = cell.generator()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        sess, server, thread = gen.serve(cell, seed)
        try:
            gen.warm(sess, cell.config, cell.traffic)
            rows, plan, f = gen.drive(sess, server, cell, seed, args.seconds)
        finally:
            gen.shut(sess, server, thread)
        sess = server = thread = None
        gc.collect()
        sample = gen.sample_finished(rows, plan, seed,
                                     int(cell.traffic["compare_requests"]))
        quant = cell.config["control_precision"] if seed in control else None
        gap, gap_ctl, n = gen.logit_gaps(cell, seed, sample, quant=quant)
        print(json.dumps({"seed": seed, "served_logit_gap": gap,
                          "control_gap": gap_ctl, "tokens_compared": n,
                          "requests_compared": len(sample),
                          "failed": f["failed"],
                          "attempted": f["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
