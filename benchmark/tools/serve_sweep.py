#!/usr/bin/env python3
"""The sweep that finds the highest rate a serving cell sustains, once:

    python3 benchmark/tools/serve_sweep.py --workload <cell> --seed 7 \
        --rates 1.5,2,2.5,3,3.5 --seconds 30

One server from the seed's weights; the cell's mix is offered at each rate in
turn (its lengths and gaps on the same grids), and a line a rate says what
came back. A rate is sustained where the backlog does not grow: the last
third of the window's requests wait no longer for their first token than the
first third did, and the slots are not all full all the time.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    import mxtpu  # noqa: F401
    gen = cell.generator()
    sess, server, thread = gen.serve(cell, args.seed)
    try:
        gen.warm(sess, cell.config, cell.traffic)
        for rate in (float(r) for r in args.rates.split(",")):
            rows, plan, f = gen.drive(sess, server, cell, args.seed,
                                      args.seconds, rate=rate)
            ttft = f["ttft_ms"]
            third = max(1, len(ttft) // 3)
            by_due = [t for _, t in sorted(
                (r["due"], (r["at"][0] - r["due"]) * 1e3) for r in rows
                if r["id"].startswith("W") and r["at"])]
            print(json.dumps({
                "rate_rps": rate, "requests": f["attempted"],
                "failed": f["failed"],
                "tokens_per_s": f["tokens_in_window"] / f["seconds"],
                "ttft_p50_ms": gen.percentile(ttft, 50),
                "ttft_p90_ms": gen.percentile(ttft, 90),
                "ttft_first_third_ms": statistics.median(by_due[:third]),
                "ttft_last_third_ms": statistics.median(by_due[-third:]),
                "tbt_p50_ms": gen.percentile(f["tbt_ms"], 50),
                "tbt_p95_ms": gen.percentile(f["tbt_ms"], 95),
                "occupancy": sum(f["live_slots"]) / max(1, len(f["live_slots"]))
                / f["slot_capacity"],
                "late_ms_p95": gen.percentile(f["late_ms"], 95),
                "steps": f["counters"]["decode_steps_total"]}), flush=True)
    finally:
        gen.shut(sess, server, thread)
    return 0


if __name__ == "__main__":
    sys.exit(main())
