#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json, runs its traffic's generator against its
configuration on the chip this process sees, and prints one JSON object as
the last line of standard output. See benchmark/README.md.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Runtime:
    """What a generator needs of the run: spans on the profiler's clock, the
    instants the window opens and closes, the compile meter, the memory."""

    def __init__(self, args, device, peaks, meter, chips):
        self.args, self.device, self.peaks = args, device, peaks
        self.meter, self.chips = meter, chips
        self.tracing = bool(args.trace)
        self.trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        self.setup_s = None
        self.window_compiles = None
        self._mark = None
        self._held = []
        self.trace = None

    def annotate(self, name):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def window_seconds(self, traffic):
        if self.tracing:
            return min(self.args.seconds, float(traffic.get("trace_seconds", 4)))
        return self.args.seconds

    def trace_starts(self):
        if self.tracing:
            import jax
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)

    def trace_stops(self):
        if self.tracing:
            import jax
            from benchmark import trace
            jax.profiler.stop_trace()
            self.trace = trace.Trace.from_file(trace.find_xplane(self.trace_dir))
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def window_opens(self):
        """The first instant of the window: all before it is set-up."""
        self._mark = self.meter.mark()
        self._held.append(self._memory_now())
        self.setup_s = time.perf_counter() - T0

    def window_closes(self):
        self._held.append(self._memory_now())
        self.window_compiles = len(self.meter.since(self._mark)["programs"])

    def _memory_now(self):
        import jax
        from benchmark import chip
        return chip.memory_bytes(jax.devices()[:self.chips])

    def memory_peak(self):
        import jax
        from benchmark import chip
        return chip.memory_peak_bytes(jax.devices()[:self.chips], self._held)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' tiny `rehearsal` sizes: "
                         "checks the harness, prints REHEARSAL and no result")
    args = ap.parse_args(argv)

    from benchmark import chip, compare, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)

    device, peaks = chip.open_device(cell.chips, args.rehearse)
    meter = chip.CompileMeter()
    import mxtpu  # noqa: F401  (places the compile cache at <checkout>/.jax_cache)

    rt = Runtime(args, device, peaks, meter, cell.chips)
    out = cell.generator().run(cell, args, rt)

    whole = meter.since()
    facts = out["facts"]
    facts.update(peaks=peaks, config=cell.config, traffic=cell.traffic,
                 trace=rt.trace, compile_s=whole["compile_s"],
                 programs_compiled=len(whole["programs"]),
                 cache_hits=whole["cache_hits"],
                 window_compiles=rt.window_compiles, cell=cell)
    metrics = {}
    if args.trace:
        for m in cell.per_layer():
            v = cell.reader(m["name"]).read(facts)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        end_to_end = dict(out["end_to_end"], setup_s=rt.setup_s)
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": end_to_end[m["name"]],
                                  "unit": m["unit"]}

    # exact comparisons, limit 0: nothing compiles inside the window, and
    # every operation attempted comes back
    compared = dict(out["values"], window_compiles=rt.window_compiles,
                    failed=out["failed"])
    rows, ok = compare.judge(compared, dict(cell.limits, window_compiles=0,
                                            failed=0))
    dev = dict(device, memory_peak_bytes=facts["memory_peak_bytes"])
    result = {"correct": bool(ok), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    notes = {"setup_s": rt.setup_s, "window_s": facts["window_s"],
             "programs_compiled": facts["programs_compiled"],
             "cache_hits": facts["cache_hits"],
             "window_compiles": rt.window_compiles,
             "compared_at": facts.get("compared_at"),
             "reference_s": facts.get("reference_s"),
             "bytes_in_use_before_reference":
                 facts.get("bytes_in_use_before_reference"),
             "steps": facts.get("steps"),
             "readings": facts.get("readings"),
             # every number the comparison worked out, limit or none
             "values": out["values"]}
    if args.trace and rt.trace is not None:
        dev["busy_s"] = rt.trace.busy_s()
        dev["window_s"] = rt.trace.window_s()
        result["breakdown"] = {"device_ops": rt.trace.top_ops(10),
                               "idle_gaps": rt.trace.idle_gaps(10)}
        # beside the contract's breakdown: time per program, and per kind
        # and shape of operation (a step of many like layers)
        notes.update(programs=rt.trace.top_programs(6),
                     op_kinds=rt.trace.top_op_kinds(12))
    result["notes"] = notes
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    sys.stdout.flush()
    for n, v, lim in rows:
        sys.stderr.write("compared %s value %r limit %r %s\n" % (
            n, v, lim, "ok" if v is not None and v <= lim else "FAIL"))
    sys.stderr.flush()
    if args.rehearse:
        print("REHEARSAL " + json.dumps(result), flush=True)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
