#!/usr/bin/env python
"""Benchmark: what the bf16 mixed-precision rewrite buys, per program.

The verdict basis is DETERMINISTIC (PR-2 convention): the cost registry's
XLA ``cost_analysis``/``memory_analysis`` numbers for the SAME program
built f32 versus under ``MXTPU_PIPELINE=bf16`` — flops and, above all,
bytes-accessed (whether the bytes delta moves throughput on the chip is
not measured; see PERF.md). Wall-clock steps/sec is recorded as a
CAVEAT only: on the 2-core CPU host XLA:CPU emulates bf16 by widening,
so CPU wall-clock says nothing about TPU behavior (noise floor recorded
per the PR-2 convention).

Also records the parity deltas the test gate enforces
(tests/test_compile.py::test_bf16_parity_gate) so the JSON is a
self-contained record.

Usage: python tools/bench_precision.py [--out BENCH_precision.json]
"""
import argparse
import json
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import mxtpu as mx  # noqa: E402
from mxtpu import diagnostics as diag  # noqa: E402
from mxtpu.analysis import dataflow  # noqa: E402
from mxtpu.compile import pipeline  # noqa: E402
from mxtpu.models import lenet, mlp  # noqa: E402


def _data(model, n=256, batch=64):
    rng = np.random.RandomState(0)
    X = rng.rand(n, 1, 28, 28).astype(np.float32) if model == "lenet" \
        else rng.rand(n, 784).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, n).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch,
                             label_name="softmax_label")


def _fit(symbol, model, names, epochs):
    it = _data(model)
    mod = mx.mod.Module(symbol, context=mx.cpu(),
                        logger=logging.getLogger("quiet"))
    mod.logger.setLevel(logging.ERROR)
    metric = mx.metric.create(["acc", "ce"])
    with pipeline.pipeline_scope(names):
        mx.random.seed(11)
        np.random.seed(11)
        t0 = time.perf_counter()
        mod.fit(it, num_epoch=epochs, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                eval_metric=metric)
        wall = time.perf_counter() - t0
    rec = diag.programs("fused_step")[-1]
    vals = dict(zip(*metric.get()))
    return rec, vals, wall


def graph_bytes(model, batch=64):
    """Graph-level activation bytes from the liveness analysis, f32 vs
    bf16-rewritten — the PLATFORM-INDEPENDENT deterministic basis. The
    cost registry's bytes-accessed reflects the host backend's lowering
    (XLA:CPU widens bf16 and pays converts); what shrinks on TPU is the
    bytes each op-output entry occupies, which liveness() computes off
    the inferred dtypes of the transformed graph."""
    get = mlp.get_symbol if model == "mlp" else lenet.get_symbol
    sym = get(10)
    dshape = (batch, 1, 28, 28) if model == "lenet" else (batch, 784)
    arg_shapes, _, _ = sym.infer_shape(data=dshape,
                                       softmax_label=(batch,))
    hints = dict(zip(sym.list_arguments(), arg_shapes))
    sym_bf, rep = pipeline.transform_graph(sym, kind="bench",
                                           shapes=hints,
                                           passes=["bf16"])
    assert rep.applied == ["bf16"], rep.render()

    def act_bytes(s):
        info = dataflow.liveness(s, shapes=hints)
        skip = set()
        for n in s._topo():
            if n.is_variable:
                skip.add(id(n))
            elif n.op.name == "Cast":
                # converts fuse into a neighboring op on TPU (weight
                # cast-at-use into the matmul's operand read, boundary
                # casts into the elementwise producer/consumer) —
                # counting them as materialized activations would
                # charge the rewrite for buffers XLA never allocates
                skip.add(id(n))
        total = sum(b for (nid, _), b in info.entry_bytes.items()
                    if nid not in skip)
        return total, info.peak_live_bytes

    t32, p32 = act_bytes(sym)
    tbf, pbf = act_bytes(sym_bf)
    return {
        "activation_bytes_f32": t32, "activation_bytes_bf16": tbf,
        "activation_bytes_delta_pct": round(100.0 * (t32 - tbf)
                                            / max(t32, 1), 2),
        "peak_live_bytes_f32": p32, "peak_live_bytes_bf16": pbf,
        "peak_live_delta_pct": round(100.0 * (p32 - pbf)
                                     / max(p32, 1), 2),
        "note": "activation bytes exclude Cast outputs (converts fuse "
                "into a neighboring op on TPU); peak-live includes "
                "every entry, so it is conservative for bf16",
    }


def bench_model(model, epochs=2):
    get = mlp.get_symbol if model == "mlp" else lenet.get_symbol
    r32, v32, w32 = _fit(get(10), model, [], epochs)
    rbf, vbf, wbf = _fit(get(10), model, ["bf16"], epochs)
    assert rbf["precision"] == "mixed_bf16", rbf
    out = {
        "graph": graph_bytes(model),
        "f32": {"flops": r32["flops"],
                "bytes_accessed": r32["bytes_accessed"],
                "temp_bytes": r32["temp_bytes"],
                "ce": v32["cross-entropy"], "acc": v32["accuracy"]},
        "bf16": {"flops": rbf["flops"],
                 "bytes_accessed": rbf["bytes_accessed"],
                 "temp_bytes": rbf["temp_bytes"],
                 "ce": vbf["cross-entropy"], "acc": vbf["accuracy"]},
        "bytes_accessed_delta_pct": round(
            100.0 * (r32["bytes_accessed"] - rbf["bytes_accessed"])
            / max(r32["bytes_accessed"], 1.0), 2),
        "flops_delta_pct": round(
            100.0 * (r32["flops"] - rbf["flops"])
            / max(r32["flops"], 1.0), 2),
        "ce_delta": round(abs(v32["cross-entropy"]
                              - vbf["cross-entropy"]), 6),
        "acc_delta": round(abs(v32["accuracy"] - vbf["accuracy"]), 6),
        "wall_s_f32": round(w32, 3),
        "wall_s_bf16": round(wbf, 3),
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_precision.json"))
    ap.add_argument("--epochs", type=int, default=2)
    args = ap.parse_args()
    results = {}
    for model in ("mlp", "lenet"):
        results[model] = bench_model(model, epochs=args.epochs)
        print("%s: graph activation bytes delta %.1f%% (peak live "
              "%.1f%%), host cost-registry bytes delta %.1f%%, flops "
              "delta %.1f%%, ce delta %.4f"
              % (model,
                 results[model]["graph"]["activation_bytes_delta_pct"],
                 results[model]["graph"]["peak_live_delta_pct"],
                 results[model]["bytes_accessed_delta_pct"],
                 results[model]["flops_delta_pct"],
                 results[model]["ce_delta"]))
    payload = {
        "bench": "bf16 mixed-precision rewrite (compile pipeline)",
        "basis": "deterministic, two views: (1) graph-level activation "
                 "bytes + peak-live bytes from the mxtpu.analysis "
                 "liveness walk over the f32 vs bf16-rewritten Symbol "
                 "(platform-independent — the bytes a bandwidth-bound "
                 "TPU step streams); (2) XLA cost_analysis/"
                 "memory_analysis from the diagnostics cost registry "
                 "for the fused_step program as built on THIS host; "
                 "same data, same seeds, %d epochs" % args.epochs,
        "host_cost_caveat": "the host cost-registry deltas are from the "
                            "CPU lowering, where XLA:CPU widens bf16 to "
                            "f32 and inserts converts — bytes-accessed "
                            "GROWS there; the graph-level activation-"
                            "bytes delta is the TPU-relevant number",
        "wall_clock_caveat": "2-core CPU host, >45% noise floor (PR-2 "
                             "convention) — wall-clock recorded but NOT "
                             "a verdict basis",
        "parity_gate": "tests/test_compile.py::test_bf16_parity_gate "
                       "(acc exact-or-gated 2/256, ce < 1e-2, master "
                       "weights f32)",
        "models": results,
    }
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    print("wrote", out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
