#!/usr/bin/env python3
"""How often the program and the plain reference route a token differently,
many seeds in one process:

    python3 benchmark/tools/route_readings.py --workload <cell> \
        --seeds 1,2,3 --out chiprun_out/readings/<x>.json

Top-k is discontinuous: where the program's bfloat16 activations move a
router score across the k-th place, the two sides send one token to
different experts, and every number `correct` compares carries that. For
every seed, at the seed's first weights on the seed's batch (step 1, before
any update): the program's choices (the router operators' second outputs,
read from the Symbol's internals through a forward-only Module) against the
reference's (`reference.route_choices`), a layer at a time: the share of
(token, slot) choices of one side that the other side did not make, and the
share of tokens with any such choice. Needs the chip; with `--rehearse` it
runs the tiny sizes on the CPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell, seed):
    import jax
    import numpy as np
    import mxtpu as mx
    gen = cell.generator()
    built = gen.prepare(cell, seed, cell.chips)
    cfg, ref = cell.config, built["reference"]
    sym = built["program"].symbol(cfg, cell.traffic)
    heads = [n for n in sym.get_internals().list_outputs()
             if n.endswith("_router_output1")]
    picks = mx.sym.Group([sym.get_internals()[n] for n in heads])
    data = [mx.io.DataDesc(n, s, dtype=d) for n, s, d in built["data_desc"]]
    mod = mx.mod.Module(picks, context=mx.tpu(0),
                        data_names=[d.name for d in data], label_names=None)
    mod.bind(data_shapes=data, for_training=False)
    bound = {n: str(b[0].dtype) for n, b in zip(
        mod._exec_group._param_names_out, mod._exec_group.param_arrays)}
    w0 = built["make_params"]()
    mod.init_params(arg_params={k: mx.nd.NDArray(v.astype(bound[k]))
                                for k, v in w0.items() if k in bound},
                    allow_missing=False)
    mod.forward(mx.io.DataBatch(
        data=[mx.nd.NDArray(built["drawn"][d.name]) for d in data], label=None,
        pad=0, index=None, provide_data=data), is_train=False)
    mine = [np.asarray(o.asnumpy()) for o in mod.get_outputs()]
    del mod
    tokens = ref.split_rows(*(built["drawn"][n] for n in built["names"]))[0]
    theirs = jax.device_get(jax.jit(
        lambda p, t: ref.route_choices(p, t, cfg))(w0, tokens))
    out = {}
    for name, a, b in zip(heads, mine, theirs):
        a = a.reshape(-1, a.shape[-1])
        b = np.asarray(b).reshape(-1, b.shape[-1])
        same = (a[:, :, None] == b[:, None, :]).any(-1)      # (tokens, k)
        out[name.split("_")[0]] = {
            "slot_share": float(1.0 - same.mean()),
            "token_share": float((~same.all(-1)).mean())}
    out["all"] = {k: float(np.mean([v[k] for v in out.values()]))
                  for k in ("slot_share", "token_share")}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    import mxtpu  # noqa: F401
    rows = {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        rows[str(seed)] = readings(cell, seed)
        print(seed, json.dumps(rows[str(seed)]), flush=True)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
