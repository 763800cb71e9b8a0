#!/usr/bin/env python3
"""Where a training cell's second loss parts from the reference's: in the
update, in the rounding of the weights as they are kept, or in the
evaluation. One seed, one process:

    python3 benchmark/tools/second_loss_readings.py --workload <cell> \
        --seed 1 --leaves l2_conv_bias,l7_conv_weight \
        --out chiprun_out/readings/<cell>.second_loss.json

The program's first three steps through `Module.fit`, with its weights W1
and first moment m1 fetched after step 1; then the plain reference's, with
its own. The reference's forward (float32) is then read at five sets of
weights: its own W1; the program's W1; W0 + m1 of either side left
unrounded; and W0 + the program's m1 rounded as the configuration states
(which is the program's W1 if its update is the reference's: the elements
that differ are counted). `linear` is the first-order change of the loss,
the reference's first gradient times W1 - W0, for either side's W1.
`moved` counts, for the named leaves and for all leaves kept under
float32, the elements whose stored value differs from W0's after step 1 and
after step 3, on both sides. `by_group` reads both forwards, the program's
Symbol bound for inference in the configuration's dtypes and the
reference's, at W0 with one group of leaves at a time taken from the
program's W1 (the embedding; the layers of each kind; the final norm and
the head), at W0 and at W1: where the two forwards part says whose change
the program's forward reads otherwise. Needs the chip; `--rehearse` runs the
tiny sizes on the CPU.
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


def by_group(built, cell, w0, w1, reference_loss):
    """{group: [the program's forward, the reference's]} of the loss at W0
    with that group's leaves from W1."""
    import jax
    import jax.numpy as jnp
    import mxtpu as mx
    cfg = cell.config
    kinds = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    names = {"M": "mamba2_layers", "E": "expert_layers", "*": "attention_layers"}

    def group(k):
        if k.startswith("tok_emb"):
            return "embedding"
        head, _, _ = k.partition("_")
        if head[:1] == "l" and head[1:].isdigit():
            return names[kinds[int(head[1:])]]
        return "final_norm_and_head"

    stated = cfg["param_dtypes"]
    drawn = built["drawn"]
    label = built["label_desc"][0][0]
    lab = jnp.asarray(drawn[label]).reshape(-1).astype(jnp.int32)
    ce = jax.jit(lambda prob: jnp.mean(-jnp.log(
        prob.reshape(lab.shape[0], -1).astype(jnp.float32)[
            jnp.arange(lab.shape[0]), lab] + 1e-12)))
    sym = built["program"].symbol(cfg, cell.traffic)

    def kept(k, v):
        return mx.nd.NDArray(jnp.asarray(v).astype(
            stated.get(k, stated["default"])))

    args = {k: kept(k, v) for k, v in w0.items()}
    args.update({n: mx.nd.NDArray(drawn[n]) for n in sym.list_arguments()
                 if n in drawn})
    exe = sym.bind(mx.tpu(0), args, grad_req="null")
    out = {}
    for g in ["none"] + sorted({group(k) for k in w0}) + ["all"]:
        mixed = {k: (w1[k] if g == "all" or group(k) == g else w0[k])
                 for k in w0}
        for k, v in mixed.items():
            exe.arg_dict[k][:] = kept(k, v)
        prob = exe.forward(is_train=False)[0]
        out[g] = [float(ce(prob._data)), reference_loss(mixed)]
        print(g, out[g], file=sys.stderr, flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--leaves", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import chip, manifest
    cell = manifest.Cell(args.workload, rehearse=args.rehearse)
    chip.open_device(cell.chips, args.rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxtpu  # noqa: F401
    from benchmark.references import optim
    from benchmark.weights import round_to_dtype
    gen, cfg = cell.generator(), cell.config
    f32 = np.float32

    def host(tree):
        return {k: np.asarray(v, f32) for k, v in jax.device_get(tree).items()}

    # ---- the program: three steps, W1 and m1 kept on the host
    built = gen.build(cell, args.seed, cell.chips)
    it = gen.DeviceBatchIter(built["batch_obj"], built["pdata"],
                             built["plabel"],
                             lambda n: contextlib.nullcontext())
    mod, kw = built["mod"], built["fit_kw"]
    w0 = host(built["make_params"]())
    out = {"program_loss": [], "reference_loss": []}
    for step in (1, 2, 3):
        mod.fit(it.arm(limit=1), **kw)
        jax.block_until_ready(mod._fused.params)
        out["program_loss"].append(float(gen._ce(kw["eval_metric"])))
        if step == 1:
            w1_prog = host({k: mod._fused.params[k] for k in w0})
            m1_prog = host({k: (s[0] if isinstance(s, (tuple, list)) else s)
                            for k, s in mod._fused.opt_state.items()
                            if k in w0})
    w3_prog = host({k: mod._fused.params[k] for k in w0})
    built["mod"] = built["batch_obj"] = mod = it = None
    kw["eval_metric"] = None
    gc.collect()

    # ---- the reference: the same three steps, as `common.follow` takes them
    ref, opt, dtypes = built["reference"], dict(built["opt"]), cfg["param_dtypes"]
    rows = ref.split_rows(*(built["drawn"][n] for n in built["names"]))
    n_rows = rows[0].shape[0]
    per = cfg.get("reference_rows_per_block") or n_rows
    block = ref.block_loss(cfg, None)
    grad_fn = jax.jit(jax.value_and_grad(block, has_aux=True))
    fwd = jax.jit(block)
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c),
                  donate_argnums=(0, 1))
    step_fn = jax.jit(lambda t, p, g, s: optim.update(opt, t, p, g, s, dtypes),
                      static_argnums=0, donate_argnums=(1, 2, 3))
    params = built["make_params"]()
    state = optim.init(opt, params)
    for step in (1, 2, 3):
        grads, metric = None, 0.0
        for r in range(0, n_rows, per):
            (_, m), g = grad_fn(params, *(x[r:r + per] for x in rows))
            grads = g if grads is None else add(grads, g)
            metric += float(m)
        out["reference_loss"].append(metric / (n_rows * built["items_per_row"]))
        params, state = step_fn(step, params, grads, state)
        del grads
        if step == 1:
            w1_ref, m1_ref = host(params), host(optim.first_moment(opt, state))
    w3_ref = host(params)
    del params, state
    gc.collect()

    def loss_at(tree):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}
        return sum(float(fwd(p, *(x[r:r + per] for x in rows))[1])
                   for r in range(0, n_rows, per)) \
            / (n_rows * built["items_per_row"])

    def kept(k):
        return dtypes.get(k, dtypes["default"])

    redo = {k: np.asarray(round_to_dtype(jnp.asarray(w0[k] + m1_prog[k]),
                                         kept(k))) for k in w0}
    out["reference_forward_at"] = {
        "reference_W1": loss_at(w1_ref),
        "program_W1": loss_at(w1_prog),
        "W0_plus_reference_m1_unrounded": loss_at(
            {k: w0[k] + m1_ref[k] for k in w0}),
        "W0_plus_program_m1_unrounded": loss_at(
            {k: w0[k] + m1_prog[k] for k in w0}),
        "W0_plus_program_m1_rounded_as_stated": loss_at(redo)}
    out["update_elements_unlike_the_reference_rule"] = int(sum(
        np.count_nonzero(redo[k] != w1_prog[k]) for k in w0))
    # m1 = -lr x the first gradient (momentum starts from nought)
    lr = opt["learning_rate"]

    def linear(w1):
        return float(sum(np.vdot(-m1_ref[k] / lr, w1[k] - w0[k]) for k in w0))

    out["linear"] = {"reference_W1": linear(w1_ref),
                     "program_W1": linear(w1_prog),
                     "reference_m1_unrounded": linear(
                         {k: w0[k] + m1_ref[k] for k in w0})}

    def moved(side1, side3, names):
        n = sum(w0[k].size for k in names)
        return {"elements": int(n),
                "step1": int(sum(np.count_nonzero(side1[k] != w0[k])
                                 for k in names)),
                "step3": int(sum(np.count_nonzero(side3[k] != w0[k])
                                 for k in names))}

    low = [k for k in w0 if kept(k) != "float32"]
    out["moved"] = {"all_under_float32": {
        "program": moved(w1_prog, w3_prog, low),
        "reference": moved(w1_ref, w3_ref, low)}}
    for k in [s for s in args.leaves.split(",") if s]:
        both = np.count_nonzero((w3_prog[k] != w0[k]) & (w3_ref[k] != w0[k]))
        out["moved"][k] = {
            "program": moved(w1_prog, w3_prog, [k]),
            "reference": moved(w1_ref, w3_ref, [k]),
            "step3_in_both": int(both),
            "change_norm": [float(np.linalg.norm(w3_prog[k] - w0[k])),
                            float(np.linalg.norm(w3_ref[k] - w0[k]))]}
    out["by_group"] = by_group(built, cell, w0, w1_prog, loss_at)
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
