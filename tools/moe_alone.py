"""Time the expert layer alone on the chip: one `_contrib_MoEExperts` layer,
forward and forward + gradient, under `jax.jit`, bfloat16, 8,192 tokens,
8 experts held, at the sizes of the two expert cells and at a deployment's
rows an expert.

    python3 tools/moe_alone.py [--parent DIR] [--seeds 2] [--calls 20]

Each size is timed for this tree's operator (`fused`: the combine kernel)
and, with ``--parent DIR`` (a checkout of another commit), for that
commit's operator, loaded beside this one in the same process. Prints one
JSON line a measurement: the median ms a call over `--calls` calls after
three, and the relative 2-norm of each result against the first form's.
Refuses to run without a TPU.
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, d, f, top_k, experts routed over, gated)
SIZES = [
    ("laguna", 3072, 1024, 10, 256, True),
    ("nemotron", 2688, 1856, 6, 128, False),
    # every token takes 6 of the 8 experts held: 6,144 rows an expert, the
    # rows of 13 trips of 4,096
    ("deployment_relu2", 2688, 1856, 6, 8, False),
    ("deployment_gated", 3072, 1024, 6, 8, True),
]
TOKENS, HELD = 8192, 8


def _load(root, name):
    """`mxtpu.ops.moe` of the checkout at `root`, under a name of its own."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "mxtpu", "__init__.py"),
        submodule_search_locations=[os.path.join(root, "mxtpu")])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules[name] = pkg
    spec.loader.exec_module(pkg)
    return importlib.import_module(name + ".ops.moe")


def _inputs(seed, d, f, k, experts, gated):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (TOKENS, d), jnp.bfloat16)
    index = jnp.argsort(jax.random.uniform(ks[1], (TOKENS, experts)),
                        axis=-1)[:, :k].astype(jnp.int32)
    weight = jax.random.uniform(ks[2], (TOKENS, k), jnp.float32, 0.1, 1.0)
    ups = tuple((jax.random.normal(ks[3 + i], (HELD, f, d)) * 0.02)
                .astype(jnp.bfloat16) for i in range(2 if gated else 1))
    down = (jax.random.normal(ks[5], (HELD, d, f)) * 0.02).astype(
        jnp.bfloat16)
    cot = jax.random.normal(ks[0], (TOKENS, d), jnp.float32)
    return x, weight, index, ups, down, cot, experts


def _programs(moe, gated):
    def layer(x, w, index, ups, down, experts):
        out, _ = moe.moe_experts(x, w, index, ups[0] if gated else None,
                                 ups[-1], down, experts, 0)
        return out

    def loss(x, w, ups, down, index, cot, experts):
        return jnp.sum(layer(x, w, index, ups, down, experts)
                       .astype(jnp.float32) * cot)

    fwd = jax.jit(layer, static_argnums=(5,))
    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
                   static_argnums=(6,))
    return (lambda a: fwd(a[0], a[1], a[2], a[3], a[4], a[6]),
            lambda a: grad(a[0], a[1], a[3], a[4], a[2], a[5], a[6]))


def _time(fn, args, calls):
    jax.block_until_ready(fn(args))
    for _ in range(2):
        jax.block_until_ready(fn(args))
    times = []
    for _ in range(calls):
        t = time.perf_counter()
        jax.block_until_ready(fn(args))
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times), fn(args)


def _rel(a, b):
    a = [np.asarray(v, np.float32) for v in jax.tree.leaves(a)]
    b = [np.asarray(v, np.float32) for v in jax.tree.leaves(b)]
    return max(float(np.linalg.norm(u - v) / max(np.linalg.norm(v), 1e-30))
               for u, v in zip(a, b))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout of the commit to compare")
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--sizes", default=",".join(s[0] for s in SIZES))
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("moe_alone: no TPU (%s)" % dev.platform)
    from mxtpu.ops import moe
    forms = [("fused", moe)]
    if a.parent:
        forms.append(("parent", _load(os.path.abspath(a.parent),
                                      "mxtpu_parent")))
    for name, d, f, k, experts, gated in SIZES:
        if name not in a.sizes.split(","):
            continue
        seeds = [_inputs(4200 + seed, d, f, k, experts, gated)
                 for seed in range(a.seeds)]
        first = {}
        for form, mod in forms:
            fwd, grad = _programs(mod, gated)
            for seed, args in enumerate(seeds):
                f_ms, out = _time(fwd, args, a.calls)
                g_ms, got = _time(grad, args, a.calls)
                ref = first.setdefault(seed, (out, got[1]))
                print(json.dumps({
                    "size": name, "seed": seed, "form": form,
                    "fwd_ms": round(f_ms, 4), "fwd_grad_ms": round(g_ms, 4),
                    "out_rel": _rel(out, ref[0]),
                    "grad_rel": _rel(got[1], ref[1]),
                    "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
