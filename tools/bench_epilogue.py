#!/usr/bin/env python
"""A/B the Pallas BN-apply+ReLU+add epilogue against XLA's own fusion on
the real chip (VERDICT r3 next #2). Prints achieved GB/s for both
formulations on ResNet-50 stage shapes at the bench batch size; the
verdict (who wins, by how much) goes to PERF.md.

Usage: python tools/bench_epilogue.py [batch]   # needs the accelerator
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mxtpu.ops.epilogue import bn_apply_relu_add, bn_apply_relu_add_reference

# (H*W, C) per image for the four ResNet-50 stages
STAGES = [(56 * 56, 256), (28 * 28, 512), (14 * 14, 1024), (7 * 7, 2048)]


def _time(fn, *args, iters=30):
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    dev = jax.devices()[0]
    print("device:", dev.device_kind)
    rng = np.random.RandomState(0)
    rows = []
    for hw, c in STAGES:
        m = batch * hw
        x = jnp.asarray(rng.randn(m, c), jnp.bfloat16)
        r = jnp.asarray(rng.randn(m, c), jnp.bfloat16)
        scale = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(c), jnp.float32)

        xla = jax.jit(bn_apply_relu_add_reference)
        pal = jax.jit(lambda a, s, b, res: bn_apply_relu_add(a, s, b, res))
        t_x = _time(xla, x, scale, shift, r)
        t_p = _time(pal, x, scale, shift, r)
        # bytes: read x + read residual + write out, all bf16
        gb = 3 * m * c * 2 / 1e9
        rows.append((hw, c, gb / t_x, gb / t_p))
        print("stage (%5d,%4d): XLA %7.1f GB/s   pallas %7.1f GB/s   "
              "(%+.1f%%)" % (hw, c, gb / t_x, gb / t_p,
                             100 * (t_x / t_p - 1)))
    best = max(r[3] / r[2] for r in rows)
    print("pallas best speedup over XLA fusion: %+.1f%%" % (100 * (best - 1)))


if __name__ == "__main__":
    main()
