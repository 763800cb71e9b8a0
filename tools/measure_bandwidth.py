#!/usr/bin/env python
"""Collective-bandwidth microbenchmark over the device mesh (role parity:
tools/bandwidth/measure.py — the reference measures KVStore push/pull
GB/s across devices; here the measured primitive is the GSPMD
all-reduce (psum) the fused data-parallel step actually uses, plus
reduce-scatter and all-gather — the two halves of the ZeRO
weight-update-sharding path).

Runs on whatever devices exist: real chips on a pod (collectives ride
ICI/DCN) or the virtual CPU mesh for plumbing checks. Prints one JSON
line per (collective, size).

Usage: python tools/measure_bandwidth.py [--sizes-mb 1,4,16] [--iters 10]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(sizes_mb, iters):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mxtpu.parallel import make_mesh

    n = len(jax.devices())
    mesh = make_mesh(shape=(n,))
    results = []

    iters = max(1, iters)

    def timeit(fn, x):
        out = fn(x)
        out.block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    for mb in sizes_mb:
        elems = int(mb * (1 << 20)) // 4
        elems = max(n, elems - elems % n)  # divisible by the axis
        x = jnp.zeros((elems,), jnp.float32)

        # DP-gradient model: every device holds a FULL replica (the
        # gradient) and the collective runs over it — in_specs=P() so the
        # per-device buffer size matches the formulas below
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                           out_specs=P(), check_vma=False)
        def allreduce(v):
            return jax.lax.psum(v, "data") / n

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(),
                           out_specs=P("data"), check_vma=False)
        def reducescatter(v):
            return jax.lax.psum_scatter(v, "data", tiled=True) / n

        # gather back from shards: per-device input is elems/n
        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P(), check_vma=False)
        def allgather(v):
            return jax.lax.all_gather(v, "data", tiled=True)

        for name, fn, bytes_moved in [
                # ring all-reduce moves 2(n-1)/n of the replica per device
                ("psum", allreduce, 2 * (n - 1) / n * elems * 4),
                ("reduce_scatter", reducescatter, (n - 1) / n * elems * 4),
                ("all_gather", allgather, (n - 1) / n * elems * 4)]:
            dt = timeit(jax.jit(fn), x)
            results.append({"collective": name, "size_mb": mb,
                            "devices": n,
                            "usec": round(dt * 1e6, 1),
                            "algo_gbps": round(bytes_moved / dt / 1e9, 3)})
            print(json.dumps(results[-1]))
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    sizes = [float(s) for s in args.sizes_mb.split(",")]
    return run(sizes, args.iters)


if __name__ == "__main__":
    main()
