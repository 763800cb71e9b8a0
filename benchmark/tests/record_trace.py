"""Records the small profiler trace that test_trace.py reduces.

Run on the chip: `chiprun -- python3 benchmark/tests/record_trace.py`. It runs
a few jitted steps (two matmuls and an elementwise pass) under the profiler,
with host gaps inside `bench.input_next` and `bench.fit` annotations, and
writes `chiprun_out/recorded/trace.xplane.pb` plus a listing of its planes and
lines. The copy kept beside the tests is `data/recorded.xplane.pb`.
"""
import glob
import json
import os
import shutil
import sys
import time


def main():
    import jax
    import jax.numpy as jnp

    out = os.path.join("chiprun_out", "recorded")
    os.makedirs(out, exist_ok=True)
    tdir = os.path.join(out, "trace")
    shutil.rmtree(tdir, ignore_errors=True)

    @jax.jit
    def step(x, w):
        with jax.named_scope("probe_matmul"):
            y = jnp.dot(x, w)
        return jnp.tanh(y) + x

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 0.001
    step(x, w).block_until_ready()
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    for i in range(6):
        with jax.profiler.TraceAnnotation("bench.input_next"):
            time.sleep(0.004)
        with jax.profiler.TraceAnnotation("bench.fit"):
            for _ in range(3):
                x = step(x, w)
            x.block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(paths[0])
    listing = {"window_s": window, "device": jax.devices()[0].device_kind, "planes": []}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            first = evs[0] if evs else None
            lines.append({"name": line.name, "events": len(evs),
                          "names": dict(sorted(names.items(), key=lambda kv: -kv[1])[:12]),
                          "first": None if first is None else
                          {"name": first.name, "start_ns": first.start_ns,
                           "duration_ns": first.duration_ns,
                           "stats": {k: str(v)[:80] for k, v in list(first.stats)[:12]}}})
        listing["planes"].append({"name": plane.name, "lines": lines})
    shutil.copy(paths[0], os.path.join(out, "trace.xplane.pb"))
    shutil.rmtree(tdir, ignore_errors=True)
    with open(os.path.join(out, "listing.json"), "w") as f:
        json.dump(listing, f, indent=1)
    print(json.dumps(listing)[:20000])
    print("bytes", os.path.getsize(os.path.join(out, "trace.xplane.pb")))


if __name__ == "__main__":
    sys.exit(main())
