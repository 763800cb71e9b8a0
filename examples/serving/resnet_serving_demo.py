#!/usr/bin/env python
"""Runnable mxtpu.serving demo: a resnet-8 HTTP inference server.

Default mode boots the server on an ephemeral port, runs a burst of
concurrent HTTP clients against it, prints the serving metrics, and
drains. ``--serve`` keeps it up for manual curl traffic instead.
"""
import argparse
import json
import os
import sys
import threading
import urllib.request

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from mxtpu.models.serving_fixtures import get_fixture  # noqa: E402
from mxtpu.serving import ServingHTTPServer, ServingSession  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true",
                    help="stay up for manual traffic instead of the demo "
                         "burst")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--requests-per-client", type=int, default=8)
    args = ap.parse_args()

    print("building resnet-8 fixture + warming bucket executables ...")
    sym_json, params, shapes = get_fixture("resnet")
    session = ServingSession(sym_json, params, shapes,
                             buckets=(1, 8, 32), max_delay_ms=5)
    server = ServingHTTPServer(session, port=args.port)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    print("serving on %s (buckets %s, %d replica(s) on %s)"
          % (server.endpoint, list(session.buckets), len(session.pool),
             ", ".join("%s=%s" % (r.ctx, r.ctx.jax_device.platform)
                       for r in session.pool.replicas)))

    if args.serve:
        print("POST %s/v1/predict | GET /v1/metrics | GET /healthz"
              % server.endpoint)
        print("Ctrl-C to drain and stop.")
        try:
            t.join()
        except KeyboardInterrupt:
            pass
        server.shutdown()
        return

    def client(seed):
        rng = np.random.RandomState(seed)
        for _ in range(args.requests_per_client):
            x = rng.rand(1, 3, 28, 28).astype(np.float32)
            req = urllib.request.Request(
                server.endpoint + "/v1/predict",
                data=json.dumps({"inputs": {"data": x.tolist()}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                out = json.loads(r.read())["outputs"][0]
            assert len(out[0]) == 10  # resnet-8 fixture has 10 classes

    print("firing %d clients x %d requests over HTTP ..."
          % (args.clients, args.requests_per_client))
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    stats = session.stats()
    print(json.dumps(stats, indent=2))
    print("batch-fill %.2f | cache hit rate %.2f | p99 %.1f ms | "
          "shed rate %.3f"
          % (stats["batch_fill_ratio"], stats["executor_cache_hit_rate"],
             stats["request_latency_ms"]["p99_ms"], stats["shed_rate"]))

    # zero-downtime hot-swap: new weights pre-warm in the process-wide
    # cache while v0 serves, then the pool pointer flips atomically
    print("hot-swapping to perturbed weights (version v1) ...")
    new_params = {k: v + 0.05 for k, v in params.items()}
    info = session.swap_model(sym_json, new_params, version_tag="v1")
    with urllib.request.urlopen(server.endpoint + "/v1/version",
                                timeout=10) as r:
        print("active version:", json.loads(r.read()))
    assert info["generation"] == 1

    server.shutdown()
    server.server_close()
    print("drained and stopped.")


if __name__ == "__main__":
    main()
