#!/usr/bin/env python
"""Runnable paged-decode demo: attention LM, chunked prefill, token
streaming over HTTP.

Default mode boots a ``DecodeSession`` in kv layout (PagedArena KV
cache) behind the shared HTTP server, streams a few generations over
``POST /v1/generate?stream=1`` (printing each token event as it
arrives), shows the KV-block/prefill panel, and drains. ``--serve``
keeps it up for manual curl traffic instead.
"""
import argparse
import http.client
import json
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from mxtpu.serving import ServingHTTPServer  # noqa: E402
from mxtpu.serving.decode import (DecodeSession,  # noqa: E402
                                  attn_decode_fixture)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true",
                    help="stay up for manual traffic instead of the "
                         "demo burst")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()

    print("building paged attention fixture (block_size=4, "
          "max_blocks_per_seq=8 -> 32-token budget) ...")
    fx = attn_decode_fixture(vocab_size=16, block_size=4,
                             max_blocks_per_seq=8, seed=0)
    sess = DecodeSession(fx["step_symbol_json"], fx["params"],
                         fx["step_example_shapes"], [], arena="paged",
                         paged=fx, buckets=(1, 2, 4), slot_capacity=4,
                         prefill_chunk_tokens=4, prefill_buckets=(4,),
                         version_tag="demo-kv")
    server = ServingHTTPServer(None, decode=sess, port=args.port)
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    ctx = sess.pool.replicas[0].ctx
    print("decode serving on %s (%s=%s, slots %d, %d KV blocks of %d "
          "tokens)"
          % (server.endpoint, ctx, ctx.jax_device.platform,
             sess.slot_capacity, sess.arena.blocks_total, sess.block_size))

    if args.serve:
        print("POST %s/v1/generate?stream=1 | GET /debug/state | "
              "GET /healthz" % server.endpoint)
        print("Ctrl-C to drain and stop.")
        try:
            t.join()
        except KeyboardInterrupt:
            pass
        server.shutdown()
        return

    host, port = server.server_address[:2]
    prompts = [[2, 5, 7], [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
               [4, 4, 8]]
    for prompt in prompts:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("POST", "/v1/generate?stream=1",
                     json.dumps({"prompt": prompt, "max_new_tokens": 8,
                                 "seed": 1, "temperature": 0.7}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        print("prompt %s -> %s %s" % (prompt, resp.status,
                                      resp.getheader("Content-Type")))
        for line in resp:
            if line.strip():
                print("  event: %s" % line.decode().strip())
        conn.close()

    panel = sess.debug_panel()
    print("kv panel: %s" % json.dumps(panel["kv"]))
    print("prefill panel: %s" % json.dumps(panel["prefill"]))
    server.shutdown()
    print("drained.")


if __name__ == "__main__":
    main()
