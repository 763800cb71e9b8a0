#!/usr/bin/env python3
"""Open-loop HTTP client of the serving cells, a process of its own.

The benchmark's process holds the chip and runs the server; this one never
touches JAX. It reads one JSON line from standard input (the schedule: the
endpoint and, for each request, the second it is due, the prompt's token ids
and the number of tokens to generate), answers `READY`, waits for `GO`, and
then sends every request at its due time whether or not earlier ones have
come back: `POST /v1/generate?stream=1`, reading the NDJSON stream token by
token. Copied from tools/loadgen_serving.py `run_open_loop` with its two
faults mended: a latency is timed from when the request was due, not from
when it was actually sent, and how late each send ran is reported.

It prints `CLOSED` when the window's last second has passed, waits for the
requests still in flight (`drain_s` at most), and prints one JSON line: for
each request when it was due and sent, when each token arrived (seconds from
`GO`), the tokens, and how it ended (`ok`, `error:<what>`, `never`).
"""
import http.client
import json
import sys
import threading
import time
import urllib.parse


def one_request(endpoint, req, t_go, row, timeout_s):
    url = urllib.parse.urlparse(endpoint)
    body = json.dumps({"prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "temperature": 0.0, "timeout_sec": timeout_s})
    conn = http.client.HTTPConnection(url.hostname, url.port,
                                      timeout=timeout_s + 10)
    try:
        row["sent"] = time.monotonic() - t_go
        conn.request("POST", "/v1/generate?stream=1", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            row["status"] = "error:http %d %s" % (
                resp.status, resp.read(200).decode("utf-8", "replace"))
            return
        while True:
            line = resp.readline()
            if not line:
                row["status"] = "error:stream ended without a terminal event"
                return
            now = time.monotonic() - t_go
            ev = json.loads(line)
            if "token" in ev:
                row["tokens"].append(int(ev["token"]))
                row["at"].append(now)
            elif "done" in ev:
                row["status"] = "ok"
                row["done"] = now
                return
            elif "error" in ev:
                row["status"] = "error:%s %s" % (ev.get("type"), ev["error"])
                return
    except (OSError, ValueError, http.client.HTTPException) as exc:
        row["status"] = "error:%s %s" % (type(exc).__name__, exc)
    finally:
        conn.close()


def main():
    plan = json.loads(sys.stdin.readline())
    reqs = sorted(plan["requests"], key=lambda r: r["due"])
    rows = [{"id": r["id"], "due": r["due"], "sent": None, "tokens": [],
             "at": [], "done": None, "status": "never"} for r in reqs]
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2
    t_go = time.monotonic()
    threads = []
    for req, row in zip(reqs, rows):
        wait = req["due"] - (time.monotonic() - t_go)
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one_request, daemon=True, args=(
            plan["endpoint"], req, t_go, row, plan["timeout_s"]))
        th.start()
        threads.append(th)
    wait = plan["close_at"] - (time.monotonic() - t_go)
    if wait > 0:
        time.sleep(wait)
    print("CLOSED", flush=True)
    deadline = time.monotonic() + plan["drain_s"]
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    # a request still in flight now never came: freeze what it has
    out = [dict(r, tokens=list(r["tokens"]), at=list(r["at"])) for r in rows]
    for r, th in zip(out, threads):
        if th.is_alive():
            r["status"] = "never"
    print(json.dumps({"rows": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
