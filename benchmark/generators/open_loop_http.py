"""Traffic of kind `open-loop-http`: requests sent on a schedule, whether or
not earlier ones have come back, to `ServingHTTPServer`'s streaming
`/v1/generate` in this process, by benchmark/loadgen.py in a process of its
own (so the client's parsing never holds this process's interpreter lock).

The mix's file gives the rate, the lead-in and the two length laws. Every
seed serves the same work: the prompt lengths, the output lengths and the
gaps between arrivals are fixed multisets on quantile grids (log-uniform
lengths, exponential gaps that sum to the window). The seed draws the token
ids and permutes the multisets; a mix that gives an `order_seed` fixes the
order too (a replayed trace: the same requests at the same instants in every
run), since at some tens of requests a tail moves with the order alone
(PERF.md). The lead-in (same rate, a grid of its own)
fills the server before the window opens and counts as set-up; its requests
are in no percentile, but the tokens they stream inside the window are work
of the window.

After the window the client waits for what is still in flight, the server is
shut and freed, the memory peak is read, and the plain reference runs once
over a seeded sample of the finished requests (the longest among them): the
number compared is the widest gap by which a served token's logit lies below
the reference's best at its position.
"""
import gc
import json
import math
import os
import subprocess
import sys
import threading
import time

from benchmark import weights

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------------ traffic
def length_grid(law, n):
    """n lengths on the quantile grid of a `log-uniform` law."""
    if law["law"] != "log-uniform":
        raise ValueError("unknown length law %r" % law["law"])
    lo, hi = float(law["min"]), float(law["max"])
    return [int(round(lo * (hi / lo) ** ((i + 0.5) / n))) for i in range(n)]


def gap_grid(n, total):
    """n exponential gaps on their quantile grid, scaled to sum to `total`."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def schedule(traffic, seed, seconds, vocab):
    """[{id, due, window, prompt, max_new_tokens}], lead-in first. `due` is
    in seconds from the first send; the window is [lead_in, lead_in +
    seconds)."""
    import numpy as np
    rng = np.random.default_rng(int(seed))
    order = np.random.default_rng(int(traffic["order_seed"])) \
        if "order_seed" in traffic else rng
    rate, lead = float(traffic["rate_rps"]), float(traffic["lead_in_s"])
    out = []
    for tag, start, span in (("L", 0.0, lead), ("W", lead, float(seconds))):
        n = int(round(rate * span))
        if n < 1:
            continue
        gaps = order.permutation(gap_grid(n, span))
        prompts = order.permutation(length_grid(traffic["prompt_tokens"], n))
        outputs = order.permutation(length_grid(traffic["output_tokens"], n))
        due = start
        for i in range(n):
            ids = rng.integers(0, vocab, size=int(prompts[i]))
            out.append({"id": "%s%d" % (tag, i), "due": due,
                        "window": tag == "W",
                        "prompt": [int(t) for t in ids],
                        "max_new_tokens": int(outputs[i])})
            due += float(gaps[i])
    return out


def reduce_rows(rows, plan, lead, seconds, timeout_s):
    """Client-side numbers of one run from the client's rows."""
    by_id = {r["id"]: r for r in plan}
    ttft, tbt, late, in_window, failed, attempted = [], [], [], 0, 0, 0
    lo, hi = lead, lead + seconds
    for r in rows:
        req = by_id[r["id"]]
        if r["sent"] is not None:
            late.append((r["sent"] - r["due"]) * 1e3)
        ok = r["status"] == "ok" and \
            len(r["tokens"]) == req["max_new_tokens"]
        if ok:
            in_window += sum(1 for t in r["at"] if lo <= t < hi)
        if not req["window"]:
            continue
        attempted += 1
        if not ok:
            failed += 1
            ttft.append(timeout_s * 1e3)
            continue
        ttft.append((r["at"][0] - r["due"]) * 1e3)
        tbt += [(b - a) * 1e3 for a, b in zip(r["at"], r["at"][1:])]
    return {"ttft_ms": ttft, "tbt_ms": tbt, "late_ms": late,
            "tokens_in_window": in_window, "failed": failed,
            "attempted": attempted}


def percentile(values, p):
    """Nearest-rank percentile (the value with p% of the samples at or
    below it)."""
    s = sorted(values)
    if not s:
        return None
    return s[min(len(s) - 1, max(0, int(math.ceil(p / 100.0 * len(s))) - 1))]


def summary(values):
    """A distribution's shape for the notes of the result line: what the
    percentile that is reported leaves out."""
    s = sorted(values)
    if not s:
        return None
    tail = s[-max(1, len(s) // 10):]
    out = {"n": len(s), "mean": sum(s) / len(s), "max": s[-1],
           "worst_tenth_mean": sum(tail) / len(tail)}
    out.update(("p%d" % p, percentile(s, p)) for p in (50, 75, 90, 95, 99))
    return out


# ------------------------------------------------------------------- server
class Gauges(threading.Thread):
    """Samples the session's slot and KV-block gauges while the window is
    open (the program keeps them as instantaneous values only)."""

    def __init__(self, sess, every_s=0.02):
        super().__init__(daemon=True, name="bench-gauges")
        self.sess, self.every_s = sess, every_s
        self.live, self.blocks = [], []
        self._stop_ev = threading.Event()

    def run(self):
        arena = self.sess.arena
        while not self._stop_ev.wait(self.every_s):
            self.live.append(arena.capacity - arena.free_slots)
            self.blocks.append(arena.blocks_live)

    def stop(self):
        self._stop_ev.set()
        self.join(5)


def serve(cell, seed):
    """The server as the configuration states it, weights from the seed."""
    from mxtpu.serving.server import ServingHTTPServer
    cfg = cell.config
    program = cell.config_module("program")
    reference = cell.config_module("reference")
    specs = reference.param_specs(cfg)
    stated = cfg["param_dtypes"]
    w = weights.make(seed, specs, round_to=stated["default"],
                     dtypes={n: stated.get(n, stated["default"])
                             for n, _, _ in specs})
    sess = program.session(cfg, w)
    del w
    server = ServingHTTPServer(None, host="127.0.0.1", port=0, decode=sess,
                               request_timeout=float(cfg["request_timeout_s"]))
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name="bench-http")
    thread.start()
    return sess, server, thread


def warm(sess, cfg, traffic):
    """Drives every program the window will use once: prompts of a full and
    a partial prefill chunk, and enough concurrent sequences with staggered
    lengths that the live batch passes through every step bucket."""
    import numpy as np
    slots = int(cfg["slot_capacity"])
    chunk = int(cfg["prefill_chunk_tokens"])
    rng = np.random.default_rng(0)
    futures = []
    for i in range(slots):
        prompt = [int(t) for t in rng.integers(0, cfg["vocab_size"],
                                               size=chunk + 2 + i)]
        futures.append(sess.generate_async(
            prompt, max_new_tokens=4 * slots + 2 * i, temperature=0.0,
            timeout=float(cfg["request_timeout_s"])))
    for f in futures:
        f.wait(float(cfg["request_timeout_s"]))


def shut(sess, server, thread):
    server.shutdown()
    server.server_close()
    thread.join(30)


class Client:
    """benchmark/loadgen.py as a child process."""

    def __init__(self, plan, endpoint, close_at, timeout_s, drain_s=60.0):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(HERE), "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        msg = {"endpoint": endpoint, "close_at": close_at,
               "timeout_s": timeout_s, "drain_s": drain_s,
               "requests": [{k: r[k] for k in
                             ("id", "due", "prompt", "max_new_tokens")}
                            for r in plan]}
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        self._expect("READY")

    def _expect(self, word):
        line = self.proc.stdout.readline().strip()
        if line != word:
            self.kill()
            raise SystemExit("load generator said %r, not %s" % (line, word))

    def go(self):
        self.proc.stdin.write("GO\n")
        self.proc.stdin.flush()
        return time.perf_counter()

    def closed(self):
        self._expect("CLOSED")

    def rows(self):
        line = self.proc.stdout.readline()
        self.proc.stdin.close()
        self.proc.wait(30)
        return json.loads(line)["rows"]

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(30)


def drive(sess, server, cell, seed, seconds, rt=None, rate=None):
    """One open-loop run against a live server: (rows, plan, facts)."""
    cfg, traffic = cell.config, dict(cell.traffic)
    if rate is not None:
        traffic["rate_rps"] = rate
    lead = float(traffic["lead_in_s"])
    timeout_s = float(cfg["request_timeout_s"])
    plan = schedule(traffic, seed, seconds, cfg["vocab_size"])
    client = Client(plan, server.endpoint, lead + seconds, timeout_s)
    gauges = Gauges(sess)
    counters = ("decode_prefill_stalls", "decode_steps_total",
                "decode_prefill_chunks", "decode_prefill_tokens")
    try:
        t_go = client.go()
        time.sleep(max(0.0, t_go + lead - time.perf_counter()))
        before = {c: sess.metrics.counter(c).value for c in counters}
        gauges.start()
        if rt is not None:
            rt.window_opens()
            if rt.tracing:
                after = float(traffic.get("trace_after_s", 5))
                time.sleep(max(0.0, t_go + lead + after - time.perf_counter()))
                rt.trace_starts()
                with rt.annotate("bench.window"):
                    time.sleep(float(traffic.get("trace_seconds", 4)))
                rt.trace_stops()
        client.closed()
        window_s = time.perf_counter() - (t_go + lead)
        gauges.stop()
        delta = {c: sess.metrics.counter(c).value - before[c] for c in counters}
        if rt is not None:
            rt.window_closes()
        rows = client.rows()
    except BaseException:
        client.kill()
        gauges.stop()
        raise
    facts = reduce_rows(rows, plan, lead, seconds, timeout_s)
    facts.update(window_s=window_s, seconds=float(seconds), counters=delta,
                 live_slots=gauges.live, live_blocks=gauges.blocks,
                 slot_capacity=int(cfg["slot_capacity"]),
                 blocks_total=int(sess.arena.blocks_total),
                 prompt_tokens_window=sum(
                     len(r["prompt"]) for r in plan if r["window"]))
    return rows, plan, facts


# ---------------------------------------------------------------- reference
def sample_finished(rows, plan, seed, n):
    """n finished window requests drawn from the seed, the longest first."""
    import numpy as np
    by_id = {r["id"]: r for r in plan}
    done = [r for r in rows if r["status"] == "ok" and by_id[r["id"]]["window"]]
    if not done:
        return []
    done.sort(key=lambda r: r["id"])
    longest = max(done, key=lambda r: len(by_id[r["id"]]["prompt"])
                  + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [(by_id[r["id"]]["prompt"], r["tokens"])
            for r in [longest] + [rest[i] for i in pick]]


def logit_gaps(cell, seed, sample, quant=None):
    """The plain reference once over each sampled prompt with its served
    tokens. Returns (widest gap of a served token below the reference's best
    logit, the same for the token the control precision puts first, tokens
    compared). Sequences are padded to one length, so one program serves."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = cell.config
    reference = cell.config_module("reference")
    w = weights.make(seed, reference.param_specs(cfg),
                     round_to=cfg["param_dtypes"]["default"])
    width = int(cell.traffic["prompt_tokens"]["max"]) \
        + int(cell.traffic["output_tokens"]["max"])

    def gaps(params, tokens, served, first, count, q):
        logits = reference.forward(params, tokens[None], cfg, quant=q,
                                   remat=False)[0]
        pos = first + jnp.arange(served.shape[0])
        rows = logits[jnp.clip(pos - 1, 0, width - 1)]
        best = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
        live = jnp.arange(served.shape[0]) < count
        return (jnp.max(jnp.where(live, best - got, 0.0)),
                jnp.argmax(rows, axis=-1))

    fn = jax.jit(gaps, static_argnums=5)
    out_max = int(cell.traffic["output_tokens"]["max"])
    worst, worst_ctl, compared = 0.0, None, 0
    for prompt, served in sample:
        seq = np.zeros((width,), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(served)] = served
        pad = np.zeros((out_max,), np.int32)
        pad[:len(served)] = served
        gap, top = fn(w, jnp.asarray(seq), jnp.asarray(pad), len(prompt),
                      len(served), None)
        worst = max(worst, float(gap))
        compared += len(served)
        if quant is not None:
            _, ctl_top = fn(w, jnp.asarray(seq), jnp.asarray(pad),
                            len(prompt), len(served), quant)
            gap_c, _ = fn(w, jnp.asarray(seq), ctl_top.astype(jnp.int32),
                          len(prompt), len(served), None)
            worst_ctl = max(worst_ctl or 0.0, float(gap_c))
    return worst, worst_ctl, compared


# ---------------------------------------------------------------------- run
def run(cell, args, rt):
    import jax
    cfg = cell.config
    sess, server, thread = serve(cell, args.seed)
    try:
        warm(sess, cfg, cell.traffic)
        rows, plan, facts = drive(sess, server, cell, args.seed,
                                  args.seconds, rt=rt)
    finally:
        shut(sess, server, thread)
    facts["memory_peak_bytes"] = rt.memory_peak()
    facts["chips"] = cell.chips
    sess = server = thread = None
    gc.collect()
    live = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()[:cell.chips]]
    facts["bytes_in_use_before_reference"] = max(live)
    t_ref = time.perf_counter()
    sample = sample_finished(rows, plan, args.seed,
                             int(cell.traffic["compare_requests"]))
    gap, _, compared = logit_gaps(cell, args.seed, sample)
    facts["reference_s"] = time.perf_counter() - t_ref
    facts["readings"] = {"requests_compared": len(sample),
                         "tokens_compared": compared,
                         "ttft_ms": summary(facts["ttft_ms"]),
                         "tbt_ms": summary(facts["tbt_ms"])}
    values = {"served_logit_gap": gap if sample else None}
    end_to_end = {
        "serve_tokens_per_s": facts["tokens_in_window"] / facts["seconds"],
        "ttft_p95_ms": percentile(facts["ttft_ms"], 95),
        "tbt_p95_ms": percentile(facts["tbt_ms"], 95)}
    return {"attempted": facts["attempted"], "failed": facts["failed"],
            "end_to_end": end_to_end, "facts": facts, "values": values}
