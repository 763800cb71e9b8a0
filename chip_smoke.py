#!/usr/bin/env python3
"""Standing proof that mxtpu starts and runs on the chip.

    python3 chip_smoke.py              # needs a TPU; exit 0 only if every phase passed
    python3 chip_smoke.py --rehearsal  # CPU only, toy sizes, never a pass

One process drives the system's main paths through the public entry points
a user would call and checks each result by the repo's own means:

  train      ResNet-50, batch 256, bf16, 224x224: Module.fit from a host
             NDArrayIter with acc + cross-entropy and a Speedometer, then
             score, save_checkpoint, Module.load and predict.
  lm         the transformer LM at d_model 2048 / 16 heads / seq 1024 with
             depth cut to 2 layers: 3 Module.fit steps whose compiled
             program must contain the Mosaic flash-attention call.
  serve      the paged attention decoder (8 heads x 128, 2 layers, vocab
             32000) in a DecodeSession behind ServingHTTPServer: four
             streamed /v1/generate requests over HTTP.
  four_chip  only with >= 4 devices: ResNet-50 at 4 x 256 under
             Module.fit(mesh="all") and under context=[tpu(0..3)], plus
             one serving replica per chip.

Sizes are fixed here, not read from the environment. Every phase prints
the device it ran on and its compile seconds apart from its run seconds
(set-up time, not a metric). The last line of a passing run is one JSON
object {"ok": true, "device": {...}}; nothing of the kind is printed when
JAX finds no TPU, when a phase fails, or in a rehearsal.
"""
import argparse
import collections
import http.client
import json
import logging
import os
import shutil
import sys
import threading
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")   # checkpoint scratch, removed at exit

FULL = {
    "train": dict(layers=50, classes=1000, image=224, batch=256, batches=4,
                  epochs=2),
    "lm": dict(vocab=16384, seq=1024, layers=2, heads=16, d_model=2048,
               batch=8, steps=3),
    "serve": dict(vocab=32000, embed=1024, heads=8, head_dim=128, layers=2,
                  block=16, max_blocks=64, prompts=(32, 200, 77, 150),
                  new_tokens=64, chunk=64),
    "four_chip": dict(layers=50, classes=1000, image=224, batch_per_chip=256,
                      mesh_steps=6, ctx_steps=3),
}
# rehearsal: same code path, sizes a CPU finishes in a minute or two
TOY = {
    "train": dict(layers=18, classes=10, image=32, batch=8, batches=4,
                  epochs=2),
    "lm": dict(vocab=64, seq=64, layers=2, heads=2, d_model=64, batch=2,
               steps=3),
    "serve": dict(vocab=64, embed=32, heads=2, head_dim=8, layers=2,
                  block=4, max_blocks=16, prompts=(6, 30, 11, 23),
                  new_tokens=8, chunk=8),
    "four_chip": dict(layers=18, classes=10, image=32, batch_per_chip=4,
                      mesh_steps=6, ctx_steps=3),
}


def say(msg, *args):
    print("[chip_smoke] " + (msg % args if args else msg), flush=True)


def check(cond, msg, *args):
    """A failed check fails the phase (plain raise: -O must not skip it)."""
    if not cond:
        raise AssertionError(msg % args if args else msg)


class CompileMeter:
    """Every trace/lower/compile JAX does in this process, by program name,
    and what the persistent cache did with it (jax.monitoring events)."""

    def __init__(self):
        import jax
        self.rows = []      # (stage, program name, seconds)
        self.cache = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event.startswith("/jax/core/compile/"):
            self.rows.append((event.rsplit("/", 1)[1],
                              str(kw.get("fun_name", "?")), float(secs)))

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.cache[event.rsplit("/", 1)[1]] += 1

    def mark(self):
        return len(self.rows), collections.Counter(self.cache)

    def since(self, mark):
        n0, cache0 = mark
        rows = self.rows[n0:]
        backend = [(n, s) for st, n, s in rows
                   if st == "backend_compile_duration"]
        cache = self.cache - cache0
        return {
            "compile_s": sum(s for _, _, s in rows),
            "programs": [n for n, _ in backend],
            "under_1s": sum(1 for _, s in backend if s < 1.0),
            "under_1s_s": sum(s for _, s in backend if s < 1.0),
            "cache_hits": cache["cache_hits"],
            "cache_writes": cache["cache_misses"],
        }


def device_line():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def all_on_platform(tree, platform):
    import jax
    for leaf in jax.tree.leaves(tree):
        for d in leaf.devices():
            if d.platform != platform:
                return False
    return True


# ------------------------------------------------------------------ train
def phase_train(cfg, platform, meter):
    import ml_dtypes
    import numpy as np

    import mxtpu as mx
    from mxtpu.models import resnet

    batch, n = cfg["batch"], cfg["batch"] * cfg["batches"]
    shape = (3, cfg["image"], cfg["image"])
    sym = resnet.get_symbol(num_classes=cfg["classes"],
                            num_layers=cfg["layers"], image_shape=shape)
    rng = np.random.RandomState(0)
    x = rng.rand(n, *shape).astype(np.float32).astype(ml_dtypes.bfloat16)
    y = rng.randint(0, cfg["classes"], (n,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=batch, shuffle=False,
                           label_name="softmax_label")
    check(str(it.provide_data[0].dtype) == "bfloat16",
          "iterator did not keep bf16 data: %s", it.provide_data[0].dtype)

    mod = mx.mod.Module(sym, context=mx.tpu(0))
    epoch_marks = []

    def at_epoch_end(epoch, symbol, arg_params, aux_params):
        epoch_marks.append((meter.mark(),
                            len(mx.diagnostics.programs("fused_step"))))

    metric = mx.metric.create(["acc", "ce"])
    syncs0 = mx.telemetry.histogram("fit_metric_sync_ms").count
    mod.fit(it, num_epoch=cfg["epochs"], eval_metric=metric,
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            initializer=mx.initializer.Xavier(
                rnd_type="gaussian", factor_type="in", magnitude=2.0),
            batch_end_callback=mx.callback.Speedometer(batch, 2),
            epoch_end_callback=at_epoch_end)

    check(mod._fused is not None, "the fused train step did not arm")
    check(mx.telemetry.histogram("fit_metric_sync_ms").count > syncs0
          and mx.diagnostics.programs("metric_accum"),
          "fit did not take the device-metric path")
    state = (mod._fused.params, mod._fused.aux, mod._fused.opt_state)
    check(all_on_platform(state, platform),
          "params/aux/optimizer state not all on a %s device", platform)
    train_metric = dict(metric.get_name_value())
    check(all(isinstance(v, float) and np.isfinite(v)
              for v in train_metric.values()),
          "train metric not finite numbers: %s", train_metric)
    check(len(epoch_marks) == cfg["epochs"], "epoch callback ran %d times",
          len(epoch_marks))
    (m1, steps1), (_, steps2) = epoch_marks[-2], epoch_marks[-1]
    epoch2 = meter.since(m1)   # everything after epoch 1's end, epoch 2 incl.
    check(steps1 == 1 and steps2 == 1,
          "fused_step compiled %d then %d times", steps1, steps2)
    check(not [p for p in epoch2["programs"] if "step" in p],
          "epoch 2 recompiled the step: %s", epoch2["programs"])

    args, auxs = mod.get_params()
    bad = [k for k, v in list(args.items()) + list(auxs.items())
           if not np.isfinite(v.asnumpy().astype(np.float32)).all()]
    check(not bad, "non-finite parameters after fit: %s", bad[:5])

    scored = dict(mod.score(it, ["acc", "ce"]))
    check(all(np.isfinite(v) for v in scored.values()),
          "score not finite: %s", scored)

    prefix = os.path.join(WORK, "resnet")
    mod.save_checkpoint(prefix, cfg["epochs"])
    loaded = mx.mod.Module.load(prefix, cfg["epochs"], context=mx.tpu(0))
    loaded.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
                for_training=False)
    want = mod.predict(it, num_batch=1).asnumpy()
    got = loaded.predict(it, num_batch=1).asnumpy()
    check(want.shape == (batch, cfg["classes"]), "predict shape %s",
          want.shape)
    check(np.isfinite(got).all(), "loaded module predicts non-finite values")
    diff = float(np.abs(got - want).max())
    check(diff <= 1e-6, "loaded module differs from trained: max|d|=%g", diff)
    check(all_on_platform(loaded.get_outputs()[0]._data, platform),
          "loaded module's outputs not on %s", platform)
    return {"train_ce": round(train_metric["cross-entropy"], 4),
            "score_ce": round(scored["cross-entropy"], 4),
            "epoch2_compiles": epoch2["programs"],
            "load_predict_maxdiff": diff}


# --------------------------------------------------------------------- lm
def phase_lm(cfg, platform, meter):
    import numpy as np

    import mxtpu as mx
    from mxtpu.models import transformer

    b, t, vocab, steps = cfg["batch"], cfg["seq"], cfg["vocab"], cfg["steps"]
    sym = transformer.get_symbol(vocab, t, num_layers=cfg["layers"],
                                 num_heads=cfg["heads"],
                                 d_model=cfg["d_model"], dtype="bfloat16")
    rng = np.random.RandomState(1)
    tokens = rng.randint(0, vocab, (b, t + 1))
    # the same batch every step: a loss that does not fall on data the
    # model has just been fitted to means the update is wrong
    x = tokens[:, :-1].astype(np.float32)
    y = tokens[:, 1:].astype(np.float32).reshape(-1)

    class SameBatch(mx.io.DataIter):
        """Host iterator a user would write for this symbol: its label is
        flattened to (B*T,), which NDArrayIter cannot express."""
        provide_data = [mx.io.DataDesc("data", (b, t))]
        provide_label = [mx.io.DataDesc("softmax_label", (b * t,))]

        def __init__(self):
            super().__init__(b)
            self.left = steps

        def reset(self):
            self.left = steps

        def next(self):
            if self.left == 0:
                raise StopIteration
            self.left -= 1
            return mx.io.DataBatch(data=[mx.nd.array(x)],
                                   label=[mx.nd.array(y)], pad=0)

    it = SameBatch()
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    running = []   # cumulative mean cross-entropy after each batch

    def after_batch(param):
        running.append(dict(param.eval_metric.get_name_value())
                       ["cross-entropy"])

    mod.fit(it, num_epoch=1, eval_metric="ce", optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9,
                              "rescale_grad": 1.0 / (b * t)},
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.0),
            batch_end_callback=after_batch)
    check(mod._fused is not None, "the fused train step did not arm")
    check(all_on_platform((mod._fused.params, mod._fused.opt_state),
                          platform),
          "LM state not all on a %s device", platform)
    check(len(running) == steps, "%d batch callbacks for %d steps",
          len(running), steps)
    loss = [running[0]] + [running[i] * (i + 1) - running[i - 1] * i
                           for i in range(1, steps)]
    check(all(np.isfinite(v) for v in loss), "LM loss not finite: %s", loss)
    check(loss[-1] < loss[0], "LM loss did not fall: %s", loss)

    rec = mx.diagnostics.latest_record("fused_step")
    hlo = rec.hlo_text() if rec is not None else None
    check(hlo, "no compiled program text for the LM step")
    mosaic = hlo.count("tpu_custom_call")
    if platform == "tpu":
        check(mosaic >= cfg["layers"],
              "LM step holds %d Mosaic calls, want >= %d: the flash kernel "
              "was not compiled into it", mosaic, cfg["layers"])
    return {"loss": [round(v, 4) for v in loss], "mosaic_calls": mosaic}


# ------------------------------------------------------------------ serve
def _stream_generate(host, port, prompt, new_tokens, out, go=None, gate=None):
    """One streamed generation; sets ``go`` once its 8th token arrived so
    a later request joins a batch that is already decoding."""
    if gate is not None:
        gate.wait(120)
    conn = http.client.HTTPConnection(host, port, timeout=600)
    try:
        conn.request("POST", "/v1/generate?stream=1",
                     json.dumps({"prompt": prompt,
                                 "max_new_tokens": new_tokens,
                                 "timeout_sec": 600}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        for line in resp:
            if not line.strip():
                continue
            ev = json.loads(line)
            if "token" in ev:
                out["tokens"].append(ev["token"])
                if go is not None and len(out["tokens"]) == 8:
                    go.set()
            elif "done" in ev:
                out["done"] = ev["done"]
            else:
                out["error"] = ev
    except Exception as exc:   # reported by the phase, which owns the verdict
        out["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        conn.close()
        if go is not None:
            go.set()


def phase_serve(cfg, platform, meter):
    import numpy as np

    from mxtpu.serving import ServingHTTPServer
    from mxtpu.serving.decode import DecodeSession, attn_decode_fixture

    fx = attn_decode_fixture(
        vocab_size=cfg["vocab"], num_embed=cfg["embed"],
        num_heads=cfg["heads"], head_dim=cfg["head_dim"],
        num_layers=cfg["layers"], block_size=cfg["block"],
        max_blocks_per_seq=cfg["max_blocks"], seed=0)
    sess = DecodeSession(fx["step_symbol_json"], fx["params"],
                         fx["step_example_shapes"], [], arena="paged",
                         paged=fx, buckets=(1, 2, 4), slot_capacity=4,
                         prefill_chunk_tokens=cfg["chunk"],
                         prefill_buckets=(cfg["chunk"],),
                         version_tag="chip-smoke")
    server = ServingHTTPServer(None, decode=sess, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        host, port = server.server_address[:2]
        rng = np.random.RandomState(2)
        outs = [{"tokens": []} for _ in cfg["prompts"]]
        go = threading.Event()
        clients = []
        for i, plen in enumerate(cfg["prompts"]):
            prompt = [int(v) for v in rng.randint(0, cfg["vocab"], plen)]
            clients.append(threading.Thread(
                target=_stream_generate,
                args=(host, port, prompt, cfg["new_tokens"], outs[i]),
                kwargs={"go": go} if i == 0 else {"gate": go}))
        for c in clients:
            c.start()
        for c in clients:
            c.join(900)
        check(not any(c.is_alive() for c in clients),
              "a generate request did not finish in 900 s")
        for i, o in enumerate(outs):
            check(o.get("status") == 200 and "error" not in o,
                  "request %d: status %s error %s", i, o.get("status"),
                  o.get("error"))
            check(len(o["tokens"]) == cfg["new_tokens"] and "done" in o,
                  "request %d: %d token events, done=%s", i,
                  len(o["tokens"]), "done" in o)
            check(o["done"]["tokens"] == o["tokens"]
                  and all(0 <= t < cfg["vocab"] for t in o["tokens"]),
                  "request %d: streamed tokens disagree with the result", i)
        joins = [o["done"]["join_step"] for o in outs]
        check(max(joins) > min(joins),
              "no request joined a running batch: join steps %s", joins)

        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        conn.close()
        check(resp.status == 200 and health.get("status") == "ok",
              "healthz: %s %s", resp.status, health)

        panel = sess.debug_panel()
        check(panel["prefill"]["chunks"] > len(cfg["prompts"]),
              "prefill was not chunked: %s", panel["prefill"])
        check(all_on_platform(sess.arena._arrays, platform),
              "KV arena not on a %s device", platform)
        check(sess._contexts[0].jax_device.platform == platform,
              "decode step bound to %s", sess._contexts[0].jax_device)
        check(panel["kv"]["blocks_free"] == panel["kv"]["blocks_total"],
              "KV blocks leaked after drain: %s", panel["kv"])
        return {"join_steps": joins, "steps": panel["steps"],
                "prefill_chunks": panel["prefill"]["chunks"],
                "kv_blocks": panel["kv"]["blocks_total"]}
    finally:
        server.shutdown()
        server.server_close()
        thread.join(30)


# -------------------------------------------------------------- four_chip
def _resnet_iter(cfg, n_dev, steps_per_epoch):
    import ml_dtypes
    import numpy as np

    import mxtpu as mx
    gbatch = cfg["batch_per_chip"] * n_dev
    shape = (3, cfg["image"], cfg["image"])
    rng = np.random.RandomState(3)
    x = rng.rand(gbatch * steps_per_epoch, *shape).astype(
        np.float32).astype(ml_dtypes.bfloat16)
    y = rng.randint(0, cfg["classes"],
                    (gbatch * steps_per_epoch,)).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=gbatch, shuffle=False,
                             label_name="softmax_label"), gbatch


def _per_device_bytes(tree):
    import jax
    per = collections.Counter()
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            per[s.device.id] += s.data.nbytes
    return per


def phase_four_chip(cfg, platform, meter):
    import jax
    import numpy as np

    import mxtpu as mx
    from mxtpu.models import resnet
    from mxtpu.models.serving_fixtures import get_fixture
    from mxtpu.serving import ServingSession

    devices = jax.devices()
    n_dev = len(devices)
    sym = resnet.get_symbol(
        num_classes=cfg["classes"], num_layers=cfg["layers"],
        image_shape=(3, cfg["image"], cfg["image"]))
    fit_kw = dict(
        eval_metric=["acc", "ce"], optimizer="sgd",
        initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2.0))

    # (a) Module.fit(mesh="all"): SPMD step, weight-update sharding
    it, gbatch = _resnet_iter(cfg, n_dev, 2)
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    mod.fit(it, num_epoch=cfg["mesh_steps"] // 2, mesh="all",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / gbatch},
            batch_end_callback=mx.callback.Speedometer(gbatch, 2), **fit_kw)
    check(mod._fused is not None and mod._fused._plan is not None,
          "fit(mesh='all') declined the mesh")
    few = [n for n, v in mod._fused.params.items()
           if len(v.sharding.device_set) != n_dev]
    check(not few, "parameters not on all %d devices: %s", n_dev, few[:5])
    opt = _per_device_bytes(mod._fused.opt_state)
    opt_total = sum(x.nbytes for x in jax.tree.leaves(mod._fused.opt_state))
    share0 = opt[devices[0].id] / float(opt_total)
    check(abs(share0 * n_dev - 1.0) <= 0.3,
          "optimizer-state share on chip 0 is %.3f, want about 1/%d",
          share0, n_dev)
    in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in devices} \
        if platform == "tpu" else {}
    check(all(v > 0 for v in in_use.values()),
          "a chip holds nothing under mesh='all': %s", in_use)
    mesh_ce = dict(mod.score(it, "ce"))["cross-entropy"]
    check(np.isfinite(mesh_ce), "mesh fit: cross-entropy %s", mesh_ce)
    del mod

    # (b) the reference idiom: one context per chip
    it, gbatch = _resnet_iter(cfg, n_dev, cfg["ctx_steps"])
    mod = mx.mod.Module(sym, context=[mx.tpu(i) for i in range(n_dev)])
    mod.fit(it, num_epoch=1,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / gbatch}, **fit_kw)
    check(mod._fused is not None, "context-list fit did not arm the fused "
          "step")
    few = [n for n, v in mod._fused.params.items()
           if len(v.sharding.device_set) != n_dev]
    check(not few, "context-list params not on all devices: %s", few[:5])
    ctx_ce = dict(mod.score(it, "ce"))["cross-entropy"]
    check(np.isfinite(ctx_ce), "context-list fit: cross-entropy %s", ctx_ce)
    del mod

    # (c) serving: one replica per chip in this one process, each answers
    sym_json, params, shapes = get_fixture("resnet")
    sess = ServingSession(sym_json, params, shapes, buckets=(1, 2, 4))
    try:
        check(len(sess.pool.replicas) == n_dev, "%d serving replicas",
              len(sess.pool.replicas))
        rng = np.random.RandomState(4)
        name, shape = next(iter(shapes.items()))
        xs = rng.rand(16, *shape).astype(np.float32)
        bad = []

        def client(x):
            for _ in range(8):
                out = sess.predict({name: x}, timeout=300)
                if not np.isfinite(np.asarray(out[0])).all():
                    bad.append("non-finite answer")

        served = {}
        for _ in range(5):   # workers race for the queue: a few rounds
            threads = [threading.Thread(target=client, args=(x,))
                       for x in xs]
            for th in threads:
                th.start()
            for th in threads:
                th.join(600)
            served = {str(rep.ctx): sum(n for n, _ in
                                        sess._bucket_service[i].values())
                      for i, rep in enumerate(sess.pool.replicas)}
            if all(served.values()):
                break
        check(not bad, "serving: %s", bad[:3])
        check(len(served) == n_dev and all(served.values()),
              "batches retired per replica: %s", served)
    finally:
        sess.close()
    return {"opt_state_share_chip0": round(share0, 4),
            "bytes_in_use": in_use, "mesh_ce": round(mesh_ce, 4),
            "ctx_ce": round(ctx_ce, 4), "replica_batches": served}


# ------------------------------------------------------------------- main
PHASES = (("train", phase_train), ("lm", phase_lm), ("serve", phase_serve))


def run_phase(name, fn, cfg, dev, meter):
    mark, t0 = meter.mark(), time.perf_counter()
    try:
        detail, ok = fn(cfg, dev["platform"], meter), True
    except Exception:   # the phase failed; later phases still run and report
        traceback.print_exc()
        detail, ok = {}, False
    wall = time.perf_counter() - t0
    c = meter.since(mark)
    say("phase=%s %s platform=%s device_kind=%s count=%d compile_s=%.1f "
        "run_s=%.1f compiles=%d (%d under 1 s: %.1f s) cache_hits=%d "
        "cache_writes=%d %s", name, "pass" if ok else "FAIL",
        dev["platform"], dev["kind"], dev["count"], c["compile_s"],
        max(0.0, wall - c["compile_s"]), len(c["programs"]), c["under_1s"],
        c["under_1s_s"], c["cache_hits"], c["cache_writes"],
        json.dumps(detail))
    return ok


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU only, toy sizes: checks the script, proves "
                         "nothing about the chip, prints no result line")
    args = ap.parse_args()

    import jax
    dev = device_line()
    if args.rehearsal:
        if dev["platform"] != "cpu":
            sys.exit("chip_smoke: --rehearsal is for a CPU-only host "
                     "(JAX_PLATFORMS=cpu); found platform=%s"
                     % dev["platform"])
    elif dev["platform"] != "tpu":
        sys.exit("chip_smoke: no TPU: jax.devices()[0].platform == %r "
                 "(device_kind %r). Nothing was run."
                 % (dev["platform"], dev["kind"]))
    sizes = TOY if args.rehearsal else FULL

    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(levelname)s %(message)s")
    from importlib import metadata

    import mxtpu as mx
    from mxtpu._native import native_available
    meter = CompileMeter()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "absent"
    say("%splatform=%s device_kind=%s count=%d jax=%s jaxlib=%s libtpu=%s",
        "REHEARSAL " if args.rehearsal else "", dev["platform"], dev["kind"],
        dev["count"], jax.__version__, metadata.version("jaxlib"), libtpu)
    say("compile cache: %s (JAX_COMPILATION_CACHE_DIR %s)",
        jax.config.jax_compilation_cache_dir or "off",
        "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset")
    say("native runtime available: %s", native_available())

    os.makedirs(WORK, exist_ok=True)
    failed = []
    try:
        for name, fn in PHASES:
            if not run_phase(name, fn, sizes[name], dev, meter):
                failed.append(name)
        if dev["count"] >= 4:
            if not run_phase("four_chip", phase_four_chip,
                             sizes["four_chip"], dev, meter):
                failed.append("four_chip")
        else:
            say("four_chip: not run (%d device%s)", dev["count"],
                "" if dev["count"] == 1 else "s")
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
        mx.nd.waitall()

    if failed:
        say("FAILED phases: %s", ", ".join(failed))
        sys.exit(1)
    if args.rehearsal:
        say("REHEARSAL platform=%s: every phase ran at toy size. This is "
            "not a chip result.", dev["platform"])
        return
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
