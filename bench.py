#!/usr/bin/env python
"""Benchmark: ResNet-50 ImageNet-shape training throughput via Module.fit
(the BASELINE.json metric: images/sec/chip + MFU on the Module.fit path).

The whole step — forward, backward, optimizer — runs as the Module's fused
one-program train step (mxtpu/module/fused.py), bf16 end to end. Baseline:
the reference's published 109 img/s ResNet-50 train on 1x K80
(example/image-classification/README.md:147-156).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "device",
...}. Needs an accelerator: with none, or when any phase raises, it exits
non-zero and prints no result. MFU method: flops/img = 3 x 2 x 4.089e9 (fwd
MACs x2, backward ~2x fwd; matches XLA's own cost analysis within 2%) over
the device's peak from PEAK_TFLOPS.

One process: nothing here starts a child that needs the chip.
"""
import json
import os
import sys
import time

import numpy as np

FLOPS_PER_IMG = 3 * 2 * 4.089e9
# bf16 peak per chip by jax device_kind. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16). A kind not listed is an error, not a default.
PEAK_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5e": 197.0}
METRIC = "resnet50_module_fit_throughput_per_chip"


def require_chip():
    """(device description, bf16 peak TFLOP/s) of the accelerator this
    process runs on; exits non-zero without one or without its peak."""
    import jax
    dev0 = jax.devices()[0]
    if dev0.platform == "cpu":
        raise SystemExit("bench: no accelerator (jax platform %r); a CPU "
                         "run measures nothing this benchmark reports"
                         % dev0.platform)
    if dev0.device_kind not in PEAK_TFLOPS:
        raise SystemExit("bench: no peak FLOP/s on record for device_kind "
                         "%r; add it to PEAK_TFLOPS with its source"
                         % (dev0.device_kind,))
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices())}
    return device, PEAK_TFLOPS[dev0.device_kind]


class _DeviceBatchIter:
    """Serves one pre-staged device-resident batch `n` times per epoch:
    isolates the model path (input pipeline is benched separately by
    tools/bench_input.py)."""

    def __init__(self, batch, n, provide_data, provide_label):
        self._batch = batch
        self._n = n
        self._i = 0
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.batch_size = provide_data[0].shape[0]

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        return self._batch

    next = __next__


class _CappedRecIter:
    """Serve exactly `n` batches from a (smaller) recordio iterator, cycling
    epochs transparently and casting data to the bound bf16 dtype on the
    host so the device transfer ships half the bytes."""

    def __init__(self, it, n, provide_data, provide_label):
        self._it = iter(it)
        self._src = it
        self._n = n
        self._i = 0
        self.provide_data = provide_data
        self.provide_label = provide_label
        self.batch_size = provide_data[0].shape[0]

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        import mxtpu as mx
        import ml_dtypes
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        try:
            b = next(self._it)
        except StopIteration:
            self._src.reset()
            self._it = iter(self._src)
            b = next(self._it)
        data = [mx.nd.array(d.asnumpy().astype(ml_dtypes.bfloat16))
                for d in b.data]
        return mx.io.DataBatch(data=data, label=b.label, pad=b.pad,
                               index=b.index, provide_data=self.provide_data,
                               provide_label=self.provide_label)

    next = __next__


def _bench_recordio(mod, batch, pdata, plabel, synth_img_per_sec):
    """VERDICT r3 next #3: the same Module.fit step fed by the real
    ImageRecordIter path (packed .rec -> host JPEG decode+augment ->
    device), reported alongside the synthetic number. The .rec is built
    from a seed on first use under the git-ignored build/ directory;
    decode threads default to the host's cores."""
    import mxtpu as mx
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    import bench_input

    n_img = int(os.environ.get("BENCH_REC_IMAGES", 1024))
    rec_path = os.path.join(here, "build", "bench_%dx256.rec" % n_img)
    if not os.path.exists(rec_path):
        os.makedirs(os.path.dirname(rec_path), exist_ok=True)
        bench_input.make_rec(rec_path, n_img, edge=256)
    threads = int(os.environ.get("BENCH_INPUT_DECODE_THREADS",
                                 os.cpu_count() or 4))
    rec_iters = int(os.environ.get("BENCH_REC_ITERS", 12))
    it = mx.io.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 224, 224), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True,
        preprocess_threads=threads, prefetch_buffer=8)
    warm = _CappedRecIter(it, 2, pdata, plabel)
    mod.fit(warm, num_epoch=1, eval_metric=_null_metric(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            force_init=False, begin_epoch=0)
    _finish(mod)
    # fresh epoch so the timed window starts with an empty prefetch buffer
    # (otherwise batches decoded during the untimed warm/sync gap inflate
    # the short measurement window)
    it.reset()
    timed = _CappedRecIter(it, rec_iters, pdata, plabel)
    t0 = time.perf_counter()
    mod.fit(timed, num_epoch=1, eval_metric=_null_metric(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / batch},
            force_init=False, begin_epoch=0)
    _finish(mod)
    dt = time.perf_counter() - t0
    rate = batch * rec_iters / dt
    return {"recordio_img_per_sec": round(rate, 2),
            "recordio_vs_synthetic": round(rate / synth_img_per_sec, 3)
            if synth_img_per_sec else None,
            "recordio_decode_threads": threads,
            "recordio_iters": rec_iters}


def _bench_dp_scaling(batch, iters):
    """SPMD data-parallel scaling entry: the same fused ResNet-50 step
    trained across ALL local devices via ``Module.fit(mesh=...)`` —
    batch per chip held at ``batch``, so ideal scaling is flat step time
    at n× the samples. Reports img/s/chip vs the single-chip headline
    plus the cross-replica weight-update sharding memory split (per-chip
    optimizer bytes / total) from the diagnostics ledger, which is exact
    on any backend."""
    import jax
    import jax.numpy as jnp
    import mxtpu as mx
    from mxtpu.models import resnet

    n_dev = len(jax.local_devices())
    if n_dev < 2:
        return {"dp_scaling": {"skipped": "single local device"}}
    gbatch = batch * n_dev
    mctx = mx.sharding.MeshContext.create("all")
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    pdata = [mx.io.DataDesc("data", (gbatch, 3, 224, 224),
                            dtype="bfloat16")]
    plabel = [mx.io.DataDesc("softmax_label", (gbatch,), dtype="float32")]
    rng = np.random.RandomState(0)
    from jax.sharding import PartitionSpec as P
    data = jax.device_put(
        jnp.asarray(rng.rand(gbatch, 3, 224, 224).astype("float32"),
                    dtype=jnp.bfloat16), mctx.sharding(P("data")))
    label = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (gbatch,)).astype("float32")),
        mctx.sharding(P("data")))
    batch_obj = mx.io.DataBatch(
        data=[mx.nd.NDArray(data)], label=[mx.nd.NDArray(label)],
        pad=0, index=None, provide_data=pdata, provide_label=plabel)
    opt_kw = {"learning_rate": 0.1, "momentum": 0.9,
              "rescale_grad": 1.0 / gbatch}
    warm = _DeviceBatchIter(batch_obj, 3, pdata, plabel)
    mod.fit(warm, num_epoch=1, eval_metric=_null_metric(),
            optimizer="sgd", optimizer_params=opt_kw, mesh=mctx)
    _finish(mod)
    if mod._fused._plan is None:
        raise RuntimeError("dp_scaling: fit declined the mesh (see fit log)")
    timed = _DeviceBatchIter(batch_obj, iters, pdata, plabel)
    t0 = time.perf_counter()
    mod.fit(timed, num_epoch=1, eval_metric=_null_metric(),
            optimizer="sgd", optimizer_params=opt_kw,
            force_init=False, begin_epoch=0, mesh=mctx)
    _finish(mod)
    dt = time.perf_counter() - t0
    img_per_sec = gbatch * iters / dt
    opt_total = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        mod._fused.opt_state))
    per_chip = {}
    for x in jax.tree_util.tree_leaves(mod._fused.opt_state):
        for s in x.addressable_shards:
            per_chip[s.device.id] = per_chip.get(s.device.id, 0) + \
                s.data.nbytes
    chip0 = per_chip.get(min(per_chip), opt_total) if per_chip else 0
    return {"dp_scaling": {
        "n_devices": n_dev,
        "global_batch": gbatch,
        "img_per_sec_total": round(img_per_sec, 2),
        "img_per_sec_per_chip": round(img_per_sec / n_dev, 2),
        "opt_state_bytes_total": opt_total,
        "opt_state_bytes_per_chip": chip0,
        "opt_state_per_chip_frac": round(chip0 / opt_total, 4)
        if opt_total else None,
        "path": "Module.fit(mesh=all) — SPMD fused step, weight-update "
                "sharding (docs/sharding.md)"}}


def _finish(mod):
    """End a timed window on completed work: fit dispatches asynchronously,
    so wait for the last step's parameters."""
    import jax
    jax.block_until_ready(mod._fused.params)


def _null_metric():
    """No-op metric: the timed window measures the train step alone."""
    import mxtpu as mx

    class _Null(mx.metric.EvalMetric):
        def __init__(self):
            super().__init__("null")

        def update(self, labels, preds):
            pass

    return _Null()


def _parse_tuned_arg():
    """``--tuned <artifact>``: run the bench under a TunedConfig
    (docs/tune.md) — the ROADMAP's real-TPU re-measurement path. The
    artifact's knobs (fit in-flight depth, metric-sync cadence, batch
    size via ``fit.batch_size``) apply with the usual precedence, so
    explicit BENCH_* env settings still win where they map to knobs."""
    argv = sys.argv[1:]
    if "--tuned" in argv:
        i = argv.index("--tuned")
        if i + 1 >= len(argv):
            sys.stderr.write("bench: --tuned needs an artifact path\n")
            sys.exit(2)
        return argv[i + 1]
    return os.environ.get("BENCH_TUNED") or None


def _bench_pipeline_catalog(batch, iters, peak):
    """Full-transform-catalog companion entry (ISSUE 14): the same fused
    ResNet-50 step built under the complete compile pipeline
    (bf16,fuse_opt,layout,remat_reuse)."""
    catalog = "bf16,fuse_opt,layout,remat_reuse"
    import jax
    import jax.numpy as jnp

    import mxtpu as mx
    from mxtpu.compile import pipeline as _pipe
    from mxtpu.models import resnet

    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    ctx = mx.tpu(0)
    pdata = [mx.io.DataDesc("data", (batch, 3, 224, 224),
                            dtype="bfloat16")]
    plabel = [mx.io.DataDesc("softmax_label", (batch,),
                             dtype="float32")]
    rng = np.random.RandomState(0)
    dev = ctx.jax_device
    data = jax.device_put(
        jnp.asarray(rng.rand(batch, 3, 224, 224).astype("float32"),
                    dtype=jnp.bfloat16), dev)
    label = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,)).astype("float32")),
        dev)
    batch_obj = mx.io.DataBatch(
        data=[mx.nd.NDArray(data)], label=[mx.nd.NDArray(label)],
        pad=0, index=None, provide_data=pdata, provide_label=plabel)
    opt_params = {"learning_rate": 0.1, "momentum": 0.9,
                  "rescale_grad": 1.0 / batch}
    with _pipe.pipeline_scope(catalog.split(",")):
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=pdata, label_shapes=plabel)
        mod.init_params(mx.initializer.Xavier(
            rnd_type="gaussian", factor_type="in", magnitude=2.0))
        mod.init_optimizer(optimizer="sgd", optimizer_params=opt_params)
        warm = _DeviceBatchIter(batch_obj, 3, pdata, plabel)
        mod.fit(warm, num_epoch=1, eval_metric=_null_metric(),
                optimizer="sgd", optimizer_params=opt_params,
                force_init=False, begin_epoch=0)
        _finish(mod)
        timed = _DeviceBatchIter(batch_obj, iters, pdata, plabel)
        t0 = time.perf_counter()
        mod.fit(timed, num_epoch=1, eval_metric=_null_metric(),
                optimizer="sgd", optimizer_params=opt_params,
                force_init=False, begin_epoch=0)
        _finish(mod)
        dt = time.perf_counter() - t0
    rep = mod._fused.pipeline_report
    per_chip = batch * iters / dt
    return {"pipeline_catalog": {
        "pipeline": catalog,
        "applied": list(rep.applied) if rep else [],
        "rejected": list(rep.rejected) if rep else [],
        "img_per_sec_per_chip": round(per_chip, 2),
        "mfu": round(per_chip * FLOPS_PER_IMG / (peak * 1e12), 4)}}


def _bench_decode_serving():
    """Stateful decode companion entry (ISSUE 15): tokens/s of the
    continuous decode loop at full arena occupancy."""
    import threading

    from mxtpu.serving.decode import DecodeSession, lm_decode_fixture

    sym_json, params, shapes, state_names, meta = lm_decode_fixture(
        vocab_size=64, num_embed=32, num_hidden=128, num_layers=2)
    # admission=None: this measures raw device throughput at a
    # saturated arena, so the length-aware policy must not shed the
    # deliberate 2x oversubscription out from under the measurement
    sess = DecodeSession(sym_json, params, shapes, state_names,
                         buckets=(1, 4, 8), admission=None)
    try:
        # saturate the arena, measure the steady per-token rate
        outcomes = []

        def run():
            try:
                sess.generate([2, 3, 5, 7], max_new_tokens=64,
                              timeout=120)
                outcomes.append("ok")
            except Exception as e:  # noqa: BLE001
                outcomes.append(type(e).__name__)

        ts = [threading.Thread(target=run)
              for _ in range(sess.slot_capacity * 2)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        dt = time.perf_counter() - t0
        stragglers = sum(t.is_alive() for t in ts)
        tokens = int(sess.metrics.counter("decode_tokens_total").value)
        steps = int(sess.metrics.counter("decode_steps_total").value)
        return {"decode_serving": {
            "model": "lstm_lm_step(vocab=64,hidden=128,layers=2)",
            "sequences": len(ts),
            "completed": outcomes.count("ok"),
            "failed": len(outcomes) - outcomes.count("ok"),
            # threads still decoding at the join deadline: the counters
            # below are a mid-run snapshot when this is nonzero
            "stragglers": stragglers,
            "tokens": tokens,
            "steps": steps,
            "tokens_per_step": round(tokens / steps, 3) if steps else 0.0,
            "tokens_per_sec": round(tokens / dt, 2) if dt else 0.0,
        }}
    finally:
        sess.close()


def main():
    tuned_path = _parse_tuned_arg()
    import jax
    import jax.numpy as jnp

    device, peak = require_chip()

    import mxtpu as mx
    from mxtpu.models import resnet

    if tuned_path:
        # install the artifact process-wide: Module.fit resolves its
        # pipeline knobs through it below with zero per-call plumbing
        mx.tune.use(tuned_path)
    if mx.tune.active() is not None and not tuned_path:
        tuned_path = "ambient:MXTPU_TUNED"
    batch_default = mx.tune.resolve("fit.batch_size") or 256
    batch = int(float(os.environ.get("BENCH_BATCH", batch_default)))
    iters = int(float(os.environ.get("BENCH_ITERS", 60)))

    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224))
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    pdata = [mx.io.DataDesc("data", (batch, 3, 224, 224), dtype="bfloat16")]
    plabel = [mx.io.DataDesc("softmax_label", (batch,), dtype="float32")]
    mod.bind(data_shapes=pdata, label_shapes=plabel)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          factor_type="in", magnitude=2.0))
    opt_params = {"learning_rate": 0.1, "momentum": 0.9,
                  "rescale_grad": 1.0 / batch}
    mod.init_optimizer(optimizer="sgd", optimizer_params=opt_params)
    if mod._fused is None:
        raise SystemExit("bench: the fused Module step did not arm")

    rng = np.random.RandomState(0)
    dev = mod._context[0].jax_device
    data = jax.device_put(
        jnp.asarray(rng.rand(batch, 3, 224, 224).astype("float32"),
                    dtype=jnp.bfloat16), dev)
    label = jax.device_put(
        jnp.asarray(rng.randint(0, 1000, (batch,)).astype("float32")), dev)
    batch_obj = mx.io.DataBatch(
        data=[mx.nd.NDArray(data)], label=[mx.nd.NDArray(label)],
        pad=0, index=None, provide_data=pdata, provide_label=plabel)
    fit_kw = dict(num_epoch=1, eval_metric=_null_metric(), optimizer="sgd",
                  optimizer_params=opt_params, force_init=False,
                  begin_epoch=0)

    # warmup epoch: compile + first steps
    mod.fit(_DeviceBatchIter(batch_obj, 3, pdata, plabel), **fit_kw)
    _finish(mod)

    timed = _DeviceBatchIter(batch_obj, iters, pdata, plabel)
    t0 = time.perf_counter()
    mod.fit(timed, **fit_kw)
    _finish(mod)
    dt = time.perf_counter() - t0

    per_chip = batch * iters / dt   # the Module binds one context
    baseline = 109.0  # K80 img/s, BASELINE.md
    out = {
        "metric": METRIC,
        "value": round(per_chip, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(per_chip / baseline, 3),
        "mfu": round(per_chip * FLOPS_PER_IMG / (peak * 1e12), 4),
        "mfu_method": "flops/img=3*2*4.089e9, peak=%.0fTF bf16" % peak,
        "device": device,
        "path": "Module.fit (fused one-program step, bf16)"}
    if tuned_path:
        out["tuned"] = tuned_path
    # companion entries: a failure in one fails the run (no error notes)
    if os.environ.get("BENCH_RECORDIO", "1") != "0":
        out.update(_bench_recordio(mod, batch, pdata, plabel, per_chip))
    if os.environ.get("BENCH_DP", "1") != "0":
        dp = _bench_dp_scaling(batch, max(8, iters // 4))
        dp_chip = dp["dp_scaling"].get("img_per_sec_per_chip")
        if dp_chip:
            dp["dp_scaling"]["scaling_vs_1chip"] = round(
                dp_chip / per_chip, 3)
        out.update(dp)
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        out.update(_bench_pipeline_catalog(batch, max(8, iters // 4), peak))
    if os.environ.get("BENCH_DECODE", "1") != "0":
        out.update(_bench_decode_serving())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
