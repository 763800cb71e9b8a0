#!/usr/bin/env python
"""Transformer-LM training MFU through the Module.fit driver path
(VERDICT r4 next #3: prove >=70% MFU is reachable by the framework on a
matmul-dominated workload — conv-train's roofline caps near ~55-60% on
v5e, so the MFU north star is demonstrated on the LM).

Same harness as bench.py (imported: one process, no child that needs
the chip): fused one-program Module step, bf16, timed windows that end on
block_until_ready, no accelerator -> non-zero exit. FLOPs model is the
standard dense-LM count 6*P*tokens (P = non-embedding-output matmul
params) plus the causal-attention term 12*L*B*T^2*D/2, over the device's
peak from bench.PEAK_TFLOPS.

Prints ONE JSON line {"metric": "transformer_lm_mfu", ...}.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import bench as _bench
    import jax
    import jax.numpy as jnp

    device, peak = _bench.require_chip()

    import mxtpu as mx
    from mxtpu.models import transformer

    # matmul-dominated size: ~0.4B params, 8k tokens/step
    batch = int(os.environ.get("TBENCH_BATCH", 8))
    seq = int(os.environ.get("TBENCH_SEQ", 1024))
    d_model = int(os.environ.get("TBENCH_DMODEL", 2048))
    layers = int(os.environ.get("TBENCH_LAYERS", 8))
    heads = int(os.environ.get("TBENCH_HEADS", 16))
    vocab = int(os.environ.get("TBENCH_VOCAB", 16384))
    iters = int(os.environ.get("TBENCH_ITERS", 20))

    sym = transformer.get_symbol(vocab, seq, num_layers=layers,
                                 num_heads=heads, d_model=d_model,
                                 dtype="bfloat16")
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    pdata = [mx.io.DataDesc("data", (batch, seq), dtype="float32")]
    plabel = [mx.io.DataDesc("softmax_label", (batch * seq,),
                             dtype="float32")]
    mod.bind(data_shapes=pdata, label_shapes=plabel)
    mod.init_params(mx.initializer.Xavier(factor_type="in", magnitude=2.0))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    assert mod._fused is not None, "fused step must arm"

    rng = np.random.RandomState(0)
    dev = mod._context[0].jax_device
    data = jax.device_put(jnp.asarray(
        rng.randint(0, vocab, (batch, seq)).astype("float32")), dev)
    label = jax.device_put(jnp.asarray(
        rng.randint(0, vocab, (batch * seq,)).astype("float32")), dev)
    batch_obj = mx.io.DataBatch(
        data=[mx.nd.NDArray(data)], label=[mx.nd.NDArray(label)],
        pad=0, index=None, provide_data=pdata, provide_label=plabel)

    warm = _bench._DeviceBatchIter(batch_obj, 3, pdata, plabel)
    fit_kw = dict(eval_metric=_bench._null_metric(), optimizer="sgd",
                  optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                                    "rescale_grad": 1.0 / batch},
                  force_init=False, begin_epoch=0)
    mod.fit(warm, num_epoch=1, **fit_kw)
    _bench._finish(mod)

    timed = _bench._DeviceBatchIter(batch_obj, iters, pdata, plabel)
    t0 = time.perf_counter()
    mod.fit(timed, num_epoch=1, **fit_kw)
    _bench._finish(mod)
    dt = time.perf_counter() - t0

    # 6*P*tokens: P = every matmul param incl. embedding-as-output head
    d_ff = 4 * d_model
    per_layer = 4 * d_model * d_model + 2 * d_model * d_ff
    p_matmul = layers * per_layer + vocab * d_model  # + lm_head
    tokens = batch * seq
    flops_dense = 6 * p_matmul * tokens
    # causal attention: fwd 2*2*B*H*T^2*dh /2 (causal), bwd ~2x
    flops_attn = 6 * layers * batch * seq * seq * d_model // 2
    flops_step = flops_dense + flops_attn
    step_t = dt / iters
    tflops = flops_step / step_t / 1e12
    mfu = tflops / peak
    out = {
        "metric": "transformer_lm_mfu",
        "value": round(mfu, 4),
        "unit": "mfu",
        "tokens_per_sec": round(tokens / step_t, 1),
        "tflops_per_sec": round(tflops, 1),
        "config": {"batch": batch, "seq": seq, "d_model": d_model,
                   "layers": layers, "heads": heads, "vocab": vocab},
        "flops_model": "6*P_matmul*tokens + causal attn 6*L*B*T^2*D/2, "
                       "peak=%.0fTF bf16" % peak,
        "device": device,
        "path": "Module.fit (fused one-program step, bf16, "
                "flash attention)"}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
