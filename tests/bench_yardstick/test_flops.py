"""The functions that count a configuration's operations and bytes, against
hand counts and XLA's own cost analysis of the plain reference."""
import json
import os

import pytest

from benchmark import manifest

BENCH = os.path.dirname(manifest.__file__)


def _config(name):
    return manifest.read_json(os.path.join(BENCH, "configs", name, "config.json"))


def _flops(name):
    return manifest.load_module(os.path.join(BENCH, "configs", name, "flops.py"),
                                "flops_" + name.replace("-", "_").replace(".", "_"))


def test_resnet50_macs_by_hand():
    cfg = _config("resnet50")
    f = _flops("resnet50")
    macs = f.forward_macs_per_image(cfg)
    # conv0: 64 x 3 x 7 x 7 at 112 x 112
    assert 64 * 3 * 49 * 112 * 112 == 118013952
    # the well-known count of the 50-layer net is about 4.1 G multiply-adds
    assert macs == pytest.approx(4.089e9, rel=0.01)
    assert f.train_flops_per_item(cfg, {}) == 6 * macs


def test_opt_train_flops_by_hand():
    cfg = _config("opt-1.3b-train")
    f = _flops("opt-1.3b-train")
    d, ff, v, n = 2048, 8192, 50272, cfg["num_hidden_layers"]
    layer = 2 * (4 * d * d + 2 * d * ff) + 4 * d * 512.5
    assert f.forward_flops_per_token(cfg, 1024) == n * layer + 2 * v * d
    assert f.train_flops_per_item(cfg, {"seq_len": 1024}) == \
        3 * (n * layer + 2 * v * d)
    flops, bytes_ = f.flash_fwd(cfg, {"seq_len": 1024}, 4)
    assert flops == 4 * 32 * 4 * 64 * 1024 * 1025 / 2
    assert bytes_ == 4 * 4 * 32 * 1024 * 64 * 2
    # at head size 64 the flops bound the kernel, not the bytes
    assert flops / 197e12 > bytes_ / 819e9


def test_opt_serve_flops_and_bytes_by_hand():
    cfg = _config("opt-1.3b")
    f = _flops("opt-1.3b")
    d, ff, v, n = 2048, 8192, 50272, 24
    assert f.flops_per_token(cfg, 0) == n * 2 * (4 * d * d + 2 * d * ff) + 2 * v * d
    assert f.flops_per_token(cfg, 100, head=False) == \
        n * (2 * (4 * d * d + 2 * d * ff) + 4 * d * 100)
    # 1.42 G parameters in bf16, 0.40 GB of KV for 2048 positions
    assert f.weight_bytes(cfg) == pytest.approx(2.84e9, rel=0.01)
    assert 2048 * f.kv_bytes_per_token(cfg) == 24 * 2 * 2048 * 2048 * 2


def test_opt_flops_against_xla_cost_analysis():
    import jax
    import jax.numpy as jnp
    from benchmark.references import opt
    cfg = dict(_config("opt-1.3b-train"), hidden_size=128, ffn_dim=512,
               num_attention_heads=2, num_hidden_layers=2, vocab_size=1024,
               max_position_embeddings=64)
    f = _flops("opt-1.3b-train")
    t = 64
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _ in opt.param_specs(cfg)}
    tokens = jax.ShapeDtypeStruct((2, t), jnp.int32)
    cost = jax.jit(lambda p, x: opt.forward(p, x, cfg, remat=False)) \
        .lower(shapes, tokens).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # XLA counts full (not causal) attention, the softmax and the norms
    full_attention = 2 * t * 4 * cfg["hidden_size"] * (t - 1) / 2 * 2
    mine = 2 * t * f.forward_flops_per_token(cfg, t) + full_attention
    assert cost["flops"] == pytest.approx(mine, rel=0.1)


def test_resnet_flops_against_xla_cost_analysis():
    import jax
    import jax.numpy as jnp
    from benchmark.references import resnet
    cfg = _config("resnet50")   # full size: at small images XLA's count
    f = _flops("resnet50")      # drops the padded taps of the 3x3 borders
    shapes = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s, _ in resnet.param_specs(cfg)}
    images = jax.ShapeDtypeStruct((1, 3, 224, 224), jnp.float32)
    cost = jax.jit(lambda p, x: resnet.forward(p, x, cfg, remat=False)) \
        .lower(shapes, images).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] == pytest.approx(
        2 * f.forward_macs_per_image(cfg), rel=0.05)
