"""Plain reference of the pre-activation ResNet for ImageNet shapes (He et
al., arXiv:1512.03385 table 1 for the depths and widths; the unit is the
pre-activation one of upstream MXNet's
example/image-classification/symbols/resnet.py, which mxtpu.models.resnet
follows, without that file's leading `bn_data`). Straightforward jax.numpy in
float32 with `highest` precision; batch normalisation uses the batch's own
statistics (biased variance), as training does. Imports nothing of mxtpu.
"""
import functools

import jax
import jax.numpy as jnp

from . import common

UNITS = {18: [2, 2, 2, 2], 34: [3, 4, 6, 3], 50: [3, 4, 6, 3],
         101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}
EPS = 2e-5


def _plan(cfg):
    depth = cfg["num_layers"]
    bottle = depth >= 50
    filters = [64, 256, 512, 1024, 2048] if bottle else [64, 64, 128, 256, 512]
    return UNITS[depth], filters, bottle


def param_specs(cfg):
    units, filters, bottle = _plan(cfg)
    specs = [("conv0_weight", (filters[0], 3, 7, 7), "he")]

    def bn(name, c):
        return [(name + "_gamma", (c,), "ones"), (name + "_beta", (c,), "zeros")]

    specs += bn("bn0", filters[0])
    cin = filters[0]
    for i, n in enumerate(units):
        cout = filters[i + 1]
        for j in range(n):
            u = "stage%d_unit%d" % (i + 1, j + 1)
            if bottle:
                mid = cout // 4
                specs += bn(u + "_bn1", cin) + bn(u + "_bn2", mid) + bn(u + "_bn3", mid)
                specs += [(u + "_conv1_weight", (mid, cin, 1, 1), "he"),
                          (u + "_conv2_weight", (mid, mid, 3, 3), "he"),
                          (u + "_conv3_weight", (cout, mid, 1, 1), "he")]
            else:
                specs += bn(u + "_bn1", cin) + bn(u + "_bn2", cout)
                specs += [(u + "_conv1_weight", (cout, cin, 3, 3), "he"),
                          (u + "_conv2_weight", (cout, cout, 3, 3), "he")]
            if j == 0:
                specs.append((u + "_sc_weight", (cout, cin, 1, 1), "he"))
            cin = cout
    specs += bn("bn1", cin)
    specs += [("fc1_weight", (cfg["num_classes"], cin), "normal:0.01"),
              ("fc1_bias", (cfg["num_classes"],), "zeros")]
    return specs


def aux_specs(cfg):
    """Moving statistics the program keeps beside the parameters."""
    out = []
    for name, shape, _ in param_specs(cfg):
        if name.endswith("_gamma"):
            stem = name[:-len("_gamma")]
            out += [(stem + "_moving_mean", shape, "zeros"),
                    (stem + "_moving_var", shape, "ones")]
    return out


def _conv(x, w, stride, pad, q):
    return jax.lax.conv_general_dilated(
        q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=common.HIGHEST)


def _bn_relu(x, p, name):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + EPS) \
        * p[name + "_gamma"].reshape(1, -1, 1, 1) \
        + p[name + "_beta"].reshape(1, -1, 1, 1)
    return jax.nn.relu(y)


def _unit(x, p, u, stride, first, bottle, q):
    a1 = _bn_relu(x, p, u + "_bn1")
    if bottle:
        y = _conv(a1, p[u + "_conv1_weight"], 1, 0, q)
        y = _conv(_bn_relu(y, p, u + "_bn2"), p[u + "_conv2_weight"], stride, 1, q)
        y = _conv(_bn_relu(y, p, u + "_bn3"), p[u + "_conv3_weight"], 1, 0, q)
    else:
        y = _conv(a1, p[u + "_conv1_weight"], stride, 1, q)
        y = _conv(_bn_relu(y, p, u + "_bn2"), p[u + "_conv2_weight"], 1, 1, q)
    sc = _conv(a1, p[u + "_sc_weight"], stride, 0, q) if first else x
    return y + sc


def forward(params, images, cfg, quant=None, remat=True):
    """Logits (B, classes) of NCHW images, in training mode."""
    q = common.rounder(quant)
    units, _, bottle = _plan(cfg)
    x = _conv(images.astype(jnp.float32), params["conv0_weight"], 2, 3, q)
    x = _bn_relu(x, params, "bn0")
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    for i, n in enumerate(units):
        for j in range(n):
            u = "stage%d_unit%d" % (i + 1, j + 1)
            f = functools.partial(_unit, u=u, stride=2 if (j == 0 and i > 0) else 1,
                                  first=j == 0, bottle=bottle, q=q)
            if remat:
                f = jax.checkpoint(f)
            x = f(x, {k: v for k, v in params.items() if k.startswith(u + "_")})
    x = _bn_relu(x, params, "bn1")
    x = jnp.mean(x, axis=(2, 3))
    return jnp.einsum("bc,kc->bk", q(x), q(params["fc1_weight"]),
                      precision=common.HIGHEST) + params["fc1_bias"]


def block_loss(cfg, quant=None):
    def f(p, images, labels):
        return common.ce_sum(forward(p, images, cfg, quant), labels)
    return f


def split_rows(images, labels):
    return images, labels.astype(jnp.int32)
