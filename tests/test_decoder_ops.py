"""The small ops the hybrid decoder brought: RMSNorm (plain and gated by
SiLU), `Activation(act_type="silu")`, the causal short convolution and the
per-vector L2 normalisation, values and gradients against jax.numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx


def _bind(sym, arrays, grads=True):
    args = {k: mx.nd.array(v) for k, v in arrays.items()}
    ex = sym.bind(mx.cpu(), args,
                  args_grad={k: mx.nd.zeros(v.shape) for k, v in arrays.items()}
                  if grads else None)
    ex.forward(is_train=grads)
    return ex


def _check(sym, arrays, fn, atol=1e-5):
    """Forward and the gradient of sum(out * w) against `fn`."""
    ex = _bind(sym, arrays)
    want = fn(**{k: jnp.asarray(v) for k, v in arrays.items()})
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, atol=atol)
    w = np.random.default_rng(1).normal(size=want.shape).astype(np.float32)
    ex.backward(mx.nd.array(w))
    grads = jax.grad(lambda kw: jnp.sum(fn(**kw) * w))(
        {k: jnp.asarray(v) for k, v in arrays.items()})
    for k in arrays:
        np.testing.assert_allclose(ex.grad_dict[k].asnumpy(), grads[k],
                                   atol=10 * atol, err_msg=k)


def _rms(data, gamma, eps=1e-6):
    return gamma * data / jnp.sqrt(jnp.mean(data ** 2, -1, keepdims=True) + eps)


def test_rms_norm():
    rng = np.random.default_rng(0)
    arrays = {"data": rng.normal(size=(2, 5, 16)).astype(np.float32) * 3,
              "gamma": rng.normal(size=(16,)).astype(np.float32)}
    sym = mx.sym.RMSNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"))
    _check(sym, arrays, _rms)
    _, out, _ = mx.sym.RMSNorm(mx.sym.Variable("data"), name="n").infer_shape(
        data=(2, 5, 16))
    assert out == [(2, 5, 16)]
    assert mx.sym.RMSNorm(mx.sym.Variable("data"), name="n").list_arguments() \
        == ["data", "n_gamma"]


def test_rms_norm_gated_by_silu():
    rng = np.random.default_rng(2)
    arrays = {"data": rng.normal(size=(2, 3, 4, 8)).astype(np.float32),
              "gamma": rng.normal(size=(8,)).astype(np.float32),
              "gate": rng.normal(size=(2, 3, 4, 8)).astype(np.float32)}
    sym = mx.sym.RMSNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"),
                         mx.sym.Variable("gate"), gated=True, eps=1e-5)
    _check(sym, arrays, lambda data, gamma, gate:
           _rms(data, gamma, 1e-5) * jax.nn.silu(gate))


def test_rms_norm_reduces_in_float32_whatever_the_input():
    x = (np.random.default_rng(3).normal(size=(4, 512)) * 50).astype(np.float32)
    got = mx.nd.RMSNorm(mx.nd.array(x).astype("bfloat16"),
                        mx.nd.ones((512,)).astype("bfloat16"))
    assert str(got.dtype) == "bfloat16"
    xb = jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
    want = _rms(xb, 1.0)
    assert float(jnp.max(jnp.abs(got.asnumpy().astype(np.float32) - want))) \
        < 2 ** -7 * float(jnp.max(jnp.abs(want)))


def test_silu():
    x = np.linspace(-6, 6, 49).astype(np.float32).reshape(7, 7)
    sym = mx.sym.Activation(mx.sym.Variable("data"), act_type="silu")
    _check(sym, {"data": x}, lambda data: data / (1 + jnp.exp(-data)))


def _conv(data, weight):
    k = weight.shape[1]
    t = data.shape[1]
    xp = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * weight[:, i] for i in range(k))


def test_causal_short_convolution():
    rng = np.random.default_rng(4)
    arrays = {"data": rng.normal(size=(2, 12, 6)).astype(np.float32),
              "weight": rng.normal(size=(6, 4)).astype(np.float32)}
    sym = mx.sym.contrib.CausalConv1D(mx.sym.Variable("data"),
                                      mx.sym.Variable("weight"), kernel=4)
    _check(sym, arrays, _conv)
    # the same numbers as a grouped Convolution with a left pad
    ncw = mx.nd.array(np.pad(arrays["data"].transpose(0, 2, 1),
                             ((0, 0), (0, 0), (3, 0))))
    grouped = mx.nd.Convolution(ncw, mx.nd.array(arrays["weight"][:, None, :]),
                                kernel=(4,), num_filter=6, num_group=6,
                                no_bias=True)
    np.testing.assert_allclose(
        grouped.asnumpy().transpose(0, 2, 1),
        _bind(sym, arrays, grads=False).outputs[0].asnumpy(), atol=1e-5)
    # the weight's shape follows from the data's
    named = mx.sym.contrib.CausalConv1D(mx.sym.Variable("data"), kernel=4,
                                        name="c")
    shapes, _, _ = named.infer_shape(data=(2, 12, 6))
    assert dict(zip(named.list_arguments(), shapes))["c_weight"] == (6, 4)


def test_the_convolution_is_causal():
    """A change at t moves nothing before t."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(1, 16, 3)).astype(np.float32)
    w = mx.nd.array(rng.normal(size=(3, 4)).astype(np.float32))
    moved = x.copy()
    moved[:, 9] += 1.0
    a = mx.nd.contrib.CausalConv1D(mx.nd.array(x), w, kernel=4).asnumpy()
    b = mx.nd.contrib.CausalConv1D(mx.nd.array(moved), w, kernel=4).asnumpy()
    assert np.array_equal(a[:, :9], b[:, :9])
    assert np.all(np.abs(a[:, 9:13] - b[:, 9:13]).max(axis=(0, 2)) > 0)
    assert np.array_equal(a[:, 13:], b[:, 13:])


def test_l2_normalisation_of_each_vector():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 5, 3, 8)).astype(np.float32)
    sym = mx.sym.L2Normalization(mx.sym.Variable("data"), mode="last",
                                 eps=1e-6)
    _check(sym, {"data": x}, lambda data: data / jnp.sqrt(
        jnp.sum(data ** 2, -1, keepdims=True) + 1e-6))


@pytest.mark.parametrize("mode", ["instance", "channel", "spatial"])
def test_the_other_l2_modes_are_as_they_were(mode):
    x = np.random.default_rng(7).normal(size=(2, 3, 4, 5)).astype(np.float32)
    red = {"instance": (1, 2, 3), "channel": (1,), "spatial": (2, 3)}[mode]
    got = mx.nd.L2Normalization(mx.nd.array(x), mode=mode).asnumpy()
    np.testing.assert_allclose(
        got, x / np.sqrt((x ** 2).sum(axis=red, keepdims=True) + 1e-10),
        rtol=1e-5)
