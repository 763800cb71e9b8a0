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


# -- the mixers' two operators with their own gradients (ops/mixers.py) ------


def _builds(name):
    from mxtpu import telemetry
    return {m.labels.get("path"): m.value
            for m in telemetry.registry().series() if m.name == name}


def _near(got, want, tol):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    return float(np.max(np.abs(got - want))) <= tol * max(
        1.0, float(np.max(np.abs(want))))


def _no_further(new, old, want):
    """Each bfloat16 result of the operator no further (in the norm, a tenth
    for the roundings' luck) from the float32 chain's than the bfloat16
    chain's is."""
    def off(got, ref):
        return float(jnp.linalg.norm(got.astype("float32") - ref))
    for got, was, ref in zip(new, old, want):
        assert got.dtype == jnp.bfloat16
        assert off(got, ref) <= 1.1 * off(was, ref) + 1e-6


def _parents_conv(data, weight, bias=None):
    """`_causal_conv1d` as the parent commit had it, to the letter."""
    k, t = weight.shape[1], data.shape[1]
    x = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(x[:, i:i + t, :].astype(jnp.float32) * w[:, i]
              for i in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(data.dtype)


def _conv_chain(data, weight, bias=None):
    """What the mixers' graphs held: the convolution, rounded, then SiLU."""
    return jax.nn.silu(_parents_conv(data, weight, bias))


def _grads(fn, args, w):
    """(fn(*args), its gradients under the weights w) as one program."""
    def run(args, w):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(w.astype(out.dtype))
    return jax.jit(run)(tuple(args), w)


def _conv_args(rng, b, t, c, k, bias, dtype="float32"):
    args = [rng.normal(size=(b, t, c)), 0.5 * rng.normal(size=(c, k))]
    if bias:
        args.append(rng.normal(size=(c,)))
    return [jnp.asarray(a, dtype) for a in args], \
        jnp.asarray(rng.normal(size=(b, t, c)), dtype)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("act", ["none", "silu"])
def test_short_conv_and_its_gradients_are_the_chains(act, bias):
    """Values and the data's, the filter's and the bias's gradients against
    the chain the operator replaces, float32 to 1e-6; in bfloat16 no further
    from the float32 chain than the bfloat16 chain is."""
    from mxtpu.ops import mixers
    chain = _conv_chain if act == "silu" else _parents_conv
    args, w = _conv_args(np.random.default_rng(10), 2, 37, 6, 4, bias)

    def op(*a):
        return mixers.short_conv(*a[:2], a[2] if bias else None, 4, act)

    want = _grads(chain, args, w)
    for got, ref in zip(_grads(op, args, w), want):
        assert _near(got, ref, 1e-6)
    low = [a.astype("bfloat16") for a in args]
    new = _grads(op, low, w.astype("bfloat16"))
    old = _grads(chain, low, w.astype("bfloat16"))
    _no_further(new, old, want)


def test_short_conv_without_activation_is_the_parents_to_the_bit():
    rng = np.random.default_rng(11)
    for dtype, bias in (("float32", False), ("bfloat16", True)):
        (x, w, *b), _ = _conv_args(rng, 2, 19, 5, 4, bias, dtype)
        got = mx.nd.contrib.CausalConv1D(
            mx.nd.array(x).astype(dtype), mx.nd.array(w).astype(dtype),
            *(mx.nd.array(v).astype(dtype) for v in b), kernel=4, bias=bias)
        # compiled as the operator is: eagerly, XLA contracts nothing
        assert np.array_equal(got.asnumpy(), np.asarray(
            jax.jit(_parents_conv)(x, w, *b)))
    with pytest.raises(Exception, match="act_type"):
        mx.nd.contrib.CausalConv1D(mx.nd.array(x), mx.nd.array(w), kernel=4,
                                   act_type="gelu")


@pytest.mark.parametrize("act,dtype,t,c,blocks,tol", [
    ("silu", "float32", 256, 256, (128, 128), 2e-6),
    ("none", "float32", 256, 256, (128, 256), 2e-6),
    ("silu", "bfloat16", 256, 256, (128, 128), 2 ** -8),
    ("silu", "float32", 256, 192, (128, 192), 2e-6),  # whole rows, 1.5 tiles
    ("silu", "float32", 512, 128, (256, 128), 2e-6),  # two slabs a block
])
def test_short_conv_backward_kernel_is_its_plain_body(act, dtype, t, c,
                                                      blocks, tol):
    """The Mosaic kernel in interpret mode, two time blocks a row, one or two
    lane tiles a block, one or two slabs of the walk: dX, dW and dbias as
    the plain body gives them."""
    from mxtpu.ops import mixers
    (x, w, b), dy = _conv_args(np.random.default_rng(12), 2, t, c, 4, True,
                               dtype)
    w, b = w.astype("float32"), b.astype("float32")
    got = mixers._conv_bwd_call(dy, x, w, b, 4, act, blocks, interpret=True)
    want = mixers._conv_bwd_plain(dy, x, w, b, 4, act)
    for g, ref in zip(got, want):
        assert g.dtype == ref.dtype and _near(g, ref, tol)


def test_short_conv_backward_kernel_at_a_blocks_edge_and_a_rows_start():
    """A time block takes the K-1 rows before it for its first rows' taps
    and the dP of the K-1 rows after it for its last rows' dX, and nothing
    crosses a batch row's start: row 1 alone gives what row 1 of two gives,
    and a change of dY right behind a block's edge moves the K-1 rows of dX
    in front of it as the plain body says."""
    from mxtpu.ops import mixers
    (x, w, b), dy = _conv_args(np.random.default_rng(13), 2, 256, 128, 4,
                               True)

    def kernel(dy, x):
        return mixers._conv_bwd_call(dy, x, w, b, 4, "silu", (128, 128),
                                     interpret=True)

    both = kernel(dy, x)
    for row in range(2):
        alone = kernel(dy[row:row + 1], x[row:row + 1])
        assert np.array_equal(both[0][row], alone[0][0])
    moved = dy.at[0, 128].add(1.0)
    dx = kernel(moved, x)[0] - both[0]
    want = mixers._conv_bwd_plain(moved, x, w, b, 4, "silu")[0] \
        - mixers._conv_bwd_plain(dy, x, w, b, 4, "silu")[0]
    assert _near(dx, want, 2e-6)
    assert float(jnp.min(jnp.max(jnp.abs(dx[0, 125:129]), axis=-1))) > 0
    assert not np.any(np.asarray(dx[0, :125])) and not np.any(
        np.asarray(dx[0, 129:])) and not np.any(np.asarray(dx[1]))
    # the other direction: x just before the edge feeds the taps behind it
    bumped = x.at[0, 127].add(1.0)
    got = kernel(dy, bumped)
    want = mixers._conv_bwd_plain(dy, bumped, w, b, 4, "silu")
    for g, ref in zip(got, want):
        assert _near(g, ref, 2e-6)


def test_short_conv_counts_its_builds_by_what_the_shape_takes():
    from mxtpu.ops import mixers
    assert mixers._conv_blocks(4096, 6144, 4, 2) == (1024, 512)
    assert mixers._conv_blocks(2048, 5760, 4, 2) == (1024, 384)
    assert mixers._conv_blocks(2048, 2880, 4, 2) == (256, 2880)  # 22.5 tiles
    assert mixers._conv_blocks(200, 256, 4, 4) is None       # T
    assert mixers._conv_blocks(256, 256, 12, 4) is None      # taps
    rng = np.random.default_rng(14)
    for shape, path in (((1, 128, 128), "fused"), ((1, 100, 128), "composed"),
                        ((1, 128, 96), "composed")):
        (x, w), dy = _conv_args(rng, *shape, 4, False)
        before = _builds("mixer_conv_builds")
        mixers.short_conv(x, w, None, 4, "silu")             # not counted
        got = _grads(lambda x, w: mixers.short_conv(x, w, None, 4, "silu"),
                     (x, w), dy)
        after = _builds("mixer_conv_builds")
        assert after.get(path, 0) == before.get(path, 0) + 1
        assert sum(after.values()) == sum(before.values()) + 1
        for g, ref in zip(got, _grads(_conv_chain, (x, w), dy)):
            assert _near(g, ref, 2e-6)


def _parents_rms(data, gamma, gate=None, eps=1e-6, groups=1):
    """`_rms_norm` over the last axis as the parent commit had it."""
    x = data.astype(jnp.float32)
    if groups > 1:
        grouped = x.reshape(x.shape[:-1] + (groups, -1))
        ms = jnp.broadcast_to(
            jnp.mean(jnp.square(grouped), axis=-1, keepdims=True),
            grouped.shape).reshape(x.shape)
    else:
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)
    if gate is not None:
        out = out * jax.nn.silu(gate.astype(jnp.float32))
    return out.astype(data.dtype)


def _norm_chain(gate_first, groups):
    if gate_first:      # Nemotron's graph: y * Activation(z), then the norm
        return lambda x, gamma, gate: _parents_rms(
            x * jax.nn.silu(gate), gamma, None, 1e-5, groups)
    return lambda x, gamma, gate: _parents_rms(x, gamma, gate, 1e-5, groups)


@pytest.mark.parametrize("shape,groups,gate_first", [
    ((2, 5, 3, 8), 1, False), ((2, 7, 24), 3, True), ((3, 16), 2, False),
    ((2, 5, 12), 1, True)])
def test_gated_norm_and_its_gradients_are_the_chains(shape, groups,
                                                     gate_first):
    """Both forms (the gate on the normed result; the gate inside the mean
    square, over groups): values and the data's, gamma's and the gate's
    gradients against the chain, float32 to 1e-6, bfloat16 no further from
    the float32 chain than the bfloat16 chain is; through the Symbol too."""
    from mxtpu.ops import mixers
    rng = np.random.default_rng(20)
    args = [jnp.asarray(rng.normal(size=s), "float32")
            for s in (shape, shape[-1:], shape)]
    w = jnp.asarray(rng.normal(size=shape), "float32")
    chain = _norm_chain(gate_first, groups)

    def op(x, gamma, gate):
        return mixers.gated_norm(x, gamma, gate, 1e-5, groups, gate_first)

    want = _grads(chain, args, w)
    for got, ref in zip(_grads(op, args, w), want):
        assert _near(got, ref, 1e-6)
    low = [a.astype("bfloat16") for a in args]
    new = _grads(op, low, w.astype("bfloat16"))
    old = _grads(chain, low, w.astype("bfloat16"))
    _no_further(new, old, want)
    sym = mx.sym.RMSNorm(mx.sym.Variable("data"), mx.sym.Variable("gamma"),
                         mx.sym.Variable("gate"), gated=True, eps=1e-5,
                         groups=groups, gate_first=gate_first)
    _check(sym, dict(zip(("data", "gamma", "gate"), map(np.asarray, args))),
           lambda data, gamma, gate: chain(data, gamma, gate))


def test_plain_rms_norm_is_the_parents_to_the_bit():
    rng = np.random.default_rng(21)
    for dtype, groups in (("float32", 1), ("bfloat16", 1), ("bfloat16", 4)):
        x = jnp.asarray(3 * rng.normal(size=(2, 9, 32)), dtype)
        gamma = jnp.asarray(rng.normal(size=(32,)), dtype)
        got = mx.nd.RMSNorm(mx.nd.array(x).astype(dtype),
                            mx.nd.array(gamma).astype(dtype), eps=1e-5,
                            groups=groups)
        assert np.array_equal(got.asnumpy(), np.asarray(jax.jit(
            _parents_rms, static_argnums=(2, 3, 4))(x, gamma, None, 1e-5,
                                                    groups)))
    before = _builds("mixer_norm_builds")
    jax.grad(lambda x: jnp.sum(mx.ops.get_op("RMSNorm").fn(
        mx.ops.registry.AttrDict(axis=-1, eps=1e-5, groups=1), x, gamma)
        .astype(jnp.float32)))(x)
    assert _builds("mixer_norm_builds") == before    # not the operator


@pytest.mark.parametrize("shape,groups,gate_first,dtype,tol", [
    ((2, 64, 4, 192), 1, False, "float32", 4e-6),   # pairs of heads, folded
    ((2, 128, 1024), 2, True, "float32", 4e-6),     # a group a block
    ((256, 256), 4, True, "bfloat16", 2 ** -7),     # two groups a lane tile
])
def test_gated_norm_kernels_are_their_plain_bodies(shape, groups, gate_first,
                                                   dtype, tol):
    """The Mosaic kernels in interpret mode against the plain bodies:
    forward, and dX, dGate and gamma's gradient backward."""
    from mxtpu.ops import mixers
    rng = np.random.default_rng(22)
    x, gate, do = (jnp.asarray(rng.normal(size=shape), dtype)
                   for _ in range(3))
    gamma = jnp.asarray(rng.normal(size=shape[-1:]), "float32")
    plan, n, flat, gamma32 = mixers._norm_view(shape, groups, gamma,
                                               x.dtype.itemsize)
    blocks = (128, plan[5])
    x, gate, do = (a.reshape(flat) for a in (x, gate, do))
    got = mixers._norm_fwd_call(x, gate, gamma32.reshape(1, -1), n, 1e-5,
                                gate_first, blocks, interpret=True)
    assert _near(got, mixers._norm_plain(x, gate, gamma32, n, 1e-5,
                                         gate_first), tol)
    got = mixers._norm_bwd_call(do, x, gate, gamma32.reshape(1, -1), n, 1e-5,
                                gate_first, blocks, interpret=True)
    want = mixers._norm_bwd_plain(do, x, gate, gamma32, n, 1e-5, gate_first)
    for g, ref in zip(got, want):
        assert g.dtype == ref.dtype and _near(g, ref, tol)


def test_gated_norm_counts_its_builds_by_what_the_shape_takes():
    from mxtpu.ops import mixers
    # (rows, channels, group, folded, rows and lanes a step)
    assert mixers._norm_plan((2, 4096, 4096), 8, 2) == (
        8192, 4096, 512, 1, 1024, 512)
    assert mixers._norm_plan((4, 2048, 30, 192), 1, 2) == (
        8192, 5760, 192, 30, 1024, 384)
    assert mixers._norm_plan((2, 100, 256), 2, 4) is None     # rows
    assert mixers._norm_plan((2, 128, 3, 192), 1, 4) is None  # an odd head
    assert mixers._norm_plan((256, 96), 1, 4) is None         # 0.75 tiles
    rng = np.random.default_rng(23)
    for shape, groups, path in (((128, 256), 2, "fused"),
                                ((2, 64, 2, 64), 1, "fused"),
                                ((100, 256), 2, "composed"),
                                ((128, 96), 1, "composed")):
        args = [jnp.asarray(rng.normal(size=s), "float32")
                for s in (shape, shape[-1:], shape)]
        before = _builds("mixer_norm_builds")
        mixers.gated_norm(*args, 1e-5, groups, True)          # not counted
        _grads(lambda *a: mixers.gated_norm(*a, 1e-5, groups, True), args,
               args[0])
        after = _builds("mixer_norm_builds")
        assert after.get(path, 0) == before.get(path, 0) + 1
        assert sum(after.values()) == sum(before.values()) + 1
