"""`_contrib_SSDScan`: the chunked forward and its own backward against the
recurrence stepped a token at a time and `jax.grad` of it, in float32 on the
CPU (where the op runs its chunk functions under `lax.scan`), and the Pallas
kernels in interpret mode against that path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.ops import ssd

NAMES = ("dx", "ddt", "dA_log", "dB", "dC", "dD")


def recurrence(x, dt, a_log, bm, cm, d):
    """x (B, T, H, P), dt (B, T, H), bm and cm (B, T, G, N): per head
    S = e^{dt A} S + dt B x^T; y = S^T C + D x, one token at a time."""
    r = x.shape[2] // bm.shape[2]

    def head(x, dt, a, bm, cm, d):
        def step(s, z):
            xt, dtt, bt, ct = z
            s = jnp.exp(dtt * a) * s + dtt * jnp.outer(bt, xt)
            return s, s.T @ ct + d * xt
        s0 = jnp.zeros((bm.shape[-1], x.shape[-1]), jnp.float32)
        return jax.lax.scan(step, s0, (x, dt, bm, cm))[1]

    heads = jax.vmap(head, in_axes=(1, 1, 0, 1, 1, 0), out_axes=1)
    return jax.vmap(heads, in_axes=(0, 0, None, 0, 0, None))(
        x, dt, -jnp.exp(a_log), jnp.repeat(bm, r, axis=2),
        jnp.repeat(cm, r, axis=2), d)


def inputs(seed, t, h=6, p=16, g=2, n=32, b=1, dtype=jnp.float32,
           published_steps=False):
    """Steps as softplus leaves them and A in [1, 16] as published, so that
    a row holds heads that keep their state and heads that forget within a
    few tokens. `published_steps`: steps drawn log-uniformly from the
    published [time_step_min, time_step_max] = [0.001, 0.1], dt x A in
    0.001-1.6: the long-memory regime, a state that spans chunks."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h, p)).astype(dtype)
    if published_steps:
        dt = jnp.exp(jax.random.uniform(ks[1], (b, t, h), minval=np.log(1e-3),
                                        maxval=np.log(1e-1)))
    else:
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0))
    bm = (0.5 * jax.random.normal(ks[3], (b, t, g, n))).astype(dtype)
    cm = (0.5 * jax.random.normal(ks[4], (b, t, g, n))).astype(dtype)
    d = jax.random.normal(ks[5], (h,))
    return (x, dt, a_log, bm, cm, d), jax.random.normal(ks[6], (b, t, h, p))


# (T, chunk, heads, head dim, groups, state): a ragged head/group ratio (3),
# T three and five chunks, one group, a head as wide as a lane tile
CASES = [(384, 128, 6, 16, 2, 32), (320, 64, 6, 16, 2, 32),
         (256, 128, 4, 32, 1, 16), (128, 64, 2, 128, 2, 16)]


@pytest.mark.parametrize("t,chunk,h,p,g,n", CASES)
def test_chunked_forward_is_the_recurrence(t, chunk, h, p, g, n):
    args, _ = inputs(3, t, h, p, g, n)
    with jax.default_matmul_precision("highest"):
        got, want = ssd.ssd_scan(*args, chunk=chunk), recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("t,chunk,h,p,g,n", CASES[:3])
def test_the_ops_own_backward_is_the_gradient_of_the_recurrence(t, chunk, h,
                                                                p, g, n):
    args, w = inputs(4, t, h, p, g, n)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=chunk) * w),
                       argnums=tuple(range(6)))(*args)
        want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                        argnums=tuple(range(6)))(*args)
    for name, a, b in zip(NAMES, got, want):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 1e-4, (name, gap)


def test_the_published_steps_long_memory_regime():
    """dt in [0.001, 0.1] (the published `time_step_min` / `time_step_max`):
    a head's state keeps 0.2-0.999 of itself a token (a memory of some ten
    tokens at the mean, of hundreds in the slowest heads), so a chunk's
    output leans on the state carried over the chunks before it. Forward and the six gradients
    against the recurrence; the kernels in interpret mode against the chunk
    path."""
    t, chunk = 512, 64
    args, w = inputs(9, t, published_steps=True)
    decay = np.exp(-np.asarray(args[1]) * np.exp(np.asarray(args[2])))
    assert decay.min() < 0.3 and decay.max() > 0.99
    with jax.default_matmul_precision("highest"):
        got, want = ssd.ssd_scan(*args, chunk=chunk), recurrence(*args)
        # beside the skip D x, the state carried into the last chunk gives
        # over a tenth of what the chunk's own tokens give
        alone = ssd.ssd_scan(*(a[:, -chunk:] if a.ndim > 1 else a
                               for a in args), chunk=chunk)
        own = alone - args[5][:, None] * args[0][:, -chunk:]
        assert float(jnp.linalg.norm(got[:, -chunk:] - alone)) \
            > 0.1 * float(jnp.linalg.norm(own))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
        g_got = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=chunk) * w),
                         argnums=tuple(range(6)))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                          argnums=tuple(range(6)))(*args)
        for name, a, b in zip(NAMES, g_got, g_want):
            gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            assert gap < 1e-4, (name, gap)
        ops, p = _operands(args, chunk)
        y1, s1 = ssd._scan_fwd(*ops, p, True)
        y2, s2 = ssd._fwd_call(*ops, p, True, interpret=True)
        np.testing.assert_allclose(y1, y2, atol=1e-5)
        np.testing.assert_allclose(s1, s2, atol=1e-5)
        dy = w.reshape(y1.shape)
        for a, b in zip(ssd._scan_bwd(*ops, s1, dy, p),
                        ssd._bwd_call(*ops, s1, dy, p, interpret=True)):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _operands(args, chunk):
    """The op's operands as it hands them to its kernels."""
    x, dt, a_log, bm, cm, d = args
    b, t, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g

    def rows(z):
        z = z.astype(jnp.float32).reshape(b, t // chunk, chunk, g, r)
        return jnp.transpose(z, (0, 3, 1, 4, 2))

    return (x.reshape(b, t, h * p), rows(dt),
            jnp.cumsum(rows(dt * -jnp.exp(a_log)), -1),
            bm.reshape(b, t, g * n), cm.reshape(b, t, g * n),
            jnp.repeat(d, p).reshape(g, 1, r * p)), p


@pytest.mark.parametrize("t,chunk,h,p,g,n,dtype", [
    CASES[0] + (jnp.float32,), CASES[0] + (jnp.bfloat16,),
    CASES[1] + (jnp.float32,)])
def test_the_kernels_in_interpret_mode_are_the_chunk_path(t, chunk, h, p, g,
                                                          n, dtype):
    """Forward (output and chunk-first states) and all of the backward's
    outputs, float32 and bfloat16 operands: the same chunk functions, so
    the same numbers."""
    args, w = inputs(5, t, h, p, g, n, dtype=dtype)
    ops, p = _operands(args, chunk)
    with jax.default_matmul_precision("highest"):
        y1, s1 = ssd._scan_fwd(*ops, p, True)
        y2, s2 = ssd._fwd_call(*ops, p, True, interpret=True)
        assert y2.dtype == dtype and s2.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(y1, np.float32),
                                   np.asarray(y2, np.float32), atol=1e-5)
        np.testing.assert_allclose(s1, s2, atol=1e-5)
        assert s1.shape == (1, g, t // chunk, n, h // g * p)
        assert float(jnp.max(jnp.abs(s1[:, :, 0]))) == 0.0
        y3 = ssd._fwd_call(*ops, p, False, interpret=True)
        np.testing.assert_array_equal(np.asarray(y2, np.float32),
                                      np.asarray(y3, np.float32))
        dy = w.reshape(y1.shape).astype(dtype)
        for a, b in zip(ssd._scan_bwd(*ops, s1, dy, p),
                        ssd._bwd_call(*ops, s1, dy, p, interpret=True)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=1e-4,
                                       rtol=1e-4)


def test_bfloat16_operands_keep_state_and_decay_in_float32():
    """bf16 x, B, C against the float32 recurrence on the same (rounded)
    operands: the gap is the products' rounding, not the state's."""
    args, w = inputs(6, 384, dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    got = ssd.ssd_scan(*args, chunk=128)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = recurrence(*wide)
    gap = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap < 2e-2, gap
    grads = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk=128) * w),
                     argnums=tuple(range(6)))(*args)
    assert [g.dtype for g in grads] == [a.dtype for a in args]


def test_what_the_backward_is_handed():
    """Residuals are the inputs and each chunk's first state: the gauge
    counts the states, and a row that is no multiple of the chunk, or heads
    that do not divide into the groups, are refused."""
    from mxtpu import telemetry
    args, _ = inputs(7, 256)
    jax.grad(lambda x: jnp.sum(ssd.ssd_scan(x, *args[1:], chunk=64)))(args[0])
    saved = [m.value for m in telemetry.registry().series()
             if m.name == "ssd_state_saved_bytes"]
    assert saved == [1 * 6 * 4 * 32 * 16 * 4]   # B x H x chunks x N x P x 4 B
    with pytest.raises(ValueError):
        ssd.ssd_scan(*inputs(7, 200)[0], chunk=128)
    with pytest.raises(ValueError):
        ssd.ssd_scan(*inputs(7, 128, h=5)[0], chunk=128)


def test_the_operator_through_the_symbol():
    args, _ = inputs(8, 128)
    names = ("data", "dt", "A_log", "B", "C", "D")
    out = mx.sym.contrib.SSDScan(*(mx.sym.Variable(n) for n in names),
                                 chunk=64, name="ssd")
    ex = out.bind(mx.cpu(), {n: mx.nd.NDArray(a) for n, a in zip(names, args)})
    got = ex.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(got, np.asarray(ssd.ssd_scan(*args, chunk=64)),
                               rtol=1e-5, atol=1e-6)
