"""Flash-attention Pallas kernel tests: interpret-mode kernels, forward
(the one that walks its key blocks and the grid one) and backward, vs the
jnp reference oracle, causal masking, gradients, which forward and which
backward a shape takes, op registration, and the ring-attention
cross-check."""
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.ops import attention as att

import jax
import jax.numpy as jnp


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype("float32") * 0.5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,s", [(128, 128), (256, 128), (128, 256)])
def test_flash_matches_reference(causal, t, s):
    b, h, d = 2, 2, 64
    q = _rand((b, h, t, d), 0)
    k = _rand((b, h, s, d), 1)
    v = _rand((b, h, s, d), 2)
    if causal and t != s:
        pytest.skip("causal assumes aligned q/kv lengths")
    out = att.flash_attention(q, k, v, causal=causal)
    ref = att._reference(q.reshape(b * h, t, d), k.reshape(b * h, s, d),
                         v.reshape(b * h, s, d), 1.0 / d ** 0.5,
                         causal).reshape(b, h, t, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_multiblock_accumulation():
    # kv length spans several 128-blocks: exercises the online softmax
    b, h, t, s, d = 1, 1, 128, 512, 64
    q, k, v = _rand((b, h, t, d)), _rand((b, h, s, d), 1), _rand(
        (b, h, s, d), 2)
    out = att.flash_attention(q, k, v, block_k=128)
    ref = att._reference(q[0], k[0], v[0], 1.0 / d ** 0.5, False)[None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match_reference():
    b, h, t, d = 1, 2, 128, 32
    q, k, v = _rand((b, h, t, d)), _rand((b, h, t, d), 1), _rand(
        (b, h, t, d), 2)

    def loss_flash(q, k, v):
        return att.flash_attention(q, k, v, causal=True).sum()

    def loss_ref(q, k, v):
        return att._reference(q.reshape(h, t, d), k.reshape(h, t, d),
                              v.reshape(h, t, d), 1.0 / d ** 0.5,
                              True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(b_).reshape(a.shape),
                                   rtol=2e-3, atol=2e-3)


def test_flash_op_registered():
    q = mx.nd.array(np.random.RandomState(0).randn(1, 2, 128, 32)
                    .astype("float32"))
    out = mx.nd.contrib.FlashAttention(q, q, q, causal=True)
    assert out.shape == (1, 2, 128, 32)


def test_blockwise_agrees_with_flash():
    from mxtpu.parallel.ring_attention import blockwise_attention

    b, t, h, d = 1, 256, 2, 32
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, t, h, d).astype("float32") * 0.3)
    k = jnp.asarray(rng.randn(b, t, h, d).astype("float32") * 0.3)
    v = jnp.asarray(rng.randn(b, t, h, d).astype("float32") * 0.3)
    blockwise = blockwise_attention(q, k, v, block_size=64)
    flash = att.flash_attention(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(blockwise),
                               np.asarray(flash.transpose(0, 2, 1, 3)),
                               rtol=2e-3, atol=2e-3)


def test_flash_ragged_kv_tail():
    # kv length not a multiple of block_k: padded columns must not leak
    b, h, t, s, d = 1, 1, 64, 96, 32
    q = _rand((b, h, t, d), 0)
    k = _rand((b, h, s, d), 1)
    v = _rand((b, h, s, d), 2)
    out = att.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = att._reference(q[0], k[0], v[0], 1.0 / d ** 0.5, False)[None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def _grads(fn, q, k, v, w):
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)


# (causal, T, S, the backward's blocks): multi-block T, so that skipping
# above the diagonal and accumulation across block pairs run in interpret
# mode; None where the kernel does not tile and the reference's VJP runs
_BWD_CASES = [
    (True, 384, 384, (128, 128)),     # 6 live pairs of 9, 3 on the diagonal
    (True, 1024, 1024, (512, 512)),   # the LM cell's blocks: 3 pairs of 4
    (False, 384, 384, (128, 128)),
    (False, 256, 384, (256, 128)),    # t != s
    (False, 64, 96, None),            # ragged kv tail
]


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("causal,t,s,blocks", _BWD_CASES)
def test_flash_backward_matches_reference_vjp(causal, t, s, blocks, dtype,
                                              tol):
    bh, d = 2, 64
    q, k, v = (_rand((bh, n, d), i).astype(dtype)
               for i, n in enumerate((t, s, s)))
    w = _rand((bh, t, d), 3)
    scale = 1.0 / d ** 0.5
    assert att._bwd_blocks(t, s, d, q.dtype.itemsize, causal) == blocks
    got = _grads(lambda *a: att._flash3(*a, scale, causal, 512, 1024),
                 q, k, v, w)
    want = _grads(lambda *a: att._reference(*a, scale, causal), q, k, v, w)
    for g, r in zip(got, want):
        assert g.dtype == r.dtype and g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g, "f4"), np.asarray(r, "f4"),
                                   rtol=tol, atol=tol)


def test_attention_bwd_builds_counts_the_path():
    from mxtpu import telemetry

    def built(path):
        return telemetry.counter("attention_bwd_builds",
                                 labels={"path": path}).value

    def grad(shape):
        q = _rand(shape)
        jax.grad(lambda a: att.flash_attention(a, q, q, causal=True).sum())(q)

    before = built("kernel"), built("reference")
    grad((1, 2, 128, 32))
    assert (built("kernel"), built("reference")) == (before[0] + 1, before[1])
    grad((1, 4, 2, 4))   # the gradient sweep's shape: nothing to tile
    assert (built("kernel"), built("reference")) == (before[0] + 1,
                                                     before[1] + 1)


def _series(name, labels=None):
    from mxtpu import telemetry
    if labels is None:
        return telemetry.gauge(name).value
    return telemetry.counter(name, labels=labels).value


@pytest.mark.parametrize("with_lse", [False, True])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [128, 256, 512, 1024])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("causal", [False, True])
def test_walked_forward_matches_reference(causal, dtype, tol, t, d, with_lse):
    """The forward that walks its key blocks inside the kernel (interpret
    mode), blocks from the shape, against the jnp reference: the output
    and, as the differentiated program asks for it, the rows' log-sum-exp."""
    bh = 2
    q, k, v = (_rand((bh, t, d), i).astype(dtype) for i in range(3))
    scale = 1.0 / d ** 0.5
    # under a causal mask the query block is the whole sequence here
    assert att._fwd_blocks(t, t, d, q.dtype.itemsize, causal) == (
        t if causal else min(t, 512), min(t, 512))
    got = att._forward(q, k, v, scale, causal, 0, 0, with_lse)
    want = att._reference(q, k, v, scale, causal)
    if with_lse:
        got, lse = got
        assert lse.dtype == jnp.float32 and lse.shape == (bh, t)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.nn.logsumexp(
                att._scores(q.astype("float32"), k.astype("float32"), scale,
                            causal), axis=-1)),
            rtol=1e-5, atol=1e-5)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, "f4"), np.asarray(want, "f4"),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("causal,blocks,walked", [
    (True, (128, 128), (128, 128)), (True, (256, 256), (256, 256)),
    (True, (512, 1024), (512, 512)),            # clamped to T
    (True, (256, 128), (256, 128)), (True, (128, 256), None),
    (False, (256, 128), (256, 128)), (False, (128, 256), (128, 256))])
def test_forward_with_the_callers_blocks(causal, blocks, walked):
    """A caller's blocks keep their meaning: the walked forward takes them
    where they tile the shape and, under a causal mask, block_q is whole
    key blocks; the grid kernel takes the others."""
    bh, t, d = 2, 512, 64
    q, k, v = (_rand((bh, t, d), i) for i in range(3))
    assert att._fwd_blocks(t, t, d, 4, causal, *blocks) == walked
    got = att._forward(q, k, v, 0.125, causal, *blocks, False)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(att._reference(q, k, v, 0.125, causal)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("causal,t,d", [(True, 256, 64), (True, 1024, 64),
                                        (True, 512, 128), (False, 256, 64),
                                        (False, 512, 128)])
def test_gradient_through_the_walked_forward(causal, t, d, dtype, tol):
    """The new forward's output and log-sum-exp feed the unchanged backward
    kernel: (dq, dk, dv) against the reference's VJP."""
    bh = 2
    q, k, v = (_rand((bh, t, d), i).astype(dtype) for i in range(3))
    g = _rand((bh, t, d), 3).astype(dtype)
    scale = 1.0 / d ** 0.5
    walk = _series("attention_fwd_builds", {"path": "walk"})
    _, vjp = jax.vjp(
        lambda a, b, c: att.flash_attention(
            a[None], b[None], c[None], causal=causal)[0], q, k, v)
    assert _series("attention_fwd_builds", {"path": "walk"}) == walk + 1
    for got, want in zip(vjp(g), att._reference_vjp(q, k, v, g, scale,
                                                    causal)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got, "f4"),
                                   np.asarray(want, "f4"),
                                   rtol=tol, atol=tol)


def test_attention_fwd_builds_counts_the_grid_where_nothing_tiles():
    """T no multiple of 128: the differentiated forward is the grid kernel,
    the one that handles a ragged key length."""
    def built():
        return tuple(_series("attention_fwd_builds", {"path": p})
                     for p in ("grid", "walk"))

    grid, walk = built()
    q = _rand((1, 2, 320, 64))
    out, vjp = jax.vjp(
        lambda a: att.flash_attention(a, q, q, causal=True), q)
    assert built() == (grid + 1, walk)
    assert (_series("flash_fwd_block_q"), _series("flash_fwd_block_k")) == (
        320, 320)
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.asarray(att._reference(q[0], q[0], q[0], 0.125, True)),
        rtol=2e-3, atol=2e-3)
    att.flash_attention(q, q, q, causal=True)   # no gradient: not counted
    assert built() == (grid + 1, walk)


def test_flash_fwd_live_block_share_at_256_wide_blocks():
    q = _rand((1, 1, 1024, 64))
    att.flash_attention(q, q, q, causal=True, block_q=256, block_k=256)
    assert (_series("flash_fwd_block_q"), _series("flash_fwd_block_k")) == (
        256, 256)
    assert _series("flash_fwd_live_block_share") == 0.625
    att.flash_attention(q, q, q, causal=True)       # 1024 x 512: two strips
    assert (_series("flash_fwd_block_q"), _series("flash_fwd_block_k")) == (
        1024, 512)
    assert _series("flash_fwd_live_block_share") == 0.75
    att.flash_attention(q, q, q, causal=True, block_q=512, block_k=1024)
    assert _series("flash_fwd_live_block_share") == 1.0     # the grid's
    att.flash_attention(q, q, q, causal=False, block_q=256, block_k=256)
    assert _series("flash_fwd_live_block_share") == 1.0


def test_pallas_epilogue_matches_reference():
    """BN-apply+ReLU+add pallas kernel (ops/epilogue.py, interpret mode)
    agrees with the XLA formulation, with and without the residual."""
    import numpy as np
    import jax.numpy as jnp
    from mxtpu.ops.epilogue import (bn_apply_relu_add,
                                    bn_apply_relu_add_reference, fold_bn)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(96, 128), jnp.float32)
    r = jnp.asarray(rng.randn(96, 128), jnp.float32)
    gamma = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(128), jnp.float32)
    mean = jnp.asarray(rng.randn(128), jnp.float32)
    var = jnp.asarray(rng.rand(128) + 0.1, jnp.float32)
    scale, shift = fold_bn(gamma, beta, mean, var)
    got = bn_apply_relu_add(x, scale, shift, r, block_m=32)
    want = bn_apply_relu_add_reference(x, scale, shift, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    got2 = bn_apply_relu_add(x, scale, shift, None, block_m=32)
    want2 = bn_apply_relu_add_reference(x, scale, shift, None)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- grouped heads and the window
def _grouped_case(b, h, g, t, d, dtype=jnp.float32):
    q = _rand((b, h, t, d), 0).astype(dtype)
    k = _rand((b, g, t, d), 1).astype(dtype)
    v = _rand((b, g, t, d), 2).astype(dtype)
    w = _rand((b, h, t, d), 3).astype(dtype)
    return q, k, v, w


def _plain_softmax(q, k, v, window):
    """Causal attention as written, one query head at a time over the
    key/value head it reads: no kernel, no repeat of K or V."""
    b, h, t, d = q.shape
    rep = h // k.shape[1]
    rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (cols > rows - window)
    out = []
    for j in range(h):
        s = jnp.einsum("btd,bsd->bts", q[:, j], k[:, j // rep],
                       precision="highest") / d ** 0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bts,bsd->btd", p, v[:, j // rep],
                              precision="highest"))
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("h,g,t,window,walked", [
    (4, 2, 256, 0, True),        # groups, full: the walk's strips
    (6, 2, 256, 128, True),      # window = one block, edge and diagonal
    (6, 2, 384, 256, True),      # T no multiple of the window: 128 blocks
    (4, 4, 512, 128, True),      # no groups, several blocks between
    (3, 1, 384, 384, True),      # a window as long as the row is none
    (6, 2, 64, 8, False),        # the rehearsal's size: the grid kernel
    (4, 2, 192, 100, False),     # a window that is no multiple of 128
])
def test_grouped_and_windowed_attention_matches_the_plain_softmax(
        h, g, t, window, walked):
    """Forward and gradient, kernels in interpret mode, against the
    softmax over an explicit mask."""
    q, k, v, w = _grouped_case(2, h, g, t, 16)
    blocks = att._fwd_blocks(t, t, 16, 4, True, 0, 0,
                             0 if window >= t else window)
    assert (blocks is not None) == walked

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = att.flash_attention(q, k, v, causal=True, window=window)
    want = _plain_softmax(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(loss(lambda *a: att.flash_attention(
        *a, causal=True, window=window)), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(lambda *a: _plain_softmax(*a, window)),
                      argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        assert a.shape == b_.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-5, atol=5e-5)


def test_the_window_and_the_groups_are_counted():
    """`attention_fwd_builds{walk_window}`, `attention_bwd_builds
    {kernel_window}` and the gauges of a windowed, grouped call."""
    from mxtpu import telemetry

    def count(name, path):
        return telemetry.counter(name, labels={"path": path}).value

    def gauge(name):
        return [m.value for m in telemetry.registry().series()
                if m.name == name][0]

    before = (count("attention_fwd_builds", "walk_window"),
              count("attention_bwd_builds", "kernel_window"))
    q, k, v, w = _grouped_case(1, 6, 2, 512, 16)
    jax.grad(lambda q: jnp.sum(att.flash_attention(
        q, k, v, causal=True, window=256) * w))(q)
    assert count("attention_fwd_builds", "walk_window") == before[0] + 1
    assert count("attention_bwd_builds", "kernel_window") == before[1] + 1
    assert gauge("attention_window") == 256
    assert gauge("attention_kv_groups") == 2
    # 256-wide blocks, two of them: the edge block and the diagonal of the
    # second query block, the diagonal alone of the first: 3 of 4
    assert gauge("flash_win_live_block_share") == pytest.approx(0.75)
    with pytest.raises(ValueError):
        att.flash_attention(q, k, v, causal=False, window=256)
    with pytest.raises(ValueError):
        att.flash_attention(q, k[:, :1], v, causal=True)


def test_the_window_op_attribute_reaches_the_kernel():
    q, k, v, _ = _grouped_case(1, 4, 2, 128, 16)
    got = mx.nd.contrib.FlashAttention(
        mx.nd.NDArray(q), mx.nd.NDArray(k), mx.nd.NDArray(v), causal=True,
        window=32).asnumpy()
    np.testing.assert_allclose(got, np.asarray(_plain_softmax(q, k, v, 32)),
                               rtol=2e-5, atol=2e-5)
