"""Flash-attention Pallas kernel tests: interpret-mode kernels, forward and
backward, vs the jnp reference oracle, causal masking, gradients, which
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
