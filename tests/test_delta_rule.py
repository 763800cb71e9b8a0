"""`_contrib_GatedDeltaRule`: the chunked forward and its own backward
against the recurrence stepped a token at a time and `jax.grad` of it, in
float32 on the CPU (where the op runs its chunk functions under `lax.scan`),
and the Pallas kernels in interpret mode against that path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.ops import delta_rule as dr


def recurrence(q, k, v, g, beta):
    """(B, H, T, .): S~ = e^g S; u = beta (v - S~^T k); S = S~ + k u^T;
    o = S^T q, one token at a time."""
    def head(q, k, v, g, b):
        def step(s, x):
            qt, kt, vt, gt, bt = x
            s = jnp.exp(gt) * s
            u = bt * (vt - s.T @ kt)
            s = s + jnp.outer(kt, u)
            return s, s.T @ qt
        s0 = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        return jax.lax.scan(step, s0, (q, k, v, g, b))[1]
    return jax.vmap(jax.vmap(head))(q, k, v, g, beta)


def inputs(seed, t, decay, beta_side, b=2, h=2, dk=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    u = jax.random.uniform(ks[3], (b, h, t))
    g = {"near_one": -0.01 * u, "fast": -1.0 - 5.0 * u,
         # A_log normal:2 as the benchmark draws it: both regimes in one call
         "mixed": -jnp.exp(2 * jax.random.normal(ks[3], (b, h, 1)))
         * jax.nn.softplus(jax.random.normal(ks[4], (b, h, t)))}[decay]
    s = jax.nn.sigmoid(2 * jax.random.normal(ks[4], (b, h, t)))
    beta = {"below_one": s, "above_one": 1 + s, "both": 2 * s}[beta_side]
    w = jax.random.normal(ks[5], (b, h, t, dv))
    return (q, k, v, g, beta), w


CASES = [(128, "near_one", "both"), (192, "near_one", "above_one"),
         (128, "fast", "both"), (192, "fast", "below_one"),
         (128, "mixed", "both"), (192, "mixed", "both")]


@pytest.mark.parametrize("t,decay,beta_side", CASES)
def test_chunked_forward_is_the_recurrence(t, decay, beta_side):
    args, _ = inputs(3, t, decay, beta_side)
    with jax.default_matmul_precision("highest"):
        got, want = dr.gated_delta_rule(*args), recurrence(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # near-unit decays with beta near 2 keep a state that does not contract:
    # float32 rounding of either side is carried along the whole row
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("t,decay,beta_side", CASES)
def test_the_ops_own_backward_is_the_gradient_of_the_recurrence(t, decay,
                                                                beta_side):
    args, w = inputs(4, t, decay, beta_side)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(dr.gated_delta_rule(*a) * w),
                       argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w),
                        argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < 1e-3, (name, gap)


@pytest.mark.parametrize("t,decay,beta_side", CASES)
def test_the_kernels_are_the_scan_path(t, decay, beta_side):
    """The chip runs the Pallas kernels, the CPU the same chunk functions
    under lax.scan and vmap. The kernels in interpret mode (grid, block
    specs, the state carried in scratch, the backward's reversed walk) give
    what the scan gives, forward and backward; only this test runs them on
    the CPU."""
    (q, k, v, g, beta), w = inputs(5, t, decay, beta_side)
    flat = [x.reshape((4,) + x.shape[2:]) for x in (q, k, v, g, beta)]
    o_k, s_k = dr._fwd_call(*flat, 64, True, interpret=True)
    o_s, s_s = dr._scan_fwd(*flat, 64, True)
    np.testing.assert_allclose(o_k, o_s, atol=1e-5)
    np.testing.assert_allclose(s_k, s_s, atol=1e-5)
    assert s_k.shape == (4, t // 64, 8, 16)   # a state a chunk, none a token
    do = w.reshape(4, t, 16)
    for a, b in zip(dr._bwd_call(*flat, s_k, do, 64, interpret=True),
                    dr._scan_bwd(*flat, s_s, do, 64)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_bfloat16_operands_keep_the_decay_in_float32():
    (q, k, v, g, beta), _ = inputs(6, 128, "mixed", "both")
    bf = jnp.bfloat16
    got = dr.gated_delta_rule(q.astype(bf), k.astype(bf), v.astype(bf), g,
                              beta.astype(bf))
    assert got.dtype == bf
    want = recurrence(q, k, v, g, beta)
    gap = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap < 0.02, gap


def test_registered_for_ndarray_and_symbol():
    (q, k, v, g, beta), _ = inputs(7, 128, "mixed", "both")
    want = np.asarray(dr.gated_delta_rule(q, k, v, g, beta))
    nds = [mx.nd.array(np.asarray(x)) for x in (q, k, v, g, beta)]
    np.testing.assert_allclose(
        mx.nd.contrib.GatedDeltaRule(*nds).asnumpy(), want, atol=1e-5)
    names = ["q", "k", "v", "g", "beta"]
    sym = mx.sym.contrib.GatedDeltaRule(*[mx.sym.Variable(n) for n in names])
    shapes = {n: x.shape for n, x in zip(names, nds)}
    _, out_shapes, _ = sym.infer_shape(**shapes)
    assert out_shapes == [(2, 2, 128, 16)]
    ex = sym.bind(mx.cpu(), dict(zip(names, nds)),
                  args_grad={n: mx.nd.zeros(x.shape) for n, x in zip(names, nds)})
    ex.forward(is_train=True)
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, atol=1e-5)
    ex.backward(mx.nd.ones((2, 2, 128, 16)))
    ref = jax.grad(lambda *a: jnp.sum(recurrence(*a)), argnums=(3,))(
        q, k, v, g, beta)[0]
    np.testing.assert_allclose(ex.grad_dict["g"].asnumpy(), ref, atol=2e-3,
                               rtol=2e-3)


def test_a_row_that_is_no_multiple_of_the_chunk_is_refused():
    (q, k, v, g, beta), _ = inputs(8, 128, "fast", "both")
    with pytest.raises(ValueError, match="multiple of the chunk"):
        dr.gated_delta_rule(q[:, :, :100], k[:, :, :100], v[:, :, :100],
                            g[:, :, :100], beta[:, :, :100])


def test_what_the_op_reports():
    from mxtpu import telemetry
    (q, k, v, g, beta), w = inputs(9, 128, "mixed", "both")
    jax.grad(lambda q: jnp.sum(dr.gated_delta_rule(q, k, v, g, beta) * w))(q)
    assert telemetry.gauge("delta_rule_chunk").value == 64
    assert telemetry.gauge("delta_rule_chunks_per_row").value == 2
    assert telemetry.gauge("delta_rule_state_saved_bytes").value == \
        2 * 2 * 2 * 8 * 16 * 4
