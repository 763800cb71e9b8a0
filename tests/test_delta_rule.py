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


def flat(x):
    """(B, H, ...) -> (B*H, ...), as the op hands its operands on."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


@pytest.mark.parametrize("t,decay,beta_side", CASES)
def test_the_kernels_are_the_scan_path(t, decay, beta_side):
    """The chip runs the Pallas kernels, the CPU the same chunk functions
    under lax.scan and vmap. The kernels in interpret mode (grid, block
    specs, the state carried in scratch, the backward's reversed walk) give
    what the scan gives, forward and backward; only this test runs them on
    the CPU."""
    args, w = inputs(5, t, decay, beta_side)
    args = [flat(x) for x in args]
    o_k, s_k, t_k = dr._fwd_call(*args, 64, True, interpret=True)
    o_s, s_s, t_s = dr._scan_fwd(*args, 64, True)
    np.testing.assert_allclose(o_k, o_s, atol=1e-5)
    np.testing.assert_allclose(s_k, s_s, atol=1e-5)
    np.testing.assert_allclose(t_k, t_s, atol=1e-5)
    assert s_k.shape == (4, t // 64, 8, 16)   # a state a chunk, none a token
    # a float32 inverse a chunk for float32 operands (nothing is rounded
    # that was not before), a grid step's side by side
    per_step = dr._per_step(t // 64)
    assert t_k.shape == (4, t // 64 // per_step, 64, 64 * per_step)
    assert t_k.dtype == jnp.float32
    do = flat(w)
    for a, b in zip(dr._bwd_call(*args, s_k, t_k, do, 64, interpret=True),
                    dr._scan_bwd(*args, s_s, t_s, do, 64)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_kernels_hand_over_a_bfloat16_inverse():
    """bfloat16 operands at four chunks a grid step, as the hybrid cell runs
    them: the forward kernel keeps T in bfloat16, the cast its own product
    takes, and the backward kernel reads that; both as the scan path, to a
    bfloat16 spacing."""
    args, w = inputs(12, 256, "mixed", "both")
    bf = jnp.bfloat16
    q, k, v, g, beta = [flat(x) for x in args]
    args = (q.astype(bf), k.astype(bf), v.astype(bf), g, beta.astype(bf))
    kernel = dr._fwd_call(*args, 64, True, interpret=True)
    scan = dr._scan_fwd(*args, 64, True)
    assert kernel[2].shape == (4, 1, 64, 4 * 64) and kernel[2].dtype == bf
    do = flat(w).astype(bf)
    for a, b in zip(
            kernel + dr._bwd_call(*args, *kernel[1:], do, 64, interpret=True),
            scan + dr._scan_bwd(*args, *scan[1:], do, 64)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-5, rtol=2 ** -7)


def products_by_precision(jaxpr, found=None):
    """{precision: dot_generals}, through every sub-jaxpr (a scan's body is
    counted once, whatever its length)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            key = eqn.params["precision"]
            found[key] = found.get(key, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            products_by_precision(sub, found)
    return found


def test_only_the_forward_takes_the_inverse():
    """The inverse's products are the op's only ones at `highest`: ten a
    chunk body (five levels of two) in the forward, none in the backward,
    which reads the forward's. `inputs` is float32; the count does not
    depend on the dtype."""
    args, w = inputs(10, 128, "mixed", "both")
    args = [flat(x) for x in args]
    hi = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    for with_states in (False, True):
        fwd = products_by_precision(jax.make_jaxpr(
            lambda *a: dr._scan_fwd(*a, 64, with_states))(*args).jaxpr)
        assert fwd.get(hi) == 10, fwd
    _, s, t_inv = dr._scan_fwd(*args, 64, True)
    bwd = products_by_precision(jax.make_jaxpr(
        lambda *a: dr._scan_bwd(*a, 64))(*args, s, t_inv, flat(w)).jaxpr)
    assert bwd and hi not in bwd, bwd


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_handed_over_inverse_is_the_rebuilt_one(dtype):
    """`_chunk_bwd` with the inverse the forward kept against `_chunk_bwd`
    with the inverse taken again from k, g and beta as the backward used
    to: the same bits in every gradient, in float32 and with bfloat16
    operands (the forward keeps T as it cast it for its own product)."""
    args, w = inputs(11, 192, "mixed", "both")
    q, k, v, g, beta = [flat(x) for x in args]
    q, k, v, beta, do = (x.astype(dtype) for x in (q, k, v, beta, flat(w)))
    _, states, kept = dr._scan_fwd(q, k, v, g, beta, 64, True)
    assert kept.dtype == dtype
    kept = dr._one_by_one(kept, 64)
    ds = jnp.zeros((4, 8, 16), jnp.float32)
    for n in reversed(range(3)):
        at = slice(64 * n, 64 * (n + 1))
        rows = (g[:, None, at], beta[:, None, at])
        rebuilt = jax.vmap(lambda q, k, g_row, b_row: dr._inverse(
            dr._chunk_prep(q, k, g_row, b_row)["A"]).astype(q.dtype))(
                q[:, at], k[:, at], *rows)
        got, want = (jax.vmap(dr._chunk_bwd)(
            states[:, n], t_inv, q[:, at], k[:, at], v[:, at], *rows,
            do[:, at], ds) for t_inv in (kept[:, n], rebuilt))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        ds = got[-1]


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
    # B*H * (T/C) * C * C numbers of the operands' dtype
    assert telemetry.gauge("delta_rule_inverse_saved_bytes").value == \
        2 * 2 * 2 * 64 * 64 * 4
    q, k, v, beta = (x.astype(jnp.bfloat16) for x in (q, k, v, beta))
    jax.grad(lambda q: jnp.sum(
        dr.gated_delta_rule(q, k, v, g, beta).astype(jnp.float32)))(q)
    assert telemetry.gauge("delta_rule_inverse_saved_bytes").value == \
        2 * 2 * 2 * 64 * 64 * 2
