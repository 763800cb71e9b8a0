"""Cross-device consistency on the real accelerator: the same symbol run
on CPU and on the TPU must agree on outputs AND gradients within a
dtype-appropriate tolerance ladder.

Model: the reference's second trust tier — tests/python/gpu/
test_operator_gpu.py check_consistency, which runs every op on cpu+gpu
contexts and compares. Run with:

    MXTPU_TEST_TPU=1 python -m pytest tests/ -m tpu -q
"""
import contextlib

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import sym
from mxtpu.test_utils import check_consistency

pytestmark = pytest.mark.tpu


def _require_accel():
    """The tier was asked for (MXTPU_TEST_TPU=1): a missing accelerator is
    a failure, never a skip that exits 0 with nothing checked."""
    import jax
    dev = jax.devices()[0]
    assert dev.platform != "cpu", (
        "MXTPU_TEST_TPU=1 but jax found no accelerator (platform %r)"
        % dev.platform)
    return mx.tpu()


def _ctx_list(accel, **shapes):
    return [dict(ctx=mx.cpu(), **shapes), dict(ctx=accel, **shapes)]


def test_dense_mlp_consistency():
    accel = _require_accel()
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=8, name="fc2")
    # 'highest' on TPU is 3-pass bf16, not bit-exact f32: ~1e-4 relative
    # residual through two matmul layers + tanh backward
    check_consistency(net, _ctx_list(accel, data=(4, 10)),
                      rtol=5e-3, atol=1e-3)


def test_conv_bn_relu_consistency():
    accel = _require_accel()
    data = sym.Variable("data")
    net = sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                          name="conv")
    net = sym.BatchNorm(net, fix_gamma=False, name="bn")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    check_consistency(net, _ctx_list(accel, data=(2, 3, 8, 8)),
                      rtol=2e-3, atol=2e-3)


def test_softmax_head_consistency():
    accel = _require_accel()
    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    check_consistency(net, _ctx_list(accel, data=(4, 6),
                                     softmax_label=(4,)),
                      rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("opname", [
    "exp", "log", "sqrt", "rsqrt", "sigmoid", "tanh", "erf", "relu",
    "square", "abs", "cbrt", "log1p", "expm1", "sin", "cos",
])
def test_unary_consistency(opname):
    accel = _require_accel()
    data = sym.Variable("data")
    # positive-domain inputs keep log/sqrt/rsqrt well-defined on both
    net = getattr(sym, opname)(sym._plus_scalar(sym.square(data),
                                                scalar=0.5))
    # TPU transcendental approximations (tanh/erf) carry ~4e-4 relative
    # error vs the CPU libm reference
    check_consistency(net, _ctx_list(accel, data=(3, 5)),
                      rtol=2e-3, atol=5e-4)


@pytest.mark.parametrize("opname", [
    "broadcast_add", "broadcast_mul", "broadcast_maximum", "dot",
    "batch_dot",
])
def test_binary_consistency(opname):
    accel = _require_accel()
    shapes = {"dot": ((4, 5), (5, 3)), "batch_dot": ((2, 3, 4), (2, 4, 3))
              }.get(opname, ((4, 5), (4, 5)))
    net = getattr(sym, opname)(sym.Variable("lhs"), sym.Variable("rhs"))
    check_consistency(net, _ctx_list(accel, lhs=shapes[0], rhs=shapes[1]),
                      rtol=1e-3, atol=1e-4)


def test_reduction_consistency():
    accel = _require_accel()
    data = sym.Variable("data")
    net = sym.Group([sym.sum(data), sym.max(data), sym.mean(data),
                     sym.norm(data)])
    check_consistency(net, _ctx_list(accel, data=(6, 7)),
                      rtol=1e-3, atol=1e-4)


def test_resnet_block_forward_consistency():
    """One bottleneck block fwd+bwd, the bench model's building block."""
    accel = _require_accel()
    data = sym.Variable("data")
    b = sym.Convolution(data, kernel=(1, 1), num_filter=8, no_bias=True,
                        name="c1")
    b = sym.BatchNorm(b, fix_gamma=False, name="b1")
    b = sym.Activation(b, act_type="relu")
    b = sym.Convolution(b, kernel=(3, 3), pad=(1, 1), num_filter=8,
                        no_bias=True, name="c2")
    b = sym.BatchNorm(b, fix_gamma=False, name="b2")
    net = sym.Activation(sym.elemwise_add(
        sym.Convolution(data, kernel=(1, 1), num_filter=8, no_bias=True,
                        name="sc"), b), act_type="relu")
    check_consistency(net, _ctx_list(accel, data=(2, 4, 8, 8)),
                      rtol=2e-3, atol=2e-3)


def test_grouped_and_depthwise_conv_consistency():
    """Grouped (resnext cardinality) and depthwise (mobilenet) convs:
    feature_group_count lowering must agree between CPU and the chip."""
    accel = _require_accel()
    data = sym.Variable("data")
    g = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                        num_group=4, no_bias=True, name="grouped")
    check_consistency(g, _ctx_list(accel, data=(2, 8, 6, 6)),
                      rtol=2e-3, atol=2e-3)
    dw = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=8,
                         num_group=8, no_bias=True, name="depthwise")
    check_consistency(dw, _ctx_list(accel, data=(2, 8, 6, 6)),
                      rtol=2e-3, atol=2e-3)


def _attention_ref(q, k, v, causal):
    """float32 attention at full matmul precision, (B, H, T, D)."""
    import jax
    import jax.numpy as jnp
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t, sl = q.shape[2], k.shape[2]
        s = jnp.where(np.tril(np.ones((t, sl), bool)), s, -1e30)
    return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, axis=-1), v)


# (dtype, B, H, T, D, block_q, block_k, rtol/atol)
_FLASH_CASES = [
    # f32, blocks divide T
    ("float32", 2, 4, 384, 64, 128, 128, 2e-3),
    # tail block: kv_len % block_k != 0 (the padded-V zeroing path) and a
    # ragged last q block
    ("float32", 2, 2, 320, 64, 128, 128, 2e-3),
    # the LM's geometry: bf16, head_dim 128, default block sizes
    ("bfloat16", 2, 4, 1024, 128, 512, 1024, 2e-2),
    # bf16 with a tail block
    ("bfloat16", 1, 4, 640, 128, 256, 256, 2e-2),
    # opt-1.3b-fit-s1024's own shape: head 64, the grid kernel's blocks
    ("bfloat16", 4, 32, 1024, 64, 512, 1024, 2e-2),
    # blocks left to the shape (0): the forward that walks its key blocks.
    # Both LM cells' shapes (the whole sequence a query block, the diagonal
    # in strips of 512 keys), f32 over three blocks a side, a caller's 256
    # and a caller's 512 x 256 (a loop under the diagonal, then two strips)
    ("bfloat16", 4, 32, 1024, 64, 0, 0, 2e-2),
    ("bfloat16", 4, 30, 2048, 128, 0, 0, 2e-2),
    ("float32", 2, 4, 384, 64, 0, 0, 2e-3),
    ("bfloat16", 1, 4, 1024, 128, 256, 256, 2e-2),
    ("bfloat16", 1, 4, 1024, 128, 512, 256, 2e-2),
]


@pytest.mark.parametrize("dtype,B,H,T,D,bq,bk,tol", _FLASH_CASES)
def test_pallas_flash_kernel_on_chip(dtype, B, H, T, D, bq, bk, tol):
    """The Mosaic-compiled flash kernels, forward and backward, against
    float32 reference math on the chip — values and gradients. CPU runs
    reach the same kernels only in interpret mode, so this validates the
    lowered kernels themselves. T=320, T=640 with 256-wide blocks and a
    caller's 512 x 1024 do not tile for the walked forward and take the
    grid kernel; T=320 does not tile for the backward either and takes the
    reference's VJP."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention as att
    _require_accel()
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D) * s, dtype)
               for s in (0.5, 0.5, 1.0))

    def flash(a, b, c):
        return att.flash_attention(a, b, c, causal=True, block_q=bq,
                                   block_k=bk)

    def flash_loss(a, b, c):
        return flash(a, b, c).astype(jnp.float32).sum()

    hlo = jax.jit(flash).lower(q, k, v).as_text()
    assert "tpu_custom_call" in hlo, "flash forward is not a Mosaic call"
    if att._bwd_blocks(T, T, D, q.dtype.itemsize, True) is not None:
        hlo = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2))).lower(
            q, k, v).as_text()
        assert hlo.count("tpu_custom_call") >= 2, \
            "the gradient holds no Mosaic backward beside the forward"
    with jax.default_matmul_precision("highest"):
        expect = _attention_ref(q, k, v, True)
        g_ref = jax.grad(lambda a, b, c: _attention_ref(
            a, b, c, True).sum(), argnums=(0, 1, 2))(q, k, v)
    # f32 operands take the MXU's multi-pass f32 path only when asked;
    # Mosaic rejects that precision for bf16 operands
    with jax.default_matmul_precision("highest") if dtype == "float32" \
            else contextlib.nullcontext():
        out = flash(q, k, v)
        g = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, "f4"), np.asarray(expect),
                               rtol=tol, atol=tol)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got, "f4"),
                                   np.asarray(want, "f4"),
                                   rtol=5 * tol, atol=5 * tol)


@pytest.mark.parametrize("m,c", [
    (4096, 256),
    (256 * 56 * 56 // 64, 64),   # ResNet-50 stage-1 width: C < 128 lanes
    (256 * 56 * 56 // 64, 256),  # ResNet-50 stage-1 block output
])
@pytest.mark.parametrize("residual", [True, False])
def test_pallas_epilogue_kernel_on_chip(m, c, residual):
    """The Mosaic-compiled BN-apply+ReLU+add epilogue (ops/epilogue.py)
    against the float32 XLA formulation on the chip."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops.epilogue import (bn_apply_relu_add,
                                    bn_apply_relu_add_reference, fold_bn)
    _require_accel()
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(m, c), jnp.bfloat16)
    r = jnp.asarray(rng.randn(m, c), jnp.bfloat16) if residual else None
    scale, shift = fold_bn(jnp.asarray(rng.rand(c) + 0.5, jnp.float32),
                           jnp.asarray(rng.randn(c), jnp.float32),
                           jnp.asarray(rng.randn(c), jnp.float32),
                           jnp.asarray(rng.rand(c) + 0.1, jnp.float32))
    hlo = bn_apply_relu_add.lower(x, scale, shift, r).as_text()
    assert "tpu_custom_call" in hlo, "epilogue is not a Mosaic call"
    got = np.asarray(bn_apply_relu_add(x, scale, shift, r), "f4")
    want = np.asarray(bn_apply_relu_add_reference(
        x.astype(jnp.float32), scale, shift,
        None if r is None else r.astype(jnp.float32)))
    # one bf16 rounding of the output: 2^-8 relative
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def _rel(got, want):
    import jax.numpy as jnp
    got, want = (jnp.asarray(a, "float32") for a in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _with_grads(f):
    """(f(*operands), its gradients under the weights w, the last argument)
    as one program."""
    import jax

    def run(*args):
        out, vjp = jax.vjp(f, *args[:-1])
        return (out,) + vjp(args[-1].astype(out.dtype))
    return jax.jit(run)


_HEAD_ROPES = {
    "default": dict(rotary_dims=0, rope_type="default", theta=10000.0),
    "yarn": dict(rotary_dims=64, rope_type="yarn", theta=500000.0,
                 factor=32.0, original_max_position=4096, beta_fast=32.0,
                 beta_slow=1.0, scale=1.4852),
}


@pytest.mark.parametrize("heads,kind", [(72, "default"), (48, "yarn"),
                                        (8, "yarn")])
def test_head_prep_kernels_on_chip(heads, kind):
    """laguna-s-2.1-fit-s4096's head preparation at the cell's sizes, the
    Mosaic kernels: values and both gradients against the chain it replaces
    (RMSNorm, transpose, RotaryEmbedding) taken in float32, at a bfloat16
    rounding or two, and no further from it than the bfloat16 chain is."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import heads as hd, rotary
    from mxtpu.ops.nn import _rms_norm
    from mxtpu.ops.registry import AttrDict
    _require_accel()
    b, t, dh, rope = 2, 4096, 128, _HEAD_ROPES[kind]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, t, heads * dh), "bfloat16")
    gamma = jnp.asarray(1.0 + 0.2 * rng.randn(dh), "bfloat16")
    w = jnp.asarray(rng.randn(b, heads, t, dh), "bfloat16")

    def fused(x, gamma):
        return hd.head_norm_rotary(x, gamma, heads, 1e-6, **rope)

    def chain(x, gamma):
        p = _rms_norm(AttrDict(axis=-1, eps=1e-6),
                      x.reshape(b, t, heads, dh), gamma)
        return rotary.rotary_embedding(p.transpose(0, 2, 1, 3), **rope)

    assert _with_grads(fused).lower(x, gamma, w).as_text().count(
        "tpu_custom_call") == 2
    got = _with_grads(fused)(x, gamma, w)
    old = _with_grads(chain)(x, gamma, w)
    want = _with_grads(chain)(*(a.astype("float32") for a in (x, gamma, w)))
    for new_, old_, want_ in zip(got, old, want):
        assert new_.dtype == jnp.bfloat16
        assert _rel(new_, want_) < 6e-3
        assert _rel(new_, want_) <= 1.05 * _rel(old_, want_)


@pytest.mark.parametrize("heads", [72, 48])
def test_head_gate_kernels_on_chip(heads):
    """The head gate at the cell's sizes against transpose, sigmoid,
    broadcast multiply and reshape in float32."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import heads as hd
    _require_accel()
    b, t, dh = 2, 4096, 128
    rng = np.random.RandomState(1)
    att = jnp.asarray(rng.randn(b, heads, t, dh), "bfloat16")
    g = jnp.asarray(rng.randn(b, t, heads), "bfloat16")
    w = jnp.asarray(rng.randn(b, t, heads * dh), "bfloat16")

    def chain(att, g):
        out = att.transpose(0, 2, 1, 3) * jax.nn.sigmoid(g)[..., None]
        return out.reshape(b, t, heads * dh)

    assert _with_grads(hd.head_gate).lower(att, g, w).as_text().count(
        "tpu_custom_call") == 2
    got = _with_grads(hd.head_gate)(att, g, w)
    old = _with_grads(chain)(att, g, w)
    want = _with_grads(chain)(*(a.astype("float32") for a in (att, g, w)))
    for new_, old_, want_ in zip(got, old, want):
        assert new_.dtype == jnp.bfloat16
        assert _rel(new_, want_) < 6e-3
        assert _rel(new_, want_) <= 1.05 * _rel(old_, want_)


def _as_the_chain_does(got, old, want, dtypes):
    """Each result of the operator at a bfloat16 rounding or two from the
    float32 chain, and no further from it than the bfloat16 chain is."""
    for new_, old_, want_, dtype in zip(got, old, want, dtypes):
        assert new_.dtype == dtype
        assert _rel(new_, want_) < 6e-3
        assert _rel(new_, want_) <= 1.05 * _rel(old_, want_)


@pytest.mark.parametrize("b,t,c,bias,calls", [
    (2, 4096, 6144, True, 1),    # nemotron-twotower-30b-fit-s4096's xBC
    (4, 2048, 5760, False, 1),   # olmo-hybrid-7b-fit-s2048's v
    (4, 2048, 2880, False, 1),   # its q and k: 22.5 lane tiles, whole rows
    (2, 1000, 256, True, 0),     # T does not tile: the plain body
])
def test_short_conv_kernel_on_chip(b, t, c, bias, calls):
    """The short convolution with its SiLU at the cells' sizes: values and
    the data's, the filter's and the bias's gradients against the chain it
    replaces (the convolution rounded, then `Activation`) taken in float32;
    the backward one Mosaic call where the shape tiles."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import mixers
    _require_accel()
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(b, t, c), "bfloat16")
    args = [x, jnp.asarray(0.29 * rng.randn(c, 4), "bfloat16")]
    if bias:
        args.append(jnp.asarray(0.1 * rng.randn(c), "bfloat16"))
    args.append(jnp.asarray(rng.randn(b, t, c), "bfloat16"))

    def fused(x, w, bias=None):
        return mixers.short_conv(x, w, bias, 4, "silu")

    def chain(x, w, bias=None):
        return jax.nn.silu(mixers._conv_plain(x, w, bias, 4, "none"))

    assert _with_grads(fused).lower(*args).as_text().count(
        "tpu_custom_call") == calls
    got = _with_grads(fused)(*args)
    old = _with_grads(chain)(*args)
    want = _with_grads(chain)(*(a.astype("float32") for a in args))
    _as_the_chain_does(got, old, want, [jnp.bfloat16] * len(got))


@pytest.mark.parametrize("shape,groups,gate_first", [
    ((2, 4096, 4096), 8, True),      # nemotron-twotower-30b-fit-s4096
    ((4, 2048, 30, 192), 1, False),  # olmo-hybrid-7b-fit-s2048: pairs of heads
])
def test_gated_norm_kernels_on_chip(shape, groups, gate_first):
    """The gated output norm at the cells' sizes, the Mosaic kernels: values
    and the data's, gamma's and the gate's gradients against the chain it
    replaces (Nemotron: `y * Activation(z)` rounded, then the grouped
    `RMSNorm`; hybrid: the norm, then the gate, in one float32 body) taken
    in float32."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import mixers
    from mxtpu.ops.nn import _rms_norm
    from mxtpu.ops.registry import AttrDict
    _require_accel()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape), "bfloat16")
    gamma = jnp.asarray(1.0 + 0.2 * rng.randn(shape[-1]), "bfloat16")
    gate, w = (jnp.asarray(rng.randn(*shape), "bfloat16") for _ in range(2))
    plain = AttrDict(axis=-1, eps=1e-5, groups=groups)

    def fused(x, gamma, gate):
        return mixers.gated_norm(x, gamma, gate, 1e-5, groups, gate_first)

    def chain(x, gamma, gate):
        if gate_first:
            return _rms_norm(plain, x * jax.nn.silu(gate), gamma)
        out = _rms_norm(plain, x.astype("float32"), gamma.astype("float32"))
        return (out * jax.nn.silu(gate.astype("float32"))).astype(x.dtype)

    assert _with_grads(fused).lower(x, gamma, gate, w).as_text().count(
        "tpu_custom_call") == 2
    got = _with_grads(fused)(x, gamma, gate, w)
    old = _with_grads(chain)(x, gamma, gate, w)
    want = _with_grads(chain)(
        *(a.astype("float32") for a in (x, gamma, gate, w)))
    _as_the_chain_does(got, old, want, [jnp.bfloat16] * 4)


def test_ssd_kernels_on_chip():
    """nemotron-twotower-30b-fit-s4096's state-space scan at the cell's
    sizes, the Mosaic kernels with bfloat16 operands: the output and all six
    gradients against the op's chunk path in float32 at `highest` (plain
    JAX, which the CPU tests tie to the token-by-token recurrence), at a
    bfloat16 rounding or two (my chip run, PR 34: 0.0018 and 0.0007-0.0030)."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import ssd
    _require_accel()
    b, t, h, p, g, n, chunk = 2, 4096, 64, 64, 8, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (b, t, h, p))).astype("bfloat16")
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    a_log = 2.0 * jax.random.normal(ks[2], (h,))
    bm, cm = (jax.nn.silu(jax.random.normal(k, (b, t, g, n))).astype("bfloat16")
              for k in ks[3:5])
    d = jnp.ones((h,))
    w = jax.random.normal(ks[5], (b, t, h, p))

    def kernels(*a):
        return ssd.ssd_scan(*a, chunk=chunk)

    def plain(x, dt, a_log, bm, cm, d):
        r, f32 = h // g, jnp.float32

        def rows(z):
            z = z.astype(f32).reshape(b, t // chunk, chunk, g, r)
            return jnp.transpose(z, (0, 3, 1, 4, 2))

        ops = (x.astype(f32).reshape(b, t, h * p), rows(dt),
               jnp.cumsum(rows(dt * -jnp.exp(a_log)), -1),
               bm.astype(f32).reshape(b, t, g * n),
               cm.astype(f32).reshape(b, t, g * n),
               jnp.repeat(d, p).reshape(g, 1, r * p))
        return ssd._scan_fwd(*ops, p, False).reshape(b, t, h, p)

    def with_grads(fn):
        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32) * w)
        return jax.jit(lambda *a: (fn(*a),) + jax.grad(
            loss, argnums=tuple(range(6)))(*a))

    args = (x, dt, a_log, bm, cm, d)
    # the forward for y alone, the differentiated forward, the backward
    text = with_grads(kernels).lower(*args).as_text()
    assert text.count("tpu_custom_call") == 3 and "stablehlo.while" not in text
    got = with_grads(kernels)(*args)
    with jax.default_matmul_precision("highest"):
        want = with_grads(plain)(*(a.astype("float32") for a in args))
    assert got[0].dtype == jnp.bfloat16
    for name, a, b_ in zip(("y", "dx", "ddt", "dA_log", "dB", "dC", "dD"),
                           got, want):
        assert _rel(a, b_) < 8e-3, (name, _rel(a, b_))


@pytest.mark.parametrize("d,f,k,experts,gated", [
    (3072, 1024, 10, 256, True),    # laguna-s-2.1-fit-s4096
    (2688, 1856, 6, 128, False),    # nemotron-twotower-30b-fit-s4096
    (2688, 1856, 6, 8, False),      # a deployment: 6 of the 8 held a token
], ids=["laguna", "nemotron", "thirteen_trips"])
def test_moe_combine_kernel_on_chip(d, f, k, experts, gated):
    """The expert layer (8,192 tokens, 8 experts held, bfloat16) at the two
    cells' sizes, one trip, and with every token routed to 6 of the 8 held,
    6,144 rows an expert, thirteen trips of 4,096 rows: with the combine
    kernel, one call a direction and, behind a first trip, one a trip into
    the float32 sum the loop carries, against the same layer with the plain
    combine, the scatter-add; the output and the data's, the routing
    weights' and every stacked leaf's gradients at a bfloat16 rounding of
    each other, since the two sum the same float32 rows in another order."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import moe
    _require_accel()
    ks = jax.random.split(jax.random.PRNGKey(42), 6)
    x = jax.random.normal(ks[0], (8192, d), jnp.bfloat16)
    index = jnp.argsort(jax.random.uniform(ks[1], (8192, experts)),
                        axis=-1)[:, :k].astype(jnp.int32)
    weight = jax.random.uniform(ks[2], (8192, k), jnp.float32, 0.1, 1.0)
    ups = [(jax.random.normal(ks[3 + i], (8, f, d)) * 0.02).astype("bfloat16")
           for i in range(2 if gated else 1)]
    down = (jax.random.normal(ks[5], (8, d, f)) * 0.02).astype("bfloat16")
    w = jax.random.normal(ks[0], (8192, d))
    trips = -(-int(moe._plan_tiled(index, 8, 0, moe.TILE)[1][-1])
              // moe.CHUNK)
    assert trips == (13 if experts == 8 else 1), trips

    def layer(x, weight, *leaves):
        return moe.moe_experts(x, weight, index, leaves[0] if gated else None,
                               leaves[-2], leaves[-1], experts, 0)[0]

    args = (x, weight, *ups, down, w)
    assert moe.COMBINE_KERNEL_NAME in \
        _with_grads(layer).lower(*args).as_text()
    got = _with_grads(layer)(*args)
    blocks, moe._combine_blocks = moe._combine_blocks, lambda *a: None
    try:
        assert moe.COMBINE_KERNEL_NAME not in \
            _with_grads(layer).lower(*args).as_text()
        plain = _with_grads(layer)(*args)
    finally:
        moe._combine_blocks = blocks
    assert got[0].dtype == jnp.bfloat16
    for a, b_ in zip(got, plain):
        assert a.dtype == b_.dtype
        assert _rel(a, b_) < 6e-3
