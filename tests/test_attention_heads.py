"""The attention block's two one-pass operators against the chains they
replace: ``_contrib_HeadNormRotary`` against ``RMSNorm`` -> ``transpose`` ->
``_contrib_RotaryEmbedding`` and ``_contrib_HeadGate`` against ``transpose``
-> ``broadcast_mul(sigmoid)`` -> ``reshape``, values and every gradient; the
Mosaic bodies in Pallas' interpret mode against the plain bodies; the Laguna
member's arguments and shapes against the list of the commit before the
operators (3763f4c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxtpu.ops import heads, rotary
from mxtpu.ops.nn import _rms_norm
from mxtpu.ops.registry import AttrDict

# the two kinds the Laguna cell runs, at a head of 16 (the rehearsal's) and
# of 128 (the cell's): every dim paired (i, i + dh/2), and YaRN over the
# first half of the dims, scaled
ROPES = {
    "default": lambda dh: dict(rotary_dims=0, rope_type="default",
                               theta=10000.0),
    "yarn": lambda dh: dict(rotary_dims=dh // 2, rope_type="yarn",
                            theta=500000.0, factor=32.0,
                            original_max_position=4096, beta_fast=32.0,
                            beta_slow=1.0, scale=1.4852),
    "none": lambda dh: dict(rope_type="none"),
}
TOL = {"float32": dict(rtol=2e-5, atol=2e-5),
       # one rounding where the chain has two or three
       "bfloat16": dict(rtol=4e-2, atol=4e-2)}


def _series(name, labels=None):
    from mxtpu import telemetry
    if labels is None:
        return telemetry.gauge(name).value
    return telemetry.counter(name, labels=labels).value


def _chain_prep(x, gamma, n, eps, rope):
    b, t, width = x.shape
    p = _rms_norm(AttrDict(axis=-1, eps=eps),
                  x.reshape(b, t, n, width // n), gamma)
    p = p.transpose(0, 2, 1, 3)
    if rope["rope_type"] == "none":
        return p
    return rotary.rotary_embedding(p, **rope)


def _chain_gate(att, g):
    b, h, t, dh = att.shape
    out = att.transpose(0, 2, 1, 3) * jax.nn.sigmoid(g).reshape(b, t, h, 1)
    return out.reshape(b, t, h * dh)


def _close(got, want, dtype, scale=1.0):
    tol = {k: v * scale for k, v in TOL[dtype].items()}
    np.testing.assert_allclose(np.asarray(got, "f4"), np.asarray(want, "f4"),
                               **tol)


def _prep_case(dtype, n, t, dh, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(2, t, n * dh), dtype),
            jnp.asarray(1.0 + 0.2 * rng.randn(dh), dtype),
            jnp.asarray(rng.randn(2, n, t, dh), "float32"))


def _value_and_grads(f, w):
    """(f(*args), its gradients under the weights w), one program."""
    def both(*args):
        out, vjp = jax.vjp(f, *args)
        return (out,) + vjp(w.astype(out.dtype))
    return jax.jit(both)


# query heads of a window layer and of a full layer at the rehearsal's sizes
# (6 and 4 over 2 of 16, for 72 and 48 over 8 of 128), a head of 128 over a
# row block, the shape that tiles, and rows that do not
@pytest.mark.parametrize("kind,n,t,dh,path", [
    ("default", 6, 64, 16, "composed"), ("yarn", 6, 64, 16, "composed"),
    ("default", 4, 64, 16, "composed"), ("yarn", 4, 64, 16, "composed"),
    ("none", 2, 64, 16, "composed"),
    ("default", 3, 128, 128, "fused"), ("yarn", 3, 128, 128, "fused"),
    ("yarn", 2, 96, 128, "composed")])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_head_prep_is_the_chain_it_replaces(dtype, kind, n, t, dh, path):
    rope = ROPES[kind](dh)
    x, gamma, w = _prep_case(dtype, n, t, dh)

    def new(x, gamma):
        return heads.head_norm_rotary(x, gamma, n, 1e-6, **rope)

    def old(x, gamma):
        return _chain_prep(x, gamma, n, 1e-6, rope)

    before = _series("attention_prep_builds", {"path": path})
    got, want = (_value_and_grads(f, w)(x, gamma) for f in (new, old))
    assert _series("attention_prep_builds", {"path": path}) == before + 1
    assert _series("attention_prep_saved_bytes") == x.size * x.dtype.itemsize
    assert got[1].dtype == x.dtype and got[2].dtype == gamma.dtype
    _close(got[0], want[0], dtype)
    _close(got[1], want[1], dtype)
    # a sum over 2 * t * n rows
    _close(got[2], want[2], dtype, scale=(2 * t * n) ** 0.5)


@pytest.mark.parametrize("h,t,dh,path", [(6, 64, 16, "composed"),
                                         (4, 64, 16, "composed"),
                                         (3, 128, 128, "fused"),
                                         (2, 96, 128, "composed")])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_head_gate_is_the_chain_it_replaces(dtype, h, t, dh, path):
    rng = np.random.RandomState(1)
    att = jnp.asarray(rng.randn(2, h, t, dh), dtype)
    g = jnp.asarray(rng.randn(2, t, h), dtype)
    w = jnp.asarray(rng.randn(2, t, h * dh), "float32")
    before = _series("attention_gate_builds", {"path": path})
    got, want = (_value_and_grads(f, w)(att, g)
                 for f in (heads.head_gate, _chain_gate))
    assert _series("attention_gate_builds", {"path": path}) == before + 1
    assert got[1].dtype == att.dtype and got[2].dtype == g.dtype
    _close(got[0], want[0], dtype)
    _close(got[1], want[1], dtype)
    _close(got[2], want[2], dtype, scale=dh ** 0.5)


def _tables(kind, t, dh):
    rope = dict(ROPES[kind](dh))
    scale = rope.pop("scale", 1.0)
    freqs = heads._rope_freqs(dh, **rope)
    return (heads._turn_tables(freqs, t, dh, scale),
            heads._shifts(dh, len(freqs)))


@pytest.mark.parametrize("kind", ["default", "yarn"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_head_prep_kernels_in_interpret_mode(dtype, kind):
    """The Mosaic bodies themselves, two groups of two heads and two row
    blocks, so that gamma's partial sums gather over a row block's groups."""
    n, t, dh = 4, 256, 128
    x, gamma, w = _prep_case(dtype, n, t, dh, seed=2)
    gamma = gamma.astype("float32").reshape(1, dh)
    tables, shifts = _tables(kind, t, dh)
    args = (n, 1e-6, shifts)
    _close(heads._prep_fwd_call(x, gamma, tables, *args, (128, 2),
                                interpret=True),
           heads._prep_plain(x, gamma, tables, *args), dtype, scale=0.25)
    dy, back = w.astype(dtype), heads._back(tables)
    got = heads._prep_bwd_call(dy, x, gamma, back, *args, (128, 2),
                               interpret=True)
    want = heads._prep_bwd_plain(dy, x, gamma, back, *args)
    _close(got[0], want[0], dtype, scale=0.25)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_head_gate_kernels_in_interpret_mode(dtype):
    rng = np.random.RandomState(3)
    att = jnp.asarray(rng.randn(2, 6, 256, 128), dtype)
    g = jnp.asarray(rng.randn(2, 256, 6), dtype)
    do = jnp.asarray(rng.randn(2, 256, 6 * 128), dtype)
    _close(heads._gate_fwd_call(att, g, 128, interpret=True),
           heads._gate_plain(att, g), dtype, scale=0.25)
    got = heads._gate_bwd_call(do, att, g, 128, interpret=True)
    want = heads._gate_bwd_plain(do, att, g)
    _close(got[0], want[0], dtype, scale=0.25)
    assert got[1].dtype == jnp.float32
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4)


def test_what_tiles():
    # the cell's shapes: 512 rows of 8 heads a step of the preparation, 128
    # rows of every head a step of the gate
    assert heads._prep_blocks(4096, 72, 128, 2) == (512, 8)
    assert heads._prep_blocks(4096, 48, 128, 2) == (512, 8)
    assert heads._prep_blocks(4096, 8, 128, 2) == (512, 8)
    assert heads._prep_blocks(4096, 6, 128, 4) == (256, 2)
    assert heads._gate_rows(4096, 72, 128, 2) == 128
    assert heads._gate_rows(4096, 8, 128, 2) == 512
    # rows that are no multiple of 128, a head that is none of 128 lanes
    assert heads._prep_blocks(4000, 72, 128, 2) is None
    assert heads._prep_blocks(4096, 72, 64, 2) is None
    assert heads._gate_rows(4096, 72, 96, 2) is None
    with pytest.raises(ValueError):
        heads.head_norm_rotary(jnp.zeros((1, 8, 30)), jnp.ones(10), 4)
    with pytest.raises(ValueError):
        heads.head_gate(jnp.zeros((1, 2, 8, 4)), jnp.zeros((1, 2, 8)))


# layer i of the rehearsal's five: (query heads, dense FFN)
_LAYERS = [(4, True), (6, False), (6, False), (6, False), (4, False)]


def _arguments_of_the_parent():
    """`list_arguments()` and the inferred shapes of the Laguna member at
    the rehearsal's sizes (d 64, head 16, 2 key/value heads, 4 of 16 experts
    of 32 held, vocabulary 512, batch 2 x 64) as commit 3763f4c gave them."""
    out = [("data", (2, 64)), ("tok_emb_weight", (512, 64))]
    for i, (h, dense) in enumerate(_LAYERS):
        layer = [("mix_norm_gamma", (64,)),
                 ("q_weight", (16 * h, 64)), ("q_norm_gamma", (16,)),
                 ("k_weight", (32, 64)), ("k_norm_gamma", (16,)),
                 ("v_weight", (32, 64)), ("gate_weight", (h, 64)),
                 ("proj_weight", (64, 16 * h)), ("ffn_norm_gamma", (64,))]
        if dense:
            layer += [("ff_gate_weight", (128, 64)),
                      ("ff_up_weight", (128, 64)),
                      ("ff_down_weight", (64, 128))]
        else:
            layer += [("router_weight", (16, 64)),
                      ("experts_gate_weight", (4, 32, 64)),
                      ("experts_up_weight", (4, 32, 64)),
                      ("experts_down_weight", (4, 64, 32)),
                      ("shared_ff_gate_weight", (32, 64)),
                      ("shared_ff_up_weight", (32, 64)),
                      ("shared_ff_down_weight", (64, 32))]
        out += [("l%d_%s" % (i, name), shape) for name, shape in layer]
    return out + [("norm_f_gamma", (64,)), ("lm_head_weight", (512, 64)),
                  ("softmax_label", (128,))]


def test_the_laguna_members_arguments_are_the_parents():
    from benchmark import manifest
    cell = manifest.Cell("laguna-s-2.1-fit-s4096", rehearse=True)
    sym = cell.config_module("program").symbol(
        dict(cell.config, dtype="bfloat16"), dict(cell.traffic, seq_len=64))
    want = _arguments_of_the_parent()
    assert len(want) == 81
    assert sym.list_arguments() == [name for name, _ in want]
    assert sym.infer_shape(data=(2, 64))[0] == [shape for _, shape in want]
    types = dict(zip(sym.list_arguments(), sym.infer_type(data="float32")[0]))
    assert {str(np.dtype(types["l1_%s_norm_gamma" % tag])) for tag in "qk"} \
        == {"bfloat16"}
