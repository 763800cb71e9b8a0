"""mxtpu.models.decoder: one builder for the zoo's causal LMs. The OPT-style
member (`transformer.get_symbol`) comes out of it as it was before the
builder existed, graph JSON and parameter names; the hybrid member has the
documented names and shapes, runs, and carries its kernels' names."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxtpu as mx
from mxtpu.models import decoder, transformer

# recorded at the parent commit (b5b3145), before transformer.py delegated,
# each under a fresh NameManager (the unnamed nodes' counters are global)
GOLDEN = {
    "plain": (dict(vocab_size=512, seq_len=32, num_layers=2, num_heads=4,
                   d_model=64, d_ff=128),
              "0a6d12b85faff5de23b7180045b5fdbae9a003d9a31257091dd1cf8c76dc71ff",
              (1, 32, 64)),
    "bf16_maxlen_dropout": (
        dict(vocab_size=512, seq_len=32, num_layers=2, num_heads=4, d_model=64,
             d_ff=128, dropout=0.1, max_len=64, dtype="bfloat16"),
        "225788a9a595a79e76a20bfb73d5758d99a9ebac12db4597f1f700d255bf14fa",
        (1, 64, 64)),
}


def _opt_names(layers):
    names = ["data", "tok_emb_weight", "pos_emb"]
    for i in range(layers):
        p = "l%d_" % i
        names += [p + "ln1_gamma", p + "ln1_beta"]
        names += [p + w + s for w in ("q", "k", "v", "proj")
                  for s in ("_weight", "_bias")]
        names += [p + "ln2_gamma", p + "ln2_beta", p + "ff1_weight",
                  p + "ff1_bias", p + "ff2_weight", p + "ff2_bias"]
    return names + ["ln_f_gamma", "ln_f_beta", "lm_head_weight",
                    "lm_head_bias", "softmax_label"]


@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_the_opt_symbol_is_unchanged_by_the_shared_builder(case):
    kw, sha, pos_shape = GOLDEN[case]
    with mx.name.NameManager():
        sym = transformer.get_symbol(**kw)
    assert hashlib.sha256(sym.tojson().encode()).hexdigest() == sha
    args = sym.list_arguments()
    assert args == _opt_names(2)
    shapes = dict(zip(args, sym.infer_shape(data=(2, 32))[0]))
    assert shapes["pos_emb"] == pos_shape
    assert shapes["l1_ff1_weight"] == (128, 64) and shapes["l0_q_bias"] == (64,)
    assert shapes["lm_head_weight"] == (512, 64)
    assert shapes["softmax_label"] == (64,)


def _hybrid(dtype=None, t=128, layers=None):
    return decoder.get_symbol(
        512, t, layers or [decoder.LINEAR] * 3 + [decoder.FULL], num_heads=4,
        d_model=64, d_ff=128, linear_key_dim=8, linear_value_dim=16,
        dtype=dtype)


def test_the_hybrid_members_parameters():
    sym = _hybrid("bfloat16")
    args = sym.list_arguments()
    shapes = dict(zip(args, sym.infer_shape(data=(2, 128))[0]))
    types = dict(zip(args, sym.infer_type(data="float32")[0]))
    want = {"tok_emb_weight": (512, 64), "norm_f_gamma": (64,),
            "lm_head_weight": (512, 64),
            "l0_q_weight": (32, 64), "l0_k_weight": (32, 64),
            "l0_v_weight": (64, 64), "l0_g_weight": (64, 64),
            "l0_q_conv_weight": (32, 4), "l0_k_conv_weight": (32, 4),
            "l0_v_conv_weight": (64, 4), "l0_a_weight": (4, 64),
            "l0_b_weight": (4, 64), "l0_A_log": (4,), "l0_dt_bias": (4,),
            "l0_o_norm_gamma": (16,), "l0_proj_weight": (64, 64),
            "l0_mix_norm_gamma": (64,), "l0_ffn_norm_gamma": (64,),
            "l0_ff_gate_weight": (128, 64), "l0_ff_up_weight": (128, 64),
            "l0_ff_down_weight": (64, 128),
            "l3_q_weight": (64, 64), "l3_k_weight": (64, 64),
            "l3_v_weight": (64, 64), "l3_proj_weight": (64, 64),
            "l3_q_norm_gamma": (64,), "l3_k_norm_gamma": (64,)}
    for name, shape in want.items():
        assert shapes[name] == shape, name
    # no bias, no position table, no q_conv in the full-attention layer
    assert not [a for a in args if a.endswith("_bias") and "dt_" not in a]
    assert "pos_emb" not in args and "l3_q_conv_weight" not in args
    assert "l3_A_log" not in args and "l2_A_log" in args
    # the decay's leaves are float32 whatever the activations are
    assert str(types["l1_A_log"]) == "float32"
    assert str(types["l1_dt_bias"]) == "float32"
    assert str(types["l1_a_weight"]) == "bfloat16"
    assert str(types["tok_emb_weight"]) == "float32"


def test_an_unknown_layer_type_is_refused():
    with pytest.raises(ValueError, match="unknown layer type"):
        decoder.get_symbol(64, 64, ["sliding_attention"], 2, 16, 32, 4, 8)


def test_the_hybrid_member_trains_through_module_fit():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, size=(4, 129))
    it = mx.io.NDArrayIter(ids[:, :-1].astype(np.float32),
                           ids[:, 1:].astype(np.float32), batch_size=2,
                           label_name="softmax_label")

    class Flat(mx.io.DataIter):
        """Labels flattened to (B*T,), as the LM symbols take them."""
        def __init__(self, inner):
            super().__init__()
            self.inner = inner
            self.batch_size = inner.batch_size
            self.provide_data = inner.provide_data
            self.provide_label = [mx.io.DataDesc("softmax_label", (2 * 128,))]

        def reset(self):
            self.inner.reset()

        def next(self):
            b = self.inner.next()
            b.label = [b.label[0].reshape((-1,))]
            return b

    mod = mx.mod.Module(_hybrid(), context=mx.cpu())
    metric = mx.metric.create("ce")
    mod.fit(Flat(it), num_epoch=3, eval_metric=metric, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5, "momentum": 0.9,
                              "rescale_grad": 1.0 / (2 * 128)},
            initializer=mx.init.Normal(0.02))
    assert mod._fused is not None, "the fused step did not arm"
    first = float(np.log(512))
    assert dict(metric.get_name_value())["cross-entropy"] < first


def test_the_hybrid_member_survives_its_json():
    """The Symbol saved and loaded again is the same graph (the checkpoint
    path): same arguments, same shapes, and the same logits from the same
    weights; its nodes carry no recomputation annotation, the step keeps
    what XLA chooses."""
    layers = [decoder.LINEAR, decoder.FULL]
    sym = _hybrid(layers=layers)
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == sym.list_arguments()
    assert again.infer_shape(data=(2, 128))[0] == sym.infer_shape(data=(2, 128))[0]
    assert not [n.name for n in sym._topo() if not n.is_variable
                and (n._extra_attrs.get("__remat__")
                     or n._extra_attrs.get("__save__"))]
    rng = np.random.default_rng(1)
    data = mx.nd.array(rng.integers(0, 512, size=(2, 128)).astype(np.float32))
    outs = []
    for s in (sym, again):
        mx.random.seed(5)
        mod = mx.mod.Module(s, context=mx.cpu())
        mod.bind(data_shapes=[("data", (2, 128))],
                 label_shapes=[("softmax_label", (256,))], for_training=False)
        mod.init_params(mx.init.Normal(0.02))
        mod.forward(mx.io.DataBatch([data], None), is_train=False)
        outs.append(mod.get_outputs()[0].asnumpy())
    np.testing.assert_array_equal(outs[0], outs[1])


def test_the_kernels_carry_their_names_in_the_lowered_hlo():
    """A Mosaic call's HLO instruction takes its kernel's name; a trace
    reader finds the flash forward, the flash backward and the delta rule's
    two calls by them (benchmark/metrics/delta_rule_roofline.py)."""
    from mxtpu.ops import attention, delta_rule
    q = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)

    def flash(q, k, v):
        return jnp.sum(attention.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    fwd_only = jax.jit(lambda q, k, v: attention.flash_attention(
        q, k, v, causal=True)).trace(q, q, q).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "mxtpu_flash_fwd" in fwd_only and "mxtpu_flash_bwd" not in fwd_only
    both = jax.jit(jax.grad(flash, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "mxtpu_flash_fwd" in both and "mxtpu_flash_bwd" in both
    assert (attention.FWD_KERNEL_NAME, attention.BWD_KERNEL_NAME) == \
        ("mxtpu_flash_fwd", "mxtpu_flash_bwd")

    k = jax.ShapeDtypeStruct((1, 2, 128, 8), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, 128, 16), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 2, 128), jnp.float32)

    def delta(*a):
        return jnp.sum(delta_rule.gated_delta_rule(*a).astype(jnp.float32))

    text = jax.jit(jax.grad(delta, argnums=(0, 1, 2, 3, 4))).trace(
        k, k, v, g, g).lower(lowering_platforms=("tpu",)).as_text()
    assert "mxtpu_delta_rule_fwd" in text and "mxtpu_delta_rule_bwd" in text
