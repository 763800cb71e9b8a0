"""mxtpu.models.decoder: one builder for the zoo's causal LMs. The OPT-style
member (`transformer.get_symbol`) comes out of it as it was before the
builder existed, graph JSON and parameter names; the hybrid member has the
documented names and shapes, runs, and carries its kernels' names."""
import hashlib
import json

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


def _json_less_blocks(sym):
    """(the Symbol's JSON without the nodes' `__block__` attributes, the
    blocks it had): the builder marks every node's model block (PR 36), no
    operator reads the mark, and the recorded graphs predate it."""
    doc = json.loads(sym.tojson())
    blocks = set()
    for node in doc["nodes"]:
        blocks.add(node.get("attrs", {}).pop("__block__", None))
        if node.get("attrs") == {}:
            del node["attrs"]
    return json.dumps(doc, indent=2), blocks


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
    stripped, blocks = _json_less_blocks(sym)
    assert blocks == {None, "embed", "attention", "ffn", "head"}
    assert hashlib.sha256(stripped.encode()).hexdigest() == sha
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


# ------------------------------------------------------ the Laguna member
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402

from mxtpu.ops import rotary  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(_ROOT, "benchmark", "configs", "laguna-s-2.1-train",
                       "config.json")) as _f:
    LAGUNA = json.load(_f)

# sha256 of the symbols' JSON at the parent commit (ae4eb87), each built
# under a NameManager of its own: the OPT and the
# Olmo-Hybrid members as their cells build them, at the published and at the
# rehearsal sizes. Their references and limits depend on names and graph.
PARENT_JSON = {
    ("opt-1.3b-fit-s1024", False):
        "cec68f99864419071f9aefa595857a3098342ba96825a1f53ff208981f3b56a0",
    ("opt-1.3b-fit-s1024", True):
        "fd4823d640e2cf6ef7a6823814d0c38229dc258cf7b78f2c55450cab0ee16fcb",
    # the hybrid member since its short convolutions carry their SiLU as
    # an attribute (PR 37: nine `Activation` nodes fewer, no name moved)
    ("olmo-hybrid-7b-fit-s2048", False):
        "9c05a955222d03d2c8f40ad4a045a1874b2334e2e4308173fc5c9d392439ae1a",
    ("olmo-hybrid-7b-fit-s2048", True):
        "420832ded325d722974cf3e972fc8b51288296eae7a3eaf5c5f24960c5cee7fd",
    # the Laguna member at the commit before the one-sublayer member
    # (23a39d9): the gated expert layer's graph has no attribute it lacked
    ("laguna-s-2.1-fit-s4096", False):
        "bc945c93ab9d8560cacfdf5b0819f08e2f3b076187c180eb1c0a84de23053332",
    ("laguna-s-2.1-fit-s4096", True):
        "df02351f53bb6724a5c37512129c815e1417265c6bc0dc59e530f8be277cb15d",
}


@pytest.mark.parametrize("cell,rehearse", sorted(PARENT_JSON))
def test_the_older_members_json_is_the_parents(cell, rehearse):
    from benchmark import manifest
    c = manifest.Cell(cell, rehearse=rehearse)
    with mx.name.NameManager():
        sym = c.config_module("program").symbol(c.config, c.traffic)
    stripped, blocks = _json_less_blocks(sym)
    assert hashlib.sha256(stripped.encode()).hexdigest() == \
        PARENT_JSON[cell, rehearse]
    assert {"embed", "attention", "ffn", "head"} <= blocks


def test_attention_factor_is_yarns():
    full = LAGUNA["rope_parameters"]["full_attention"]
    assert full["attention_factor"] == pytest.approx(
        0.1 * math.log(full["factor"]) + 1, rel=1e-12)


def _rope_attrs(kind):
    from benchmark import manifest
    cell = manifest.Cell("laguna-s-2.1-fit-s4096")
    return cell.config_module("program")._rope(LAGUNA, kind)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_inverse_frequencies_are_the_formulas(kind):
    """Both of the configuration's sets, at the published head size."""
    a = _rope_attrs(kind)
    got = rotary.inv_freq(a["rotary_dims"], a["rope_type"], a["theta"],
                          a.get("factor", 1.0),
                          a.get("original_max_position", 0),
                          a.get("beta_fast", 32.0), a.get("beta_slow", 1.0))
    if kind == "sliding_attention":
        assert a["rotary_dims"] == 128 and a.get("scale", 1.0) == 1.0
        want = [10000.0 ** (-2 * i / 128) for i in range(64)]
    else:
        assert a["rotary_dims"] == 64
        assert a["scale"] == pytest.approx(1.4852030263919618)

        def c(beta):
            return 64 * math.log(8192 / (2 * math.pi * beta)) / (
                2 * math.log(500000))
        lo, hi = math.floor(c(32)), math.ceil(c(1))
        assert (lo, hi) == (9, 18)
        want = []
        for i in range(32):
            e = 500000.0 ** (-2 * i / 64)
            ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
            want.append(e / 128 * ramp + e * (1 - ramp))
        # below lo the published frequency, above hi a 128th of it
        assert got[0] == pytest.approx(1.0)
        assert got[31] == pytest.approx(500000.0 ** (-62 / 64) / 128)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), rtol=1e-6)


@pytest.mark.parametrize("dims,scale", [(16, 1.0), (8, 1.4852030263919618)])
def test_the_rotation_is_the_formula_and_its_gradient_the_transpose(dims,
                                                                     scale):
    import jax
    import jax.numpy as jnp
    x = np.random.RandomState(0).randn(2, 3, 24, 16).astype("float32")
    got = rotary.rotary_embedding(jnp.asarray(x), dims, "default", 100.0,
                                  scale=scale)
    f = np.asarray([100.0 ** (-2 * i / dims) for i in range(dims // 2)])
    ang = np.arange(24)[:, None] * f[None, :]
    c, s = scale * np.cos(ang), scale * np.sin(ang)
    u1, u2 = x[..., :dims // 2], x[..., dims // 2:dims]
    want = np.concatenate([u1 * c - u2 * s, u2 * c + u1 * s, x[..., dims:]],
                          axis=-1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # <R x, y> = <x, R^T y>: the op's own gradient is the transpose
    y = np.random.RandomState(1).randn(*x.shape).astype("float32")
    g = jax.grad(lambda t: jnp.sum(rotary.rotary_embedding(
        t, dims, "default", 100.0, scale=scale) * y))(jnp.asarray(x))
    h1, h2 = y[..., :dims // 2], y[..., dims // 2:dims]
    want_g = np.concatenate([h1 * c + h2 * s, h2 * c - h1 * s, y[..., dims:]],
                            axis=-1)
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5, atol=2e-5)
    # bfloat16 in, bfloat16 out
    assert rotary.rotary_embedding(jnp.asarray(x, jnp.bfloat16), dims
                                   ).dtype == jnp.bfloat16


def _laguna(dtype=None, t=64):
    from benchmark import manifest
    cell = manifest.Cell("laguna-s-2.1-fit-s4096", rehearse=True)
    cfg = dict(cell.config, dtype=dtype)
    return cell.config_module("program").symbol(
        cfg, dict(cell.traffic, seq_len=t)), cfg


def test_the_laguna_members_parameters():
    sym, cfg = _laguna("bfloat16")
    assert sym.list_outputs() == ["softmax_output"] + [
        "l%d_experts_output1" % i for i in (1, 2, 3, 4)]
    args = sym.list_arguments()
    shapes = dict(zip(args, sym.infer_shape(data=(2, 64))[0]))
    types = dict(zip(args, sym.infer_type(data="float32")[0]))
    # 4 query heads in the full layers, 6 in the window layers, over 2
    # key/value heads of 16
    assert shapes["l0_q_weight"] == (64, 64) and shapes["l1_q_weight"] == (96, 64)
    assert shapes["l1_k_weight"] == shapes["l1_v_weight"] == (32, 64)
    assert shapes["l1_q_norm_gamma"] == shapes["l1_k_norm_gamma"] == (16,)
    assert shapes["l1_gate_weight"] == (6, 64) and shapes["l4_gate_weight"] == (4, 64)
    assert shapes["l1_proj_weight"] == (64, 96)
    # layer 0 dense, the others sparse: 4 of 16 experts held, a shared one
    assert shapes["l0_ff_gate_weight"] == (128, 64) and "l0_router_weight" not in shapes
    assert shapes["l2_router_weight"] == (16, 64)
    assert shapes["l2_experts_gate_weight"] == shapes["l2_experts_up_weight"] == (4, 32, 64)
    assert shapes["l2_experts_down_weight"] == (4, 64, 32)
    assert shapes["l2_shared_ff_down_weight"] == (64, 32) and "l2_ff_gate_weight" not in shapes
    # the router and the embedding stay float32, the rest follows bfloat16
    assert str(np.dtype(types["l2_router_weight"])) == "float32"
    assert str(np.dtype(types["tok_emb_weight"])) == "float32"
    assert str(np.dtype(types["l2_experts_up_weight"])) == "bfloat16"
    assert not [n for n in args if n.endswith("_bias") or n == "pos_emb"]
    # the window is on the sliding layers' attention alone, rotary on q and k
    nodes = {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}
    attr = lambda n: nodes[n].get("attrs", nodes[n].get("attr", {}))  # noqa: E731
    assert attr("l1_attn")["window"] == "8" and "window" not in attr("l0_attn")
    # (one node norms, turns and transposes a q or a k)
    assert attr("l0_q_norm")["rope_type"] == "yarn" and attr("l0_q_norm")["rotary_dims"] == "8"
    assert attr("l1_k_norm")["rope_type"] == "default" and attr("l1_k_norm")["rotary_dims"] == "16"
    assert attr("l1_q_norm")["num_heads"] == "6" and attr("l1_k_norm")["num_heads"] == "2"
    assert nodes["l1_q_norm"]["op"] == "_contrib_HeadNormRotary"
    assert nodes["l1_gated"]["op"] == "_contrib_HeadGate" and "l1_v_norm" not in nodes
    with pytest.raises(ValueError):
        decoder.grouped_attention_mix(mx.sym.Variable("x"), 8, 4, 2, 8, 32,
                                      "p", gate="per_dim")


def test_the_laguna_member_trains_through_module_fit_with_its_loads():
    """`Module.fit`'s fused step takes the group: the metric sees the
    softmax alone, the loads ride the metric sync and are counted."""
    from mxtpu import telemetry
    sym, cfg = _laguna("bfloat16")
    ids = np.random.RandomState(3).randint(0, 512, (8, 65))

    class Rows(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = 2
            self.provide_data = [mx.io.DataDesc("data", (2, 64))]
            self.provide_label = [mx.io.DataDesc("softmax_label", (128,))]
            self.at = 0

        def reset(self):
            self.at = 0

        def next(self):
            if self.at >= 8:
                raise StopIteration
            rows = ids[self.at:self.at + 2]
            self.at += 2
            return mx.io.DataBatch(
                [mx.nd.array(rows[:, :-1].astype("float32"))],
                [mx.nd.array(rows[:, 1:].reshape(-1).astype("float32"))],
                pad=0, provide_data=self.provide_data,
                provide_label=self.provide_label)

    def value(name):
        return sum(m.value for m in telemetry.registry().series()
                   if m.name == name)

    before = value("moe_pairs_routed"), value("moe_tokens_seen")
    mod = mx.mod.Module(sym, context=mx.cpu())
    metric = mx.metric.create("ce")
    mod.fit(Rows(), num_epoch=2, optimizer="sgd", eval_metric=metric,
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Normal(0.02))
    assert mod._fused is not None
    outs = mod.get_outputs()
    assert [o.shape for o in outs] == [(128, 512)] + [(4,)] * 4
    assert np.isfinite(metric.get()[1]) and metric.get()[1] < 7.0
    # two metric syncs (an epoch's end each), four expert layers, 128 tokens
    assert value("moe_tokens_seen") - before[1] == 2 * 4 * 128
    pairs = value("moe_pairs_routed") - before[0]
    assert 0 < pairs <= 2 * 4 * 128 * 3
    assert int(sum(o.asnumpy().sum() for o in outs[1:])) <= 4 * 128 * 3
    # score() goes through the executors: the metric is not handed the loads
    mod.score(Rows(), mx.metric.create("ce"))


def test_the_laguna_member_survives_its_json():
    sym, _ = _laguna()
    again = mx.sym.load_json(sym.tojson())
    assert again.list_arguments() == sym.list_arguments()
    assert again.list_outputs() == sym.list_outputs()
    assert again.tojson() == sym.tojson()


# -------------------------------------------------- the one-sublayer member
def _nemotron(dtype=None, t=64, pattern=None):
    from benchmark import manifest
    cell = manifest.Cell("nemotron-twotower-30b-fit-s4096", rehearse=True)
    cfg = dict(cell.config, dtype=dtype)
    if pattern:
        cfg.update(hybrid_override_pattern=pattern,
                   num_hidden_layers=len(pattern))
    return cell.config_module("program").symbol(
        cfg, dict(cell.traffic, seq_len=t)), cfg


def test_the_nemotron_h_members_parameters():
    """MEMEM*, the rehearsal's depth, at its sizes: 6 Mamba-2 heads of 16 in
    2 groups of state 16, 4 query heads over 2 key/value heads of 16, 4 of 16 relu2
    experts of 32 beside a shared one of 64."""
    sym, cfg = _nemotron("bfloat16")
    assert sym.list_outputs() == ["softmax_output"] + [
        "l%d_experts_output1" % i for i in (1, 3)]
    args = sym.list_arguments()
    shapes = dict(zip(args, sym.infer_shape(data=(2, 64))[0]))
    types = {k: str(np.dtype(v)) for k, v in
             zip(args, sym.infer_type(data="float32")[0])}
    per_layer = {i: sorted(n.split("_", 1)[1] for n in args
                           if n.startswith("l%d_" % i)) for i in range(6)}
    mamba = sorted(["mix_norm_gamma", "in_proj_weight", "conv_weight",
                    "conv_bias", "A_log", "dt_bias", "D", "o_norm_gamma",
                    "proj_weight"])
    experts = sorted(["ffn_norm_gamma", "router_weight", "experts_up_weight",
                      "experts_down_weight", "shared_ff_up_weight",
                      "shared_ff_down_weight"])
    attention = sorted(["mix_norm_gamma", "q_weight", "k_weight", "v_weight",
                        "proj_weight"])
    # one sublayer a layer: one norm, and nothing of the other kind
    for i, kind in enumerate("MEMEM*"):
        assert per_layer[i] == {"M": mamba, "E": experts,
                                "*": attention}[kind], i
    inner, conv = 6 * 16, 6 * 16 + 2 * 2 * 16
    assert shapes["l0_in_proj_weight"] == (2 * inner + 2 * 2 * 16 + 6, 64)
    assert shapes["l0_conv_weight"] == (conv, 4)
    assert shapes["l0_conv_bias"] == (conv,)
    assert shapes["l0_A_log"] == shapes["l0_dt_bias"] == shapes["l0_D"] == (6,)
    assert shapes["l0_o_norm_gamma"] == (inner,)
    assert shapes["l0_proj_weight"] == (64, inner)
    assert shapes["l5_q_weight"] == (64, 64)
    assert shapes["l5_k_weight"] == shapes["l5_v_weight"] == (32, 64)
    assert shapes["l1_router_weight"] == (16, 64)
    assert shapes["l1_experts_up_weight"] == (4, 32, 64)
    assert shapes["l1_experts_down_weight"] == (4, 64, 32)
    assert shapes["l1_shared_ff_up_weight"] == (64, 64)
    assert shapes["l1_shared_ff_down_weight"] == (64, 64)
    # float32 whatever dtype is: the embedding, the routers, the decay's leaves
    wide = {"tok_emb_weight"} | {"l%d_router_weight" % i for i in (1, 3)} \
        | {"l%d_%s" % (i, n) for i in (0, 2, 4)
           for n in ("A_log", "dt_bias", "D")}
    assert {n for n in args if types[n] == "float32"} - {
        "data", "softmax_label"} == wide
    assert types["l0_conv_bias"] == types["l0_in_proj_weight"] == "bfloat16"
    assert {n: types[n] for n in wide} == {
        n: cfg["param_dtypes"][n] for n in wide}
    nodes = {n["name"]: n for n in json.loads(sym.tojson())["nodes"]}
    attr = lambda n: nodes[n].get("attrs", nodes[n].get("attr", {}))  # noqa: E731
    assert nodes["l0_ssd"]["op"] == "_contrib_SSDScan"
    assert attr("l0_ssd")["chunk"] == "32"
    assert attr("l0_o_norm")["groups"] == "2"
    assert attr("l1_experts")["activation"] == "relu2"
    # plain attention: no q/k norm, no rotation, no gate
    assert not [n for n in nodes if n.startswith("l5_") and (
        "norm" in n and n != "l5_mix_norm" and not n.startswith("l5_mix_norm")
        or "gate" in n or "rot" in n)]
    assert nodes["l5_attn"]["op"] == "_contrib_FlashAttention"
    assert not [n for n, v in nodes.items()
                if v["op"] in ("_contrib_HeadNormRotary", "_contrib_HeadGate",
                               "_contrib_RotaryEmbedding")]


def test_a_layer_is_a_mixer_a_feed_forward_part_or_both():
    """`build`'s one-sublayer layers beside whole ones, the dense relu2 FFN,
    and what is refused."""
    sym, _ = _nemotron(pattern="M-*E")
    args = sym.list_arguments()
    assert [n for n in args if n.startswith("l1_")] == [
        "l1_ffn_norm_gamma", "l1_ff_up_weight", "l1_ff_down_weight"]
    shapes = dict(zip(args, sym.infer_shape(data=(2, 64))[0]))
    assert shapes["l1_ff_up_weight"] == (32, 64)
    assert shapes["l1_ff_down_weight"] == (64, 32)
    whole = decoder.build(512, 64, [decoder.FULL, None], 4, 64, 128,
                          norm="rms_pre", ffn=["relu2", "silu_gated"],
                          num_kv_heads=2, head_dim=16, qk_norm=False)
    names = whole.list_arguments()
    assert "l0_mix_norm_gamma" in names and "l0_ffn_norm_gamma" in names
    assert "l1_mix_norm_gamma" not in names and "l1_ff_gate_weight" in names
    with pytest.raises(AssertionError):
        decoder.build(512, 64, [decoder.NONE], 4, 64, 128, ffn=["none"])
    with pytest.raises(AssertionError):
        decoder.grouped_attention_mix(mx.sym.Variable("x"), 8, 4, 2, 8, 32,
                                      "p", rope={"rope_type": "default"},
                                      qk_norm=False)


def test_the_nemotron_h_member_trains_through_module_fit_with_its_loads():
    from mxtpu import telemetry
    sym, cfg = _nemotron("bfloat16")
    ids = np.random.RandomState(5).randint(0, 512, (2, 65))
    batch = mx.io.DataBatch(
        [mx.nd.array(ids[:, :-1].astype("float32"))],
        [mx.nd.array(ids[:, 1:].reshape(-1).astype("float32"))], pad=0,
        provide_data=[mx.io.DataDesc("data", (2, 64))],
        provide_label=[mx.io.DataDesc("softmax_label", (128,))])

    class Rows(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size, self.at = 2, 0
            self.provide_data = batch.provide_data
            self.provide_label = batch.provide_label

        def reset(self):
            self.at = 0

        def next(self):
            if self.at >= 4:
                raise StopIteration
            self.at += 1
            return batch

    def value(name):
        return sum(m.value for m in telemetry.registry().series()
                   if m.name == name)

    seen = value("moe_tokens_seen")
    mx.random.seed(5)
    mod = mx.mod.Module(sym, context=mx.cpu())
    metric = mx.metric.create("ce")
    # squared-ReLU experts under plain SGD run away at the rates the other
    # members' tests use (PERF.md, PR 34): a hundredth
    mod.fit(Rows(), num_epoch=1, optimizer="sgd", eval_metric=metric,
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
            initializer=mx.init.Normal(0.02))
    assert mod._fused is not None
    assert [o.shape for o in mod.get_outputs()] == [(128, 512)] + [(4,)] * 2
    assert np.isfinite(metric.get()[1]) and metric.get()[1] < 7.0
    # the relu2 layers feed the counters the gated ones feed
    assert value("moe_tokens_seen") - seen == 2 * 128
    # two chunks of 32 a row, 2 rows, 6 heads, a state of 16 x 16 float32
    assert value("ssd_state_saved_bytes") == 2 * 6 * 2 * 16 * 16 * 4
    again = mx.sym.load_json(sym.tojson())
    assert again.tojson() == sym.tojson()


def test_the_scans_kernels_carry_their_names_in_the_lowered_hlo():
    from mxtpu.ops import ssd
    x = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((1, 256, 4), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    h = jax.ShapeDtypeStruct((4,), jnp.float32)

    def loss(*a):
        return jnp.sum(ssd.ssd_scan(*a).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).trace(
        x, dt, h, bc, bc, h).lower(lowering_platforms=("tpu",)).as_text()
    assert "mxtpu_ssd_fwd" in text and "mxtpu_ssd_bwd" in text
    assert "stablehlo.while" not in text
    assert (ssd.FWD_KERNEL_NAME, ssd.BWD_KERNEL_NAME) == \
        ("mxtpu_ssd_fwd", "mxtpu_ssd_bwd")
