"""The decoder-only language-model family (symbol factory).

One builder for every causal LM of the zoo: a stack of residual blocks, each
a token mixer and a feed-forward sublayer (or one of the two alone), between
an embedding and an output head. What differs between members is a few
choices:

- ``layer_types``: one entry a layer, ``"full_attention"`` (causal softmax
  attention through ``_contrib_FlashAttention``), ``"sliding_attention"``
  (the same under a window of ``window`` keys that ends in the query's
  own), ``"linear_attention"`` (the gated delta rule through
  ``_contrib_GatedDeltaRule``, behind causal short convolutions, with a
  gated RMSNorm on its output), ``"mamba2"`` (the selective state-space
  scan through ``_contrib_SSDScan``, behind one in-projection and a causal
  short convolution, with a gated group RMSNorm on its output) or
  ``"none"``: the layer has no mixer, one norm and one residual add;
- ``norm``: ``"layer_pre"`` (LayerNorm before each sublayer, OPT's block),
  ``"rms_post"`` (RMSNorm on each sublayer's output before the residual
  add, and on q and k of full attention: the Olmo 2/3 block) or
  ``"rms_pre"`` (RMSNorm before each sublayer; q and k each through an
  RMSNorm over the dims of a head);
- ``ffn``: ``"relu"`` (two biased matrices), ``"silu_gated"`` (three,
  (silu(x W_gate) * x W_up) W_down, no bias), ``"relu2"`` (two,
  relu(x W_up)^2 W_down, no bias), ``"moe"`` (a router over
  ``num_experts``, the share of the routed experts held here through
  ``_contrib_MoEExperts``, and a shared expert beside them, both SiLU-gated
  or both ``relu2`` by the expert layers' ``activation``) or ``"none"``:
  the layer has no feed-forward part; one name, or one a layer;
- ``positions``: ``"learned"`` (a ``pos_emb`` table), ``"none"`` or
  ``"rotary"`` (q and k turned inside each attention layer as
  ``_contrib_RotaryEmbedding`` turns them, its parameters by the layer's
  type);
- ``num_heads`` may differ by layer, ``num_kv_heads`` key/value heads are
  shared by the query heads in groups, and ``gate="per_head"`` multiplies
  each head's output by a sigmoid of the block's normed input.

``mxtpu.models.transformer.get_symbol`` is the ``layer_pre`` / ``relu`` /
``learned`` member and keeps its graph and parameter names;
``get_symbol`` here builds the hybrid ``rms_post`` / ``silu_gated`` /
``none`` member (Olmo-Hybrid: three linear-attention layers, then one of
full attention); ``get_laguna_symbol`` the ``rms_pre`` / ``rotary`` member
with window and full attention at unequal head counts over shared key/value
heads, a per-head gate and expert layers (Laguna); ``get_nemotron_h_symbol``
the ``rms_pre`` member whose layers are one sublayer each, a ``mamba2``
mixer, plain attention over shared key/value heads (no q/k norm, rotation
or gate) or ``relu2`` expert layers, by a pattern of ``M``, ``*`` and ``E``
(Nemotron-H).

Parameter names of the hybrid member (shapes as FullyConnected keeps them,
(out, in); H heads, d = ``d_model``, f = ``d_ff``):

- ``tok_emb_weight`` (V, d), ``norm_f_gamma`` (d), ``lm_head_weight`` (V, d);
- every layer ``l<i>_``: ``mix_norm_gamma``, ``ffn_norm_gamma`` (d);
  ``ff_gate_weight``, ``ff_up_weight`` (f, d), ``ff_down_weight`` (d, f);
- a full-attention layer: ``q_weight``, ``k_weight``, ``v_weight``,
  ``proj_weight`` (d, d); ``q_norm_gamma``, ``k_norm_gamma`` (d);
- a linear-attention layer: ``q_weight``, ``k_weight`` (H d_k, d),
  ``v_weight``, ``g_weight`` (H d_v, d); ``q_conv_weight``,
  ``k_conv_weight`` (H d_k, K), ``v_conv_weight`` (H d_v, K);
  ``a_weight``, ``b_weight`` (H, d); ``A_log``, ``dt_bias`` (H,), float32
  whatever ``dtype`` is; ``o_norm_gamma`` (d_v); ``proj_weight`` (d, H d_v).

Parameter names of the ``rms_pre`` member (G key/value heads of dh, H query
heads in that layer, E experts held of width f_e):

- ``tok_emb_weight``, ``norm_f_gamma``, ``lm_head_weight`` as above; every
  layer ``mix_norm_gamma``, ``ffn_norm_gamma`` (d); ``q_weight`` (H dh, d),
  ``k_weight``, ``v_weight`` (G dh, d), ``q_norm_gamma``, ``k_norm_gamma``
  (dh), ``gate_weight`` (H, d), ``proj_weight`` (d, H dh);
- a dense layer ``ff_gate_weight``, ``ff_up_weight``, ``ff_down_weight``; an
  expert layer ``router_weight`` (num_experts, d), float32 whatever
  ``dtype`` is, ``experts_gate_weight``, ``experts_up_weight`` (E, f_e, d),
  ``experts_down_weight`` (E, d, f_e), and the shared expert's
  ``shared_ff_gate_weight``, ``shared_ff_up_weight``,
  ``shared_ff_down_weight``.

Parameter names of the one-sublayer member (a layer has ``mix_norm_gamma``
or ``ffn_norm_gamma`` (d), not both; H heads of P, G groups of state N):

- a ``mamba2`` layer: ``in_proj_weight`` (2 H P + 2 G N + H, d), its rows
  [z, x, B, C, dt]; ``conv_weight`` (H P + 2 G N, K), ``conv_bias``
  (H P + 2 G N); ``A_log``, ``dt_bias``, ``D`` (H,), float32 whatever
  ``dtype`` is; ``o_norm_gamma`` (H P); ``proj_weight`` (d, H P);
- an attention layer: ``q_weight``, ``k_weight``, ``v_weight``,
  ``proj_weight`` alone;
- an expert layer: ``router_weight`` (float32), ``experts_up_weight``
  (E, f_e, d), ``experts_down_weight`` (E, d, f_e), ``shared_ff_up_weight``
  (f_s, d), ``shared_ff_down_weight`` (d, f_s).

With expert layers the symbol is a group: the softmax first, then each
expert layer's loads ((E,) int32, the pairs each held expert received), which
take no gradient and which ``fit`` fetches with the metric.

Layout discipline as transformer.py's: tokens (B, T) -> (B, T, D); both
mixers in (B, H, T, dh); every matmul a FullyConnected(flatten=False).

Every node carries the model block it belongs to as a ``__block__``
attribute (``mx.AttrScope(block=...)``): ``embed``, ``attention``,
``delta_rule``, ``mamba2``, ``ffn`` (the dense FFNs and an expert layer's
shared expert), ``experts`` (router, top-k, dispatch, products, combine) and
``head`` (final norm, head projection, loss); a sublayer's norm and residual
add belong to the block they feed. No operator reads it: a profiler's device
table and the benchmark's per-block shares do (docs/observability.md,
"Device time by graph node").
"""
import functools

from .. import symbol as sym
from ..attribute import AttrScope

FULL, LINEAR = "full_attention", "linear_attention"
SLIDING, MAMBA2, NONE = "sliding_attention", "mamba2", "none"


def _block(block):
    """The builder's nodes (and the variables it makes) are of `block`."""
    def wrap(build):
        @functools.wraps(build)
        def scoped(*args, **kwargs):
            with AttrScope(block=block):
                return build(*args, **kwargs)
        return scoped
    return wrap


def _fc(x, num_hidden, name, no_bias=False, flatten=False):
    """FullyConnected along the last axis; `no_bias` is only written into
    the graph where it is set, so the biased members' JSON stays as it was."""
    kw = {"no_bias": True} if no_bias else {}
    if not flatten:
        kw["flatten"] = False
    return sym.FullyConnected(x, num_hidden=num_hidden, name=name, **kw)


def _heads(x, seq_len, num_heads, dh):
    """(B, T, H dh) -> (B, H, T, dh)."""
    x = sym.reshape(x, shape=(-1, seq_len, num_heads, dh))
    return sym.transpose(x, axes=(0, 2, 1, 3))


@_block("attention")
def attention_mix(x, seq_len, num_heads, d_model, prefix, no_bias=False,
                  qk_norm_eps=None):
    """Proj(Attn(x)): causal softmax attention over `num_heads` heads of
    d_model / num_heads. With `qk_norm_eps`, q and k pass an RMSNorm over
    all d_model channels first (Olmo 2/3 QK-norm)."""
    dh = d_model // num_heads

    def heads(tag):
        p = _fc(x, d_model, "%s_%s" % (prefix, tag), no_bias)
        if qk_norm_eps is not None and tag != "v":
            p = sym.RMSNorm(p, eps=qk_norm_eps,
                            name="%s_%s_norm" % (prefix, tag))
        return _heads(p, seq_len, num_heads, dh)

    q, k, v = heads("q"), heads("k"), heads("v")
    att = sym.contrib.FlashAttention(q, k, v, causal=True,
                                     name="%s_attn" % prefix)
    att = sym.transpose(att, axes=(0, 2, 1, 3))
    att = sym.reshape(att, shape=(-1, seq_len, d_model))
    return _fc(att, d_model, "%s_proj" % prefix, no_bias)


@_block("attention")
def grouped_attention_mix(x, seq_len, num_heads, num_kv_heads, head_dim,
                          d_model, prefix, window=0, rope=None, gate=None,
                          norm_eps=1e-6, qk_norm=True):
    """Proj(gate * Attn(x)): `num_heads` query heads of `head_dim` over
    `num_kv_heads` key/value heads (query head j reads key/value head
    j // (H / G)), causal, under `window` keys if it is not 0. q and k each
    pass an RMSNorm over a head's dims (one learned vector a layer), then
    `rope` (the attributes of ``_contrib_RotaryEmbedding``), if any: one
    ``_contrib_HeadNormRotary`` each. With `gate` ``"per_head"`` each
    head's output is multiplied by sigmoid(x W_g) before W_o
    (``_contrib_HeadGate``). Without `qk_norm` q and k are the plain
    projections (and take no rotation). No bias anywhere."""
    assert qk_norm or rope is None, "rotation rides in the q/k norm's pass"

    def heads(tag, n):
        p = _fc(x, n * head_dim, "%s_%s" % (prefix, tag), True)
        if tag == "v" or not qk_norm:
            return _heads(p, seq_len, n, head_dim)
        # norm, rotation and head transpose in one pass over the projection
        return sym.contrib.HeadNormRotary(
            p, num_heads=n, eps=norm_eps, name="%s_%s_norm" % (prefix, tag),
            **(rope or {"rope_type": "none"}))

    q, k, v = (heads("q", num_heads), heads("k", num_kv_heads),
               heads("v", num_kv_heads))
    kw = {"window": window} if window else {}
    att = sym.contrib.FlashAttention(q, k, v, causal=True,
                                     name="%s_attn" % prefix, **kw)
    if gate == "per_head":
        # the gate and the transpose back in one pass over the output
        att = sym.contrib.HeadGate(
            att, _fc(x, num_heads, "%s_gate" % prefix, True),
            name="%s_gated" % prefix)
    elif gate is not None:
        raise ValueError("unknown attention gate %r" % (gate,))
    else:
        att = sym.reshape(sym.transpose(att, axes=(0, 2, 1, 3)),
                          shape=(-1, seq_len, num_heads * head_dim))
    return _fc(att, d_model, "%s_proj" % prefix, True)


@_block("delta_rule")
def delta_rule_mix(x, seq_len, num_heads, d_model, prefix, key_dim, value_dim,
                   conv_kernel=4, neg_eigval=True, norm_eps=1e-6):
    """The Gated DeltaNet mixer (arXiv:2412.06464): q, k, v through a causal
    short convolution with SiLU (one ``_contrib_CausalConv1D`` each, the
    activation its attribute), q and k L2-normalised per head, the gated
    delta rule, a per-head RMSNorm gated by silu(x W_g) (one gated
    ``RMSNorm``), then W_o. Both operators bring their own gradients
    (ops/mixers.py): nothing float32 of a q, k, v or output's size lies
    between the projections and the kernel. The decay (A_log, dt_bias,
    softplus, exp) is float32 whatever x is."""
    h = num_heads

    def short(tag, dh):
        p = _fc(x, h * dh, "%s_%s" % (prefix, tag), True)
        p = sym.contrib.CausalConv1D(p, kernel=conv_kernel, act_type="silu",
                                     name="%s_%s_conv" % (prefix, tag))
        p = sym.reshape(p, shape=(-1, seq_len, h, dh))
        if tag != "v":
            p = sym.L2Normalization(p, mode="last", eps=1e-6)
            if tag == "q":
                p = p * (key_dim ** -0.5)
        return sym.transpose(p, axes=(0, 2, 1, 3))

    q, k, v = short("q", key_dim), short("k", key_dim), short("v", value_dim)

    def per_head(tag):
        return _fc(x, h, "%s_%s" % (prefix, tag), True)

    beta = sym.Activation(per_head("b"), act_type="sigmoid")
    if neg_eigval:
        beta = beta * 2.0
    beta = sym.transpose(beta, axes=(0, 2, 1))
    a_log = sym.Variable("%s_A_log" % prefix, shape=(h,), dtype="float32")
    dt_bias = sym.Variable("%s_dt_bias" % prefix, shape=(h,), dtype="float32")
    dt = sym.broadcast_add(sym.Cast(per_head("a"), dtype="float32"),
                           sym.reshape(dt_bias, shape=(1, 1, h)))
    g = sym.broadcast_mul(sym.Activation(dt, act_type="softrelu"),
                          sym.reshape(sym.negative(sym.exp(a_log)),
                                      shape=(1, 1, h)))
    g = sym.transpose(g, axes=(0, 2, 1))
    o = sym.contrib.GatedDeltaRule(q, k, v, g, beta, name="%s_delta" % prefix)
    o = sym.transpose(o, axes=(0, 2, 1, 3))
    gate = _fc(x, h * value_dim, "%s_g" % prefix, True)
    gate = sym.reshape(gate, shape=(-1, seq_len, h, value_dim))
    o = sym.RMSNorm(o, gate=gate, gated=True, eps=norm_eps,
                    name="%s_o_norm" % prefix)
    o = sym.reshape(o, shape=(-1, seq_len, h * value_dim))
    return _fc(o, d_model, "%s_proj" % prefix, True)


@_block("mamba2")
def mamba2_mix(x, seq_len, d_model, prefix, num_heads, head_dim, n_groups,
               state_size, conv_kernel=4, chunk=128, norm_eps=1e-5):
    """The Mamba-2 mixer (arXiv:2405.21060): [z, xBC, dt] = x W_in; xBC
    through a causal short convolution with bias and SiLU (one
    ``_contrib_CausalConv1D``, the activation its attribute), split into x
    (H heads of P), B and C (G groups of N); dt = softplus(dt + dt_bias);
    the state-space scan with A = -exp(A_log) and the skip D; y * silu(z)
    through an RMSNorm over each of the G groups of channels (one gated
    ``RMSNorm`` with ``gate_first``: z is its gate and multiplies before the
    mean square); W_out. Both operators bring their own gradients
    (ops/mixers.py). dt, A_log, dt_bias and D are float32 whatever x is."""
    h, p, g, n = num_heads, head_dim, n_groups, state_size
    inner, conv_dim = h * p, h * p + 2 * g * n

    def cut(v, begin, end):
        return sym.slice_axis(v, axis=2, begin=begin, end=end)

    zxbcdt = _fc(x, 2 * inner + 2 * g * n + h, "%s_in_proj" % prefix, True)
    z = cut(zxbcdt, 0, inner)
    xbc = sym.contrib.CausalConv1D(cut(zxbcdt, inner, inner + conv_dim),
                                   kernel=conv_kernel, bias=True,
                                   act_type="silu", name="%s_conv" % prefix)
    xs = sym.reshape(cut(xbc, 0, inner), shape=(-1, seq_len, h, p))
    bm = sym.reshape(cut(xbc, inner, inner + g * n),
                     shape=(-1, seq_len, g, n))
    cm = sym.reshape(cut(xbc, inner + g * n, conv_dim),
                     shape=(-1, seq_len, g, n))
    a_log, dt_bias, skip = (
        sym.Variable("%s_%s" % (prefix, name), shape=(h,), dtype="float32")
        for name in ("A_log", "dt_bias", "D"))
    dt = sym.broadcast_add(
        sym.Cast(cut(zxbcdt, inner + conv_dim, inner + conv_dim + h),
                 dtype="float32"),
        sym.reshape(dt_bias, shape=(1, 1, h)))
    dt = sym.Activation(dt, act_type="softrelu")
    y = sym.contrib.SSDScan(xs, dt, a_log, bm, cm, skip, chunk=chunk,
                            name="%s_ssd" % prefix)
    y = sym.reshape(y, shape=(-1, seq_len, inner))
    y = sym.RMSNorm(y, gate=z, gated=True, gate_first=True, eps=norm_eps,
                    groups=g, name="%s_o_norm" % prefix)
    return _fc(y, d_model, "%s_proj" % prefix, True)


@_block("ffn")
def relu_ffn(x, d_model, d_ff, prefix):
    f = _fc(x, d_ff, "%s_ff1" % prefix)
    f = sym.Activation(f, act_type="relu")
    return _fc(f, d_model, "%s_ff2" % prefix)


@_block("ffn")
def silu_gated_ffn(x, d_model, d_ff, prefix):
    gate = _fc(x, d_ff, "%s_ff_gate" % prefix, True)
    up = _fc(x, d_ff, "%s_ff_up" % prefix, True)
    f = sym.Activation(gate, act_type="silu") * up
    return _fc(f, d_model, "%s_ff_down" % prefix, True)


@_block("ffn")
def relu2_ffn(x, d_model, d_ff, prefix):
    up = _fc(x, d_ff, "%s_ff_up" % prefix, True)
    f = sym.square(sym.Activation(up, act_type="relu"))
    return _fc(f, d_model, "%s_ff_down" % prefix, True)


_DENSE_FFN = {"relu": relu_ffn, "relu2": relu2_ffn,
              "silu_gated": silu_gated_ffn}


@_block("experts")
def moe_ffn(x, d_model, prefix, num_experts, top_k, experts_held, hidden,
            shared_hidden=0, expert_offset=0, scale=1.0,
            score_func="sigmoid", norm_topk=True, activation="silu_gated"):
    """(y, loads): the routed experts held here, each an FFN of width
    `hidden` (SiLU-gated, or ``relu2``: relu(x W_up)^2 W_down), weighted by
    a float32 router over all `num_experts` (`top_k` a token), and beside
    them one shared expert of the same form and width `shared_hidden`,
    outside the router, if it is not 0. `loads` is the second output of
    ``_contrib_MoEExperts``."""
    # the gated form's graph is written as it was: no attribute it lacked
    kw = {} if activation == "silu_gated" else {"activation": activation}
    shared = _DENSE_FFN[activation]
    router = sym.Variable("%s_router_weight" % prefix, dtype="float32")
    chosen = sym.contrib.MoERouter(
        x, weight=router, num_experts=num_experts, top_k=top_k, scale=scale,
        score_func=score_func, norm_topk=norm_topk,
        name="%s_router" % prefix)
    routed = sym.contrib.MoEExperts(
        x, chosen[0], chosen[1], num_experts=num_experts,
        experts_held=experts_held, hidden=hidden,
        expert_offset=expert_offset, name="%s_experts" % prefix, **kw)
    y = routed[0]
    if shared_hidden:
        y = y + shared(x, d_model, shared_hidden, prefix + "_shared")
    return y, routed[1]


def _sublayer(h, fn, norm, name, eps, dropout, block):
    """h + fn(LN(h)) (`layer_pre`), h + fn(RMSNorm(h)) (`rms_pre`) or
    h + RMSNorm(fn(h)) (`rms_post`); the norm and the add are of `block`,
    the one `fn` builds."""
    with AttrScope(block=block):
        if norm == "layer_pre":
            y = fn(sym.LayerNorm(h, name=name))
        elif norm == "rms_pre":
            y = fn(sym.RMSNorm(h, eps=eps, name=name))
        else:
            y = sym.RMSNorm(fn(h), eps=eps, name=name)
        if dropout > 0:
            y = sym.Dropout(y, p=dropout)
        return h + y


_MIXER_BLOCK = {FULL: "attention", SLIDING: "attention",
                LINEAR: "delta_rule", MAMBA2: "mamba2"}


def build(vocab_size, seq_len, layer_types, num_heads, d_model, d_ff,
          norm="rms_post", ffn="silu_gated", positions="none", dropout=0.0,
          max_len=None, dtype=None, norm_eps=1e-6, linear=None,
          num_kv_heads=None, head_dim=None, window=0, gate=None, rope=None,
          moe=None, mamba=None, qk_norm=True):
    """Causal LM of the family: data (B, T) int tokens -> SoftmaxOutput over
    (B*T, vocab). `linear` holds the linear-attention layers' sizes
    (``key_dim``, ``value_dim``, ``conv_kernel``, ``neg_eigval``, and
    ``num_heads`` if it differs). `num_heads` and `ffn` may be one a layer.
    The ``rms_pre`` member's attention takes `num_kv_heads`, `head_dim`,
    `window` (its ``sliding_attention`` layers'), `gate` and, under
    ``positions="rotary"``, `rope`: {layer type: attributes of
    ``_contrib_RotaryEmbedding``}. `moe` holds the expert layers' sizes
    (`moe_ffn`'s arguments), `mamba` the ``mamba2`` layers' (`mamba2_mix`'s).
    A layer whose type or whose ffn is ``"none"`` (or None) has the other
    sublayer alone."""
    layer_types = [k or NONE for k in layer_types]
    heads_of = list(num_heads) if isinstance(num_heads, (list, tuple)) \
        else [num_heads] * len(layer_types)
    ffn_of = [f or NONE for f in ffn] if isinstance(ffn, (list, tuple)) \
        else [ffn] * len(layer_types)
    assert len(heads_of) == len(ffn_of) == len(layer_types), \
        "one head count and one ffn kind a layer"
    assert norm in ("layer_pre", "rms_post", "rms_pre")
    assert all(f in ("relu", "silu_gated", "relu2", "moe", NONE)
               for f in ffn_of)
    assert all(k != NONE or f != NONE for k, f in zip(layer_types, ffn_of)), \
        "a layer is a mixer, a feed-forward part or both"
    assert positions in ("learned", "none", "rotary")
    grouped = norm == "rms_pre"
    if not grouped:
        num_heads = heads_of[0]
        assert d_model % num_heads == 0, "d_model must divide into heads"
    pre = norm == "layer_pre"
    data = sym.Variable("data")
    with AttrScope(block="embed"):
        h = sym.Embedding(data, input_dim=vocab_size, output_dim=d_model,
                          name="tok_emb")
        if dtype is not None:
            h = sym.Cast(h, dtype=dtype)
        if positions == "learned":
            max_len = max_len or seq_len
            assert max_len >= seq_len, "max_len must cover seq_len"
            pos = sym.Variable("pos_emb", shape=(1, max_len, d_model))
            if dtype is not None:
                pos = sym.Cast(pos, dtype=dtype)
            if max_len != seq_len:
                pos = sym.slice_axis(pos, axis=1, begin=0, end=seq_len)
            h = sym.broadcast_add(h, pos)
    lin = dict(linear or {})
    lin_heads = lin.pop("num_heads", num_heads)
    loads = []
    for i, kind in enumerate(layer_types):
        p = "l%d" % i
        if grouped and kind in (FULL, SLIDING):
            def mix(x, p=p, kind=kind, heads=heads_of[i]):
                return grouped_attention_mix(
                    x, seq_len, heads, num_kv_heads or heads,
                    head_dim or d_model // heads, d_model, p,
                    window=window if kind == SLIDING else 0,
                    rope=(rope or {}).get(kind)
                    if positions == "rotary" else None,
                    gate=gate, norm_eps=norm_eps, qk_norm=qk_norm)
        elif kind == FULL:
            def mix(x, p=p):
                return attention_mix(
                    x, seq_len, num_heads, d_model, p, no_bias=not pre,
                    qk_norm_eps=None if pre else norm_eps)
        elif kind == LINEAR:
            def mix(x, p=p):
                return delta_rule_mix(x, seq_len, lin_heads, d_model, p,
                                      norm_eps=norm_eps, **lin)
        elif kind == MAMBA2:
            def mix(x, p=p):
                return mamba2_mix(x, seq_len, d_model, p, norm_eps=norm_eps,
                                  **mamba)
        elif kind != NONE:
            raise ValueError("layer %d: unknown layer type %r" % (i, kind))
        if kind != NONE:
            h = _sublayer(h, mix, norm, p + ("_ln1" if pre else "_mix_norm"),
                          norm_eps, dropout, _MIXER_BLOCK[kind])
        if ffn_of[i] == NONE:
            continue
        if ffn_of[i] == "moe":
            def feed(x, p=p):
                y, load = moe_ffn(x, d_model, p, **moe)
                loads.append(load)
                return y
        else:
            def feed(x, p=p, ffn=_DENSE_FFN[ffn_of[i]]):
                return ffn(x, d_model, d_ff, p)
        h = _sublayer(h, feed, norm, p + ("_ln2" if pre else "_ffn_norm"),
                      norm_eps, dropout,
                      "experts" if ffn_of[i] == "moe" else "ffn")
    with AttrScope(block="head"):
        if pre:
            h = sym.LayerNorm(h, name="ln_f")
        else:
            h = sym.RMSNorm(h, eps=norm_eps, name="norm_f")
        h = sym.reshape(h, shape=(-1, d_model))
        logits = _fc(h, vocab_size, "lm_head", no_bias=not pre, flatten=True)
        if dtype is not None:
            logits = sym.Cast(logits, dtype="float32")
        out = sym.SoftmaxOutput(logits, name="softmax")
    return sym.Group([out] + loads) if loads else out


def get_symbol(vocab_size, seq_len, layer_types, num_heads, d_model, d_ff,
               linear_key_dim, linear_value_dim, linear_num_heads=None,
               linear_conv_kernel=4, linear_allow_neg_eigval=True,
               norm_eps=1e-6, dtype=None):
    """The hybrid member: RMSNorm after each sublayer, a SiLU-gated FFN, no
    biases, no position table (position comes from the recurrent layers),
    an untied head. Train with label = data shifted left by one, flattened
    to (B*T,). `seq_len` must be a multiple of the delta rule's chunk (64)
    when `layer_types` has a linear-attention layer."""
    return build(vocab_size, seq_len, list(layer_types), num_heads, d_model,
                 d_ff, norm="rms_post", ffn="silu_gated", positions="none",
                 dtype=dtype, norm_eps=norm_eps,
                 linear={"num_heads": linear_num_heads or num_heads,
                         "key_dim": linear_key_dim,
                         "value_dim": linear_value_dim,
                         "conv_kernel": linear_conv_kernel,
                         "neg_eigval": linear_allow_neg_eigval})


def get_laguna_symbol(vocab_size, seq_len, layer_types, num_heads,
                      num_kv_heads, head_dim, d_model, d_ff, mlp_layer_types,
                      window, rope, moe, gate="per_head", norm_eps=1e-6,
                      dtype=None):
    """The window/full-attention expert member (Laguna): RMSNorm before each
    sublayer and after the last block, `num_heads[i]` query heads in layer i
    over `num_kv_heads` key/value heads of `head_dim`, a window of `window`
    keys in the ``sliding_attention`` layers, q and k normed per head and
    rotated by `rope[layer type]`, a per-head sigmoid gate, a SiLU-gated FFN
    of `d_ff` in the ``dense`` layers of `mlp_layer_types` and `moe` (the
    arguments of `moe_ffn`) in the ``sparse`` ones, no biases, an untied
    head. Train with label = data shifted left by one, flattened to
    (B*T,)."""
    kinds = {"dense": "silu_gated", "sparse": "moe"}
    return build(vocab_size, seq_len, list(layer_types), list(num_heads),
                 d_model, d_ff, norm="rms_pre",
                 ffn=[kinds[m] for m in mlp_layer_types], positions="rotary",
                 dtype=dtype, norm_eps=norm_eps, num_kv_heads=num_kv_heads,
                 head_dim=head_dim, window=window, gate=gate, rope=rope,
                 moe=moe)


def get_nemotron_h_symbol(vocab_size, seq_len, pattern, d_model, num_heads,
                          num_kv_heads, head_dim, mamba, moe, d_ff=0,
                          norm_eps=1e-5, dtype=None):
    """The one-sublayer member (Nemotron-H): layer i is `pattern[i]`, ``M`` a
    ``mamba2`` mixer (`mamba`: the arguments of `mamba2_mix`), ``*`` causal
    attention of `num_heads` query heads over `num_kv_heads` key/value heads
    of `head_dim` with no q/k norm, rotation, gate or bias, ``E`` an expert
    layer (`moe`: the arguments of `moe_ffn`, ``activation="relu2"``) and
    ``-`` a dense ``relu2`` FFN of `d_ff`; each x + f(RMSNorm(x)), a final
    RMSNorm, no position table, an untied head. Train with label = data
    shifted left by one, flattened to (B*T,). `seq_len` must be a multiple
    of the scan's chunk where the pattern has an ``M``."""
    mixer = {"M": MAMBA2, "*": FULL, "E": NONE, "-": NONE}
    feed = {"M": NONE, "*": NONE, "E": "moe", "-": "relu2"}
    return build(vocab_size, seq_len, [mixer[c] for c in pattern], num_heads,
                 d_model, d_ff, norm="rms_pre", ffn=[feed[c] for c in pattern],
                 positions="none", dtype=dtype, norm_eps=norm_eps,
                 num_kv_heads=num_kv_heads, head_dim=head_dim, moe=moe,
                 mamba=mamba, qk_norm=False)
