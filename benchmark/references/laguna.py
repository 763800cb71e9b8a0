"""Plain reference of the Laguna decoder
(huggingface.co/poolside/Laguna-S-2.1 `config.json`): layers of two kinds by
`layer_types`, causal attention over all keys (`full_attention`) or over the
last `sliding_window` (`sliding_attention`), `num_attention_heads_per_layer`
query heads over `num_key_value_heads` key/value heads (query head j reads
key/value head j // (H / G)), q and k normed per head and rotated (two sets
of `rope_parameters`: plain in the window layers, YaRN over half of a head's
dims in the full ones), each head's output gated by a sigmoid of the block's
normed input; a SiLU-gated FFN in the `dense` layers of `mlp_layer_types`
and, in the `sparse` ones, a float32 sigmoid router over all
`router_num_experts` experts, `num_experts_per_tok` a token, normalised and
scaled, beside one shared expert. Each block h = x + Mix(RMSNorm(x)),
y = h + FFN(RMSNorm(h)); a final RMSNorm and an untied head.

Straightforward jax.numpy in float32 with matmul precision `highest`:
attention is a softmax over an explicit mask, a key/value group at a time;
the rotation is the formula; the router is `jax.lax.top_k` on the scores;
the experts are a loop over those held here, each over ALL tokens times a
weight that is 0 where the expert was not chosen. No kernel, no sort, no
grouped product, no cache. Imports nothing of mxtpu.

This chip's share: the file's `num_experts` experts are held here, numbers
`expert_offset` .. + `num_experts` - 1 of the router's `router_num_experts`.
The router scores all of them, the `num_experts_per_tok` are chosen among
all, the weights are normalised over all chosen, and what the experts held
elsewhere would have added is left out.

What the published config does not fix, as `assumed` in the configuration's
file: pre-norm blocks; q and k through an RMSNorm over a head's dims with
one learned vector a layer, before rotation; the gate a sigmoid of the
normed input, a head a number, before W_o; the router's sigmoid scores,
normalised over the chosen and scaled, no selection bias; the shared expert
ungated. Parameter names follow mxtpu.models.decoder's symbol.

Planted faults (`fault=`): `no_window` leaves the window out of the
`sliding_attention` layers; `held_norm` normalises the routed weights over
the chosen experts held here in place of all chosen.

Memory: `block_loss` walks the rows of its block itself, one row at a time
under `jax.checkpoint`, each layer checkpointed again and attention a
key/value group at a time, so one row's float32 activations are all that is
live beside weights, gradient and optimizer state.
"""
import functools
import math

import jax
import jax.numpy as jnp

from . import common

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_types(cfg):
    """The kinds of the layers that are held: the first
    `num_hidden_layers` of the published pattern."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _heads(cfg, i):
    return cfg["num_attention_heads_per_layer"][i]


def param_specs(cfg):
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    g = cfg["num_key_value_heads"]
    std = "normal:%g" % cfg["init_std"]
    specs = [("tok_emb_weight", (v, d), std), ("norm_f_gamma", (d,), "ones"),
             ("lm_head_weight", (v, d), std)]

    def ffn(p, width):
        return [(p + "ff_gate_weight", (width, d), std),
                (p + "ff_up_weight", (width, d), std),
                (p + "ff_down_weight", (d, width), std)]

    for i in range(cfg["num_hidden_layers"]):
        p, h = "l%d_" % i, _heads(cfg, i)
        specs += [(p + "mix_norm_gamma", (d,), "ones"),
                  (p + "ffn_norm_gamma", (d,), "ones"),
                  (p + "q_weight", (h * dh, d), std),
                  (p + "k_weight", (g * dh, d), std),
                  (p + "v_weight", (g * dh, d), std),
                  (p + "q_norm_gamma", (dh,), "ones"),
                  (p + "k_norm_gamma", (dh,), "ones"),
                  (p + "gate_weight", (h, d), std),
                  (p + "proj_weight", (d, h * dh), std)]
        if cfg["mlp_layer_types"][i] == "dense":
            specs += ffn(p, cfg["intermediate_size"])
        else:
            e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
            specs += [(p + "router_weight", (cfg["router_num_experts"], d), std),
                      (p + "experts_gate_weight", (e, f, d), std),
                      (p + "experts_up_weight", (e, f, d), std),
                      (p + "experts_down_weight", (e, d, f), std)]
            specs += ffn(p + "shared_", cfg["shared_expert_intermediate_size"])
    return specs


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + eps)


def _lin(x, w, q):
    return jnp.einsum("td,ed->te", q(x), q(w), precision=common.HIGHEST)


def _ffn(h, gate, up, down, q):
    return _lin(jax.nn.silu(_lin(h, gate, q)) * _lin(h, up, q), down, q)


# ------------------------------------------------------------------ rotary
def inv_freq(rope, head_dim):
    """(r, the r/2 inverse frequencies, the factor m on cos and sin) of one
    set of `rope_parameters`."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = rope["rope_theta"]
    e = [theta ** (-2.0 * i / r) for i in range(r // 2)]
    if rope["rope_type"] == "default":
        return r, jnp.asarray(e, jnp.float32), 1.0

    def c(beta):    # YaRN, arXiv:2309.00071: the dim that turns beta times
        return (r * math.log(rope["original_max_position_embeddings"]
                             / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    lo = max(math.floor(c(rope["beta_fast"])), 0)
    hi = min(math.ceil(c(rope["beta_slow"])), r - 1)
    f = []
    for i, e_i in enumerate(e):
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        f.append(e_i / rope["factor"] * ramp + e_i * (1.0 - ramp))
    return r, jnp.asarray(f, jnp.float32), rope["attention_factor"]


def rotate(x, rope, head_dim):
    """x (T, H, head_dim): the first r dims of each head turned by the
    position's angle, halves paired (i, i + r/2); the rest pass."""
    r, f, m = inv_freq(rope, head_dim)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None, :]
    c, s = (m * jnp.cos(angle))[:, None, :], (m * jnp.sin(angle))[:, None, :]
    u1, u2 = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([u1 * c - u2 * s, u2 * c + u1 * s, x[..., r:]],
                           axis=-1)


# --------------------------------------------------------------- attention
def _attend(qkv, window, q):
    """The query heads of one key/value group: qh (rep, T, dh) over kh, vh
    (T, dh), a softmax over an explicit mask."""
    qh, kh, vh = qkv
    t, dh = kh.shape
    s = jnp.einsum("htd,sd->hts", q(qh), q(kh),
                   precision=common.HIGHEST) / (dh ** 0.5)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = cols <= rows
    if window:
        seen = seen & (cols > rows - window)
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("hts,sd->htd", q(jax.nn.softmax(s, axis=-1)), q(vh),
                      precision=common.HIGHEST)


def _mix(x, lp, cfg, kind, heads, q, fault, remat):
    t = x.shape[0]
    dh, g, eps = cfg["head_dim"], cfg["num_key_value_heads"], cfg["rms_norm_eps"]
    rope = cfg["rope_parameters"][kind]
    qq = _rms(_lin(x, lp["q_weight"], q).reshape(t, heads, dh),
              lp["q_norm_gamma"], eps)
    kk = _rms(_lin(x, lp["k_weight"], q).reshape(t, g, dh),
              lp["k_norm_gamma"], eps)
    vv = _lin(x, lp["v_weight"], q).reshape(t, g, dh)
    qq, kk = rotate(qq, rope, dh), rotate(kk, rope, dh)
    window = cfg["sliding_window"] if kind == SLIDING else 0
    if fault == "no_window":
        window = 0
    attend = functools.partial(_attend, window=window, q=q)
    groups = (qq.reshape(t, g, heads // g, dh).transpose(1, 2, 0, 3),
              kk.transpose(1, 0, 2), vv.transpose(1, 0, 2))
    a = jax.lax.map(jax.checkpoint(attend) if remat else attend, groups)
    a = a.transpose(2, 0, 1, 3).reshape(t, heads, dh)
    gate = jax.nn.sigmoid(_lin(x, lp["gate_weight"], q))
    return _lin((a * gate[:, :, None]).reshape(t, heads * dh),
                lp["proj_weight"], q)


# ----------------------------------------------------------------- experts
def route(x, w_r, cfg, fault=None):
    """(weights (T, k), indices (T, k)): float32 sigmoid scores of all the
    router's experts, the k largest (ties to the lower index), normalised
    over the chosen and scaled."""
    rho = jnp.einsum("td,ed->te", x, w_r, precision=common.HIGHEST)
    score, index = jax.lax.top_k(jax.nn.sigmoid(rho),
                                 cfg["num_experts_per_tok"])
    over = score
    if fault == "held_norm":
        lo = cfg["expert_offset"]
        over = jnp.where((index >= lo) & (index < lo + cfg["num_experts"]),
                         score, 0.0)
    total = jnp.sum(over, axis=-1, keepdims=True)
    if fault == "held_norm":
        total = jnp.where(total > 0, total, 1.0)
    return cfg["moe_routed_scaling_factor"] * score / total, index


def _moe(x, lp, cfg, q, fault):
    w, index = route(x, lp["router_weight"], cfg, fault)
    out = _ffn(x, lp["shared_ff_gate_weight"], lp["shared_ff_up_weight"],
               lp["shared_ff_down_weight"], q)

    @jax.checkpoint
    def expert(acc, held):      # one expert held here, over all tokens
        e, gate, up, down = held
        mine = jnp.sum(jnp.where(index == cfg["expert_offset"] + e, w, 0.0),
                       axis=-1)
        return acc + mine[:, None] * _ffn(x, gate, up, down, q), None

    return jax.lax.scan(
        expert, out, (jnp.arange(cfg["num_experts"]),
                      lp["experts_gate_weight"], lp["experts_up_weight"],
                      lp["experts_down_weight"]))[0]


def _layer(h, lp, i, cfg, q, fault, remat):
    eps = cfg["rms_norm_eps"]
    h = h + _mix(_rms(h, lp["mix_norm_gamma"], eps), lp, cfg,
                 layer_types(cfg)[i], _heads(cfg, i), q, fault, remat)
    x = _rms(h, lp["ffn_norm_gamma"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return h + _ffn(x, lp["ff_gate_weight"], lp["ff_up_weight"],
                        lp["ff_down_weight"], q)
    return h + _moe(x, lp, cfg, q, fault)


def row_logits(params, tokens, cfg, quant=None, remat=True, fault=None):
    """Logits (T, V) of one row of ids (T,)."""
    q = common.rounder(quant)
    h = params["tok_emb_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        layer = functools.partial(_layer, i=i, cfg=cfg, q=q, fault=fault,
                                  remat=remat)
        h = (jax.checkpoint(layer) if remat else layer)(h, lp)
    h = _rms(h, params["norm_f_gamma"], cfg["rms_norm_eps"])
    return _lin(h, params["lm_head_weight"], q)


def route_choices(params, tokens, cfg):
    """The experts each token of `tokens` (B, T) is sent to in every sparse
    layer, [(B, T, k) int32], as the sound reference routes them: what
    benchmark/tools/route_readings.py holds the program's choices against."""
    def same(a):
        return a

    def row(ids):
        h, picks, eps = params["tok_emb_weight"][ids], [], cfg["rms_norm_eps"]
        for i in range(cfg["num_hidden_layers"]):
            p = "l%d_" % i
            lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
            h = h + _mix(_rms(h, lp["mix_norm_gamma"], eps), lp, cfg,
                         layer_types(cfg)[i], _heads(cfg, i), same, None, True)
            x = _rms(h, lp["ffn_norm_gamma"], eps)
            if cfg["mlp_layer_types"][i] == "dense":
                h = h + _ffn(x, lp["ff_gate_weight"], lp["ff_up_weight"],
                             lp["ff_down_weight"], same)
            else:
                picks.append(route(x, lp["router_weight"], cfg)[1])
                h = h + _moe(x, lp, cfg, same, None)
        return picks
    return jax.lax.map(row, tokens)


def forward(params, tokens, cfg, quant=None, remat=True, fault=None):
    """Logits (B, T, V) of the whole sequences `tokens` (B, T) of ids."""
    return jax.lax.map(
        lambda row: row_logits(params, row, cfg, quant, remat, fault), tokens)


def block_loss(cfg, quant=None, fault=None):
    """(params, tokens, labels) -> (summed cross-entropy, metric's sum) of a
    block of whole rows, walked one row at a time; what `common.follow`
    differentiates."""
    @jax.checkpoint
    def row(p, tokens, labels):
        return common.ce_sum(row_logits(p, tokens, cfg, quant, fault=fault),
                             labels)

    def f(p, tokens, labels):
        def step(acc, xs):
            ce, metric = row(p, *xs)
            return (acc[0] + ce, acc[1] + metric), None
        zero = jnp.zeros((), jnp.float32)
        return jax.lax.scan(step, (zero, zero), (tokens, labels))[0]
    return f


def split_rows(tokens, labels):
    """The batch as the program gets it (labels flattened) -> row-major
    arrays whose first axis is the row."""
    return tokens.astype(jnp.int32), labels.reshape(tokens.shape).astype(jnp.int32)
