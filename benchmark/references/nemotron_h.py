"""Plain reference of the Nemotron-H decoder
(huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16
`config.json`, `model_type` `nemotron_h`): layers of ONE sublayer each,
h = x + f(RMSNorm(x)), f by the layer's character in
`hybrid_override_pattern`:

- `M`, Mamba-2 (arXiv:2405.21060): [z, xBC, dt] = x~ W_in; xBC through a
  causal depthwise convolution of `conv_kernel` taps with bias, then SiLU,
  split into x (`mamba_num_heads` heads of `mamba_head_dim`), B and C
  (`n_groups` groups of `ssm_state_size`, a group shared by H / G heads);
  dt = softplus(dt + dt_bias), A = -exp(A_log); per head
  S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T, y_t = S_t^T C_t + D x_t;
  y * silu(z) through an RMSNorm over each group's channels; W_out.
- `*`: causal attention of `num_attention_heads` query heads over
  `num_key_value_heads` key/value heads of `head_dim` (query head j reads
  key/value head j // (H / G)); no bias, no position, no q/k norm, no gate.
- `E`: a float32 sigmoid router over all `router_num_experts` experts,
  `num_experts_per_tok` a token, normalised over the chosen and scaled by
  `routed_scaling_factor`; each expert ungated, relu(x W_up)^2 W_down; one
  shared expert of the same form added.

A final RMSNorm and an untied head.

Straightforward jax.numpy in float32 with matmul precision `highest`. **The
scan is the recurrence itself, a `lax.scan` over tokens**, all heads of a
row at once (no chunk, no cumulative sum, no quadratic form); the
convolution is K shifted products; attention is a softmax over an explicit
mask, a key/value group at a time; the router is `jax.lax.top_k` on the
scores; the experts are a loop over those held here, each over ALL tokens
times a weight that is 0 where the expert was not chosen. No kernel, no
sort, no grouped product, no cache. Imports nothing of mxtpu.

This chip's share: the file's `n_routed_experts` experts are held here,
numbers `expert_offset` .. + `n_routed_experts` - 1 of the router's
`router_num_experts`. The router scores all of them, the
`num_experts_per_tok` are chosen among all, the weights are normalised over
all chosen, and what the experts held elsewhere would have added is left
out.

What the published config does not fix is listed as `assumed` in the
configuration's file. Parameter names follow mxtpu.models.decoder's symbol.

Planted faults (`fault=`): `no_decay` replaces exp(dt A) by 1 (the state
never forgets); `held_norm` normalises the routed weights over the chosen
experts held here in place of all chosen.

Memory: `block_loss` walks the rows of its block itself, one row at a time
under `jax.checkpoint`, each layer checkpointed again, the scan's steps in
checkpointed stretches of 128 tokens, attention a key/value group at a time
in stretches of 4 query heads, so one row's float32 activations are all
that is live beside weights, gradient and optimizer state.
"""
import functools

import jax
import jax.numpy as jnp

from . import common

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
STRETCH = 128       # tokens of the scan rematerialised together


def pattern(cfg):
    """The kinds of the layers that are held: the first
    `num_hidden_layers` characters of the published pattern."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _mamba_dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def param_specs(cfg):
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    std = "normal:%g" % cfg["init_std"]
    h, _, g, n, inner, conv_dim = _mamba_dims(cfg)
    specs = [("tok_emb_weight", (v, d), std), ("norm_f_gamma", (d,), "ones"),
             ("lm_head_weight", (v, d), std)]
    for i, kind in enumerate(pattern(cfg)):
        q = "l%d_" % i
        if kind == MAMBA:
            specs += [(q + "mix_norm_gamma", (d,), "ones"),
                      (q + "in_proj_weight", (2 * inner + 2 * g * n + h, d),
                       std),
                      (q + "conv_weight", (conv_dim, cfg["conv_kernel"]),
                       cfg["init"]["conv_weight"]),
                      (q + "conv_bias", (conv_dim,), cfg["init"]["conv_bias"]),
                      (q + "A_log", (h,), cfg["init"]["A_log"]),
                      (q + "dt_bias", (h,), cfg["init"]["dt_bias"]),
                      (q + "D", (h,), cfg["init"]["D"]),
                      (q + "o_norm_gamma", (inner,), "ones"),
                      (q + "proj_weight", (d, inner), std)]
        elif kind == ATTENTION:
            hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
            specs += [(q + "mix_norm_gamma", (d,), "ones"),
                      (q + "q_weight", (hq * dh, d), std),
                      (q + "k_weight", (hk * dh, d), std),
                      (q + "v_weight", (hk * dh, d), std),
                      (q + "proj_weight", (d, hq * dh), std)]
        elif kind == EXPERTS:
            e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
            fs = cfg["moe_shared_expert_intermediate_size"]
            specs += [(q + "ffn_norm_gamma", (d,), "ones"),
                      (q + "router_weight", (cfg["router_num_experts"], d),
                       std),
                      (q + "experts_up_weight", (e, f, d), std),
                      (q + "experts_down_weight", (e, d, f), std),
                      (q + "shared_ff_up_weight", (fs, d), std),
                      (q + "shared_ff_down_weight", (d, fs), std)]
        else:
            raise ValueError("layer %d: unknown kind %r" % (i, kind))
    return specs


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + eps)


def _lin(x, w, q):
    return jnp.einsum("td,ed->te", q(x), q(w), precision=common.HIGHEST)


def _ffn(h, up, down, q):
    return _lin(jnp.square(jax.nn.relu(_lin(h, up, q))), down, q)


# ------------------------------------------------------------------ mamba-2
def _recurrence(x, dt, a, bm, cm, q):
    """y (T, H, P) of x (T, H, P), dt (T, H), a (H,), bm and cm (T, H, N)
    (each head's own copy of its group's): one token a step, the steps in
    checkpointed stretches so that the backward holds one stretch's states
    and each stretch's first."""
    t, h, p = x.shape
    n = bm.shape[-1]

    def step(s, z):
        xt, dtt, decay, bt, ct = z
        write = jnp.einsum("hn,hp->hnp", q(bt), q(dtt[:, None] * xt),
                           precision=common.HIGHEST)
        s = decay[:, None, None] * s + write
        return s, jnp.einsum("hnp,hn->hp", q(s), q(ct),
                             precision=common.HIGHEST)

    @jax.checkpoint
    def stretch(s, zs):
        return jax.lax.scan(step, s, zs)

    size = STRETCH if t % STRETCH == 0 else t
    decay = jnp.exp(dt * a) if a is not None else jnp.ones_like(dt)
    zs = jax.tree.map(lambda v: v.reshape((t // size, size) + v.shape[1:]),
                      (x, dt, decay, bm, cm))
    _, y = jax.lax.scan(stretch, jnp.zeros((h, n, p), jnp.float32), zs)
    return y.reshape(t, h, p)


def _mamba(x, lp, cfg, q, fault):
    t = x.shape[0]
    h, p, g, n, inner, conv_dim = _mamba_dims(cfg)
    k = cfg["conv_kernel"]
    zxbcdt = _lin(x, lp["in_proj_weight"], q)
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim]
    dt = jax.nn.softplus(zxbcdt[:, inner + conv_dim:] + lp["dt_bias"])
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    taps = q(lp["conv_weight"])
    conv = lp["conv_bias"] + sum(q(padded[i:i + t]) * taps[:, i]
                                 for i in range(k))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(t, h, p)
    bm = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), h // g, 1)
    cm = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), h // g, 1)
    a = None if fault == "no_decay" else -jnp.exp(lp["A_log"])
    y = _recurrence(xs, dt, a, bm, cm, q) + lp["D"][:, None] * xs
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return _lin(lp["o_norm_gamma"] * y.reshape(t, inner), lp["proj_weight"],
                q)


# --------------------------------------------------------------- attention
def _attend(qkv, q):
    """A stretch of query heads of one key/value group: qh (rep, T, dh)
    over kh, vh (T, dh), a softmax over an explicit mask."""
    qh, kh, vh = qkv
    t, dh = kh.shape
    s = jnp.einsum("htd,sd->hts", q(qh), q(kh),
                   precision=common.HIGHEST) / (dh ** 0.5)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("hts,sd->htd", q(jax.nn.softmax(s, axis=-1)), q(vh),
                      precision=common.HIGHEST)


def _attention(x, lp, cfg, q, remat):
    t = x.shape[0]
    dh, g = cfg["head_dim"], cfg["num_key_value_heads"]
    heads = cfg["num_attention_heads"]
    rep = heads // g
    part = next(c for c in (4, 2, 1) if rep % c == 0)
    qq = _lin(x, lp["q_weight"], q).reshape(t, g, rep // part, part, dh)
    kk = _lin(x, lp["k_weight"], q).reshape(t, g, dh)
    vv = _lin(x, lp["v_weight"], q).reshape(t, g, dh)
    # (G x rep / part) stretches of `part` query heads, each with its group's
    # keys and values
    stretches = (
        qq.transpose(1, 2, 3, 0, 4).reshape(g * (rep // part), part, t, dh),
        jnp.repeat(kk.transpose(1, 0, 2), rep // part, axis=0),
        jnp.repeat(vv.transpose(1, 0, 2), rep // part, axis=0))
    attend = functools.partial(_attend, q=q)
    a = jax.lax.map(jax.checkpoint(attend) if remat else attend, stretches)
    a = a.reshape(heads, t, dh).transpose(1, 0, 2).reshape(t, heads * dh)
    return _lin(a, lp["proj_weight"], q)


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
        held = (index >= lo) & (index < lo + cfg["n_routed_experts"])
        over = jnp.where(held, score, 0.0)
    total = jnp.sum(over, axis=-1, keepdims=True)
    if fault == "held_norm":
        total = jnp.where(total > 0, total, 1.0)
    return cfg["routed_scaling_factor"] * score / total, index


def _moe(x, lp, cfg, q, fault):
    w, index = route(x, lp["router_weight"], cfg, fault)
    out = _ffn(x, lp["shared_ff_up_weight"], lp["shared_ff_down_weight"], q)

    @jax.checkpoint
    def expert(acc, held):      # one expert held here, over all tokens
        e, up, down = held
        mine = jnp.sum(jnp.where(index == cfg["expert_offset"] + e, w, 0.0),
                       axis=-1)
        return acc + mine[:, None] * _ffn(x, up, down, q), None

    return jax.lax.scan(
        expert, out, (jnp.arange(cfg["n_routed_experts"]),
                      lp["experts_up_weight"], lp["experts_down_weight"]))[0]


def _layer(h, lp, kind, cfg, q, fault, remat):
    eps = cfg["layer_norm_epsilon"]
    if kind == MAMBA:
        return h + _mamba(_rms(h, lp["mix_norm_gamma"], eps), lp, cfg, q,
                          fault)
    if kind == ATTENTION:
        return h + _attention(_rms(h, lp["mix_norm_gamma"], eps), lp, cfg, q,
                              remat)
    return h + _moe(_rms(h, lp["ffn_norm_gamma"], eps), lp, cfg, q, fault)


def _layer_params(params, i):
    p = "l%d_" % i
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def row_logits(params, tokens, cfg, quant=None, remat=True, fault=None):
    """Logits (T, V) of one row of ids (T,)."""
    q = common.rounder(quant)
    h = params["tok_emb_weight"][tokens]
    for i, kind in enumerate(pattern(cfg)):
        layer = functools.partial(_layer, kind=kind, cfg=cfg, q=q,
                                  fault=fault, remat=remat)
        h = (jax.checkpoint(layer) if remat else layer)(
            h, _layer_params(params, i))
    h = _rms(h, params["norm_f_gamma"], cfg["layer_norm_epsilon"])
    return _lin(h, params["lm_head_weight"], q)


def route_choices(params, tokens, cfg):
    """The experts each token of `tokens` (B, T) is sent to in every expert
    layer, [(B, T, k) int32], as the sound reference routes them: what
    benchmark/tools/route_readings.py holds the program's choices against."""
    def same(a):
        return a

    def row(ids):
        h, picks = params["tok_emb_weight"][ids], []
        for i, kind in enumerate(pattern(cfg)):
            lp = _layer_params(params, i)
            if kind == EXPERTS:
                x = _rms(h, lp["ffn_norm_gamma"], cfg["layer_norm_epsilon"])
                picks.append(route(x, lp["router_weight"], cfg)[1])
            h = _layer(h, lp, kind, cfg, same, None, True)
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
    return (tokens.astype(jnp.int32),
            labels.reshape(tokens.shape).astype(jnp.int32))
