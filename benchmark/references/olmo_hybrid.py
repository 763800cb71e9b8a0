"""Plain reference of the Olmo-Hybrid decoder
(huggingface.co/allenai/Olmo-Hybrid-7B `config.json`): layers of two kinds
by `layer_types`, a Gated DeltaNet mixer (Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; beta in (0, 2): Grazzi et al., arXiv:2411.12537) or full
causal attention, each block h = x + RMSNorm(Mix(x)), y = h +
RMSNorm(FFN(h)) with a SiLU-gated FFN, a final RMSNorm and an untied head.
Straightforward jax.numpy in float32 with matmul precision `highest`; the
delta rule is stepped one token at a time as it is written, attention
materialises its scores; no kernel, no chunked form, no cache.

What the published config does not fix, as `assumed` in the configuration's
file: the block order and the QK-norm over all channels are Olmo 2/3's
(arXiv:2501.00656); no rotary embedding and no position table
(`rope_theta` is null); the short convolution has no bias; q and k are
L2-normalised per head as x / sqrt(sum x^2 + 1e-6); weights are drawn by
benchmark/weights.py's rules (`A_log` normal, `dt_bias` zero, where the
published initialisation draws A uniform and dt log-uniform). Parameter
names follow mxtpu.models.decoder's symbol. Imports nothing of mxtpu.

Memory: `block_loss` walks the rows of its block itself, one row at a time
under `jax.checkpoint`, each layer checkpointed again, the token recurrence
in checkpointed segments and attention a few heads at a time, so one row's
float32 activations are all that is live beside weights, gradient and
optimizer state.
"""
import functools

import jax
import jax.numpy as jnp

from . import common

FULL, LINEAR = "full_attention", "linear_attention"
SEGMENT = 64        # tokens a checkpointed segment of the recurrence
L2_EPS = 1e-6


def layer_types(cfg):
    """The kinds of the layers that are held: the first
    `num_hidden_layers` of the published pattern."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def param_specs(cfg):
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    kw = cfg["linear_conv_kernel_dim"]
    init = cfg["init"]
    std = "normal:%g" % cfg["init_std"]
    specs = [("tok_emb_weight", (v, d), std), ("norm_f_gamma", (d,), "ones"),
             ("lm_head_weight", (v, d), std)]
    for i, kind in enumerate(layer_types(cfg)):
        p = "l%d_" % i
        specs += [(p + "mix_norm_gamma", (d,), "ones"),
                  (p + "ffn_norm_gamma", (d,), "ones"),
                  (p + "ff_gate_weight", (f, d), std),
                  (p + "ff_up_weight", (f, d), std),
                  (p + "ff_down_weight", (d, f), std)]
        if kind == FULL:
            specs += [(p + w + "_weight", (d, d), std)
                      for w in ("q", "k", "v", "proj")]
            specs += [(p + "q_norm_gamma", (d,), "ones"),
                      (p + "k_norm_gamma", (d,), "ones")]
        else:
            specs += [(p + "q_weight", (h * dk, d), std),
                      (p + "k_weight", (h * dk, d), std),
                      (p + "v_weight", (h * dv, d), std),
                      (p + "g_weight", (h * dv, d), std),
                      (p + "q_conv_weight", (h * dk, kw), init["conv"]),
                      (p + "k_conv_weight", (h * dk, kw), init["conv"]),
                      (p + "v_conv_weight", (h * dv, kw), init["conv"]),
                      (p + "a_weight", (h, d), init["a_weight"]),
                      (p + "b_weight", (h, d), init["b_weight"]),
                      (p + "A_log", (h,), init["A_log"]),
                      (p + "dt_bias", (h,), init["dt_bias"]),
                      (p + "o_norm_gamma", (dv,), "ones"),
                      (p + "proj_weight", (d, h * dv), std)]
    return specs


def _rms(x, w, eps):
    return w * x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                          keepdims=True) + eps)


def _lin(x, w, q):
    return jnp.einsum("td,ed->te", q(x), q(w), precision=common.HIGHEST)


def _ffn(h, lp, q):
    gate = jax.nn.silu(_lin(h, lp["ff_gate_weight"], q))
    return _lin(gate * _lin(h, lp["ff_up_weight"], q), lp["ff_down_weight"], q)


def _attend(qkv, q):
    """Causal softmax attention of a few heads, (G, T, dh) each."""
    qh, kh, vh = qkv
    t, dh = qh.shape[1:]
    s = jnp.einsum("htd,hsd->hts", q(qh), q(kh),
                   precision=common.HIGHEST) / (dh ** 0.5)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hts,hsd->htd", q(jax.nn.softmax(s, axis=-1)), q(vh),
                      precision=common.HIGHEST)


def _full_mix(x, lp, cfg, q, head_groups):
    """x (T, d): QK-norm over all channels, heads of d / H, W_o."""
    t, d = x.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    qq = _rms(_lin(x, lp["q_weight"], q), lp["q_norm_gamma"], eps)
    kk = _rms(_lin(x, lp["k_weight"], q), lp["k_norm_gamma"], eps)
    vv = _lin(x, lp["v_weight"], q)
    qkv = tuple(a.reshape(t, heads, d // heads).transpose(1, 0, 2)
                for a in (qq, kk, vv))
    if head_groups > 1:     # a few heads' scores at a time
        qkv = tuple(a.reshape((head_groups, heads // head_groups) + a.shape[1:])
                    for a in qkv)
        a = jax.lax.map(jax.checkpoint(functools.partial(_attend, q=q)), qkv)
        a = a.reshape((heads,) + a.shape[2:])
    else:
        a = _attend(qkv, q)
    return _lin(a.transpose(1, 0, 2).reshape(t, d), lp["proj_weight"], q)


def _short_conv(x, w):
    """Causal depthwise convolution over time: x (T, C), w (C, K)."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[i:i + t] * w[:, i] for i in range(k))


def delta_rule(qh, kh, vh, g, beta, reset_every=None):
    """The gated delta rule as written, a token at a time: qh, kh (T, H,
    d_k), vh (T, H, d_v), g, beta (T, H) -> o (T, H, d_v). The state
    starts at 0. `reset_every` plants a fault: the state is set to 0 again
    every so many tokens."""
    t, h, dk = qh.shape
    dv = vh.shape[2]

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[:, None, None] * s
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt,
                                           precision=common.HIGHEST))
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=common.HIGHEST)

    @jax.checkpoint
    def segment(s, xs):
        if reset_every:
            s = jnp.zeros_like(s)
        return jax.lax.scan(token, s, xs)

    seg = reset_every or (SEGMENT if t % SEGMENT == 0 else t)
    xs = tuple(a.reshape((t // seg, seg) + a.shape[1:])
               for a in (qh, kh, vh, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(t, h, dv)


def _linear_mix(x, lp, cfg, q, reset_every):
    """x (T, d): the Gated DeltaNet mixer."""
    t = x.shape[0]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])

    def short(w, dh):
        y = _short_conv(_lin(x, lp[w + "_weight"], q), lp[w + "_conv_weight"])
        return jax.nn.silu(y).reshape(t, h, dh)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1,
                                         keepdims=True) + L2_EPS)

    qh, kh, vh = unit(short("q", dk)) * dk ** -0.5, unit(short("k", dk)), \
        short("v", dv)
    beta = jax.nn.sigmoid(_lin(x, lp["b_weight"], q))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        _lin(x, lp["a_weight"], q) + lp["dt_bias"])
    o = delta_rule(q(qh), q(kh), q(vh), g, beta, reset_every)
    gate = _lin(x, lp["g_weight"], q).reshape(t, h, dv)
    o = _rms(o, lp["o_norm_gamma"], cfg["rms_norm_eps"]) * jax.nn.silu(gate)
    return _lin(o.reshape(t, h * dv), lp["proj_weight"], q)


def _layer(h, lp, kind, cfg, q, head_groups, reset_every):
    eps = cfg["rms_norm_eps"]
    if kind == FULL:
        mix = _full_mix(h, lp, cfg, q, head_groups)
    else:
        mix = _linear_mix(h, lp, cfg, q, reset_every)
    h = h + _rms(mix, lp["mix_norm_gamma"], eps)
    return h + _rms(_ffn(h, lp, q), lp["ffn_norm_gamma"], eps)


def _head_groups(cfg):
    """Groups the heads' scores are taken in: a handful of heads at a time
    at the published count, all at once at a test's."""
    heads = cfg["num_attention_heads"]
    return next((g for g in (5, 4, 2) if heads % g == 0 and heads // g >= 4), 1)


def row_logits(params, tokens, cfg, quant=None, remat=True, fault=None):
    """Logits (T, V) of one row of ids (T,)."""
    q = common.rounder(quant)
    reset_every = SEGMENT if fault == "chunk_reset" else None
    h = params["tok_emb_weight"][tokens]
    for i, kind in enumerate(layer_types(cfg)):
        p = "l%d_" % i
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        layer = functools.partial(
            _layer, kind=kind, cfg=cfg, q=q, reset_every=reset_every,
            head_groups=_head_groups(cfg) if remat else 1)
        h = (jax.checkpoint(layer) if remat else layer)(h, lp)
    h = _rms(h, params["norm_f_gamma"], cfg["rms_norm_eps"])
    return _lin(h, params["lm_head_weight"], q)


def forward(params, tokens, cfg, quant=None, remat=True, fault=None):
    """Logits (B, T, V) of the whole sequences `tokens` (B, T) of ids."""
    return jax.lax.map(
        lambda row: row_logits(params, row, cfg, quant, remat, fault), tokens)


def block_loss(cfg, quant=None, fault=None):
    """(params, tokens, labels) -> (summed cross-entropy, metric's sum) of a
    block of whole rows, walked one row at a time; what `common.follow`
    differentiates. `fault="chunk_reset"` plants this architecture's own
    fault: the recurrent state set to 0 at every 64th token."""
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
