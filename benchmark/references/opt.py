"""Plain reference of the OPT decoder (Zhang et al., arXiv:2205.01068;
huggingface.co/facebook/opt-1.3b `modeling_opt.py`): learned positions,
pre-LayerNorm blocks with biased q/k/v/out projections and a ReLU FFN, a
final LayerNorm and the output head. Straightforward jax.numpy in float32
with matmul precision `highest`; no kernel, no cache, no batching tricks.

Departures from the published model, as `assumed` in the configurations'
files: the head is untied and has a bias, and positions start at 0 (the
published model offsets them by 2); both as mxtpu.models.transformer has
them. Parameter names follow that symbol, so the training and the serving
configuration share this file. Imports nothing of mxtpu.
"""
import functools

import jax
import jax.numpy as jnp

from . import common


def param_specs(cfg):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    std = "normal:%g" % cfg["init_std"]
    specs = [("tok_emb_weight", (v, d), std),
             ("pos_emb", (1, cfg["max_position_embeddings"], d), std),
             ("ln_f_gamma", (d,), "ones"), ("ln_f_beta", (d,), "zeros"),
             ("lm_head_weight", (v, d), std), ("lm_head_bias", (v,), "zeros")]
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        for ln in ("ln1", "ln2"):
            specs += [(p + ln + "_gamma", (d,), "ones"),
                      (p + ln + "_beta", (d,), "zeros")]
        for w in ("q", "k", "v", "proj"):
            specs += [(p + w + "_weight", (d, d), std),
                      (p + w + "_bias", (d,), "zeros")]
        specs += [(p + "ff1_weight", (f, d), std), (p + "ff1_bias", (f,), "zeros"),
                  (p + "ff2_weight", (d, f), std), (p + "ff2_bias", (d,), "zeros")]
    return specs


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _layer(h, lp, heads, q):
    """One decoder block on (B, T, D); `lp` maps the short names to arrays."""
    b, t, d = h.shape
    dh = d // heads

    def lin(x, w):
        return jnp.einsum("btd,ed->bte", q(x), q(lp[w + "_weight"]),
                          precision=common.HIGHEST) + lp[w + "_bias"]

    x = _ln(h, lp["ln1_gamma"], lp["ln1_beta"])
    qh, kh, vh = (lin(x, w).reshape(b, t, heads, dh).transpose(0, 2, 1, 3)
                  for w in ("q", "k", "v"))
    s = jnp.einsum("bhtd,bhsd->bhts", q(qh), q(kh),
                   precision=common.HIGHEST) / (dh ** 0.5)
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jnp.einsum("bhts,bhsd->bhtd", q(jax.nn.softmax(s, axis=-1)), q(vh),
                   precision=common.HIGHEST)
    a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
    h = h + lin(a, "proj")
    x = _ln(h, lp["ln2_gamma"], lp["ln2_beta"])
    return h + lin(jax.nn.relu(lin(x, "ff1")), "ff2")


def forward(params, tokens, cfg, quant=None, remat=True):
    """Logits (B, T, V) of the whole sequences `tokens` (B, T) of ids."""
    q = common.rounder(quant)
    t = tokens.shape[1]
    h = params["tok_emb_weight"][tokens] + params["pos_emb"][:, :t]
    layer = functools.partial(_layer, heads=cfg["num_attention_heads"], q=q)
    if remat:
        layer = jax.checkpoint(layer)
    for i in range(cfg["num_hidden_layers"]):
        p = "l%d_" % i
        lp = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}
        h = layer(h, lp)
    h = _ln(h, params["ln_f_gamma"], params["ln_f_beta"])
    return jnp.einsum("btd,vd->btv", q(h), q(params["lm_head_weight"]),
                      precision=common.HIGHEST) + params["lm_head_bias"]


def block_loss(cfg, quant=None):
    """(params, tokens, labels) -> (summed cross-entropy, metric's sum) of a
    block of whole rows; what `common.follow` differentiates."""
    def f(p, tokens, labels):
        logits = forward(p, tokens, cfg, quant)
        return common.ce_sum(logits.reshape(-1, logits.shape[-1]),
                             labels.reshape(-1))
    return f


def split_rows(tokens, labels):
    """The batch as the program gets it (labels flattened) -> row-major
    arrays whose first axis is the row."""
    return tokens.astype(jnp.int32), labels.reshape(tokens.shape).astype(jnp.int32)
