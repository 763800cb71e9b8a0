"""The system under test for `laguna-s-2.1-train`: the zoo's decoder family
(mxtpu.models.decoder) at the configuration's sizes, the layers that are
held being the first `num_hidden_layers` of the published per-layer lists,
the experts held being `num_experts` of the router's `router_num_experts`,
and how a batch is drawn from the seed (ids from the vocabulary's slice)."""


def _rope(cfg, kind):
    """One set of `rope_parameters` as the attributes of
    `_contrib_RotaryEmbedding`."""
    r = cfg["rope_parameters"][kind]
    out = {"rotary_dims": int(cfg["head_dim"] * r["partial_rotary_factor"]),
           "rope_type": r["rope_type"], "theta": float(r["rope_theta"])}
    if r["rope_type"] == "yarn":
        out.update(factor=float(r["factor"]),
                   original_max_position=r["original_max_position_embeddings"],
                   beta_fast=float(r["beta_fast"]),
                   beta_slow=float(r["beta_slow"]),
                   scale=r["attention_factor"])
    return out


def symbol(cfg, traffic):
    from mxtpu.models import decoder
    n = cfg["num_hidden_layers"]
    return decoder.get_laguna_symbol(
        cfg["vocab_size"], int(traffic["seq_len"]),
        layer_types=cfg["layer_types"][:n],
        num_heads=cfg["num_attention_heads_per_layer"][:n],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        mlp_layer_types=cfg["mlp_layer_types"][:n],
        window=cfg["sliding_window"],
        rope={kind: _rope(cfg, kind) for kind in cfg["rope_parameters"]},
        moe={"num_experts": cfg["router_num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["num_experts"],
             "expert_offset": cfg["expert_offset"],
             "hidden": cfg["moe_intermediate_size"],
             "shared_hidden": cfg["shared_expert_intermediate_size"],
             "scale": cfg["moe_routed_scaling_factor"],
             "norm_topk": cfg["norm_topk_prob"]},
        gate={"per-head": "per_head"}[cfg["gating"]],
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["dtype"])


def items_per_row(cfg, traffic):
    return int(traffic["seq_len"])


def inputs(cfg, traffic, batch):
    """(data descs, label descs, draw): `draw(key)` makes the token ids and
    the next-token labels on the device; every row differs."""
    import jax
    import jax.numpy as jnp
    t, v = int(traffic["seq_len"]), cfg["vocab_size"]

    def draw(key):
        ids = jax.random.randint(key, (batch, t + 1), 0, v)
        return {"data": ids[:, :-1].astype(jnp.float32),
                "softmax_label": ids[:, 1:].reshape(-1).astype(jnp.float32)}

    return ([("data", (batch, t), "float32")],
            [("softmax_label", (batch * t,), "float32")], draw)
