"""The system under test for `nemotron-twotower-30b-train`: the zoo's decoder
family (mxtpu.models.decoder) at the configuration's sizes, the layers that
are held being the first `num_hidden_layers` characters of the published
`hybrid_override_pattern`, the experts held being `n_routed_experts` of the
router's `router_num_experts`, and how a batch is drawn from the seed (ids
from the vocabulary's slice)."""


def symbol(cfg, traffic):
    from mxtpu.models import decoder
    return decoder.get_nemotron_h_symbol(
        cfg["vocab_size"], int(traffic["seq_len"]),
        pattern=cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        mamba={"num_heads": cfg["mamba_num_heads"],
               "head_dim": cfg["mamba_head_dim"],
               "n_groups": cfg["n_groups"],
               "state_size": cfg["ssm_state_size"],
               "conv_kernel": cfg["conv_kernel"],
               "chunk": cfg["chunk_size"]},
        moe={"num_experts": cfg["router_num_experts"],
             "top_k": cfg["num_experts_per_tok"],
             "experts_held": cfg["n_routed_experts"],
             "expert_offset": cfg["expert_offset"],
             "hidden": cfg["moe_intermediate_size"],
             "shared_hidden": cfg["moe_shared_expert_intermediate_size"],
             "scale": cfg["routed_scaling_factor"],
             "norm_topk": cfg["norm_topk_prob"],
             "activation": cfg["mlp_hidden_act"]},
        norm_eps=cfg["layer_norm_epsilon"], dtype=cfg["dtype"])


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
