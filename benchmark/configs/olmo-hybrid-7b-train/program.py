"""The system under test for `olmo-hybrid-7b-train`: the zoo's decoder
family (mxtpu.models.decoder) at the configuration's sizes, the layers that
are held being the first `num_hidden_layers` of the published `layer_types`,
and how a batch is drawn from the seed (ids from the vocabulary's slice)."""


def symbol(cfg, traffic):
    from mxtpu.models import decoder
    return decoder.get_symbol(
        cfg["vocab_size"], int(traffic["seq_len"]),
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        num_heads=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"],
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_dim=cfg["linear_key_head_dim"],
        linear_value_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
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
