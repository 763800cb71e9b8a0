"""The system under test for `opt-1.3b-train`: mxtpu's transformer LM symbol
at the configuration's sizes, and how a batch is drawn from the seed."""


def symbol(cfg, traffic):
    from mxtpu.models import transformer
    return transformer.get_symbol(
        cfg["vocab_size"], int(traffic["seq_len"]),
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], d_model=cfg["hidden_size"],
        d_ff=cfg["ffn_dim"], dropout=cfg["dropout"],
        max_len=cfg["max_position_embeddings"], dtype=cfg["dtype"])


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
