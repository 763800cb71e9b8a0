"""Operations and bytes serving the OPT decoder needs, from its shapes.

A token processed (prompt or output) costs 2 flops a weight of every matrix
it passes (per layer q, k, v, out: 4 d^2, and the FFN: 2 d f) and, for a
generated token, of the head (v d); attention adds 4 d flops per layer and
cached position. Embedding look-ups count nothing. A decode step must read
every weight once and the live cache once, whatever the batch.
"""


def flops_per_token(cfg, context, head=True):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    layer = 2 * (4 * d * d + 2 * d * f) + 4 * d * context
    return cfg["num_hidden_layers"] * layer + (2 * v * d if head else 0)


def weight_bytes(cfg, bytes_per=2):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    per_layer = 4 * d * d + 2 * d * f + 9 * d + f
    return bytes_per * (n * per_layer + 2 * v * d + v + 2 * d
                        + cfg["max_position_embeddings"] * d)


def kv_bytes_per_token(cfg, bytes_per=2):
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * bytes_per
