"""Operations a training step of the OPT decoder needs, from its shapes.

Per token, forward: 2 flops a weight of every matrix the token passes (q, k,
v, out: 4 d^2; FFN: 2 d f; per layer) and of the head (v d); the embedding
and position look-ups are gathers and count nothing. Causal attention adds
2 (QK^T) + 2 (PV) flops per head dimension and key, over on average (T+1)/2
keys: 4 d (T+1)/2 a layer. Backward is twice the forward. Recomputation does
not count. Checked against XLA's cost analysis in
tests/bench_yardstick/test_flops.py.
"""


def forward_flops_per_token(cfg, seq_len):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    layer = 2 * (4 * d * d + 2 * d * f) + 4 * d * (seq_len + 1) / 2
    return cfg["num_hidden_layers"] * layer + 2 * v * d


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, int(traffic["seq_len"]))


def flash_fwd(cfg, traffic, batch):
    """(flops, bytes) one call of the flash forward kernel needs: causal
    QK^T and PV over B x H heads, and q, k, v read and o written once."""
    t, d = int(traffic["seq_len"]), cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = d // h
    flops = batch * h * 4 * dh * t * (t + 1) / 2
    bytes_ = 4 * batch * h * t * dh * 2
    return flops, bytes_
