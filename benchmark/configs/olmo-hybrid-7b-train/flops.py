"""Operations a training step of the Olmo-Hybrid decoder needs, from its
shapes, and the operations and bytes of its gated delta rule.

Per token, forward: 2 flops a weight of every matrix the token passes. A
linear-attention layer: q, k (2 d H d_k), v and the output gate (2 d H d_v),
the output projection (H d_v d), the decay's and beta's (2 d H), the short
convolutions' K taps a channel, and the recurrence, 6 d_k d_v a head
(S~^T k, the rank-one update, S^T q: 2 d_k d_v each). A full-attention
layer: 4 d^2 and causal attention's 2 (QK^T) + 2 (PV) flops per channel and
key over on average (T+1)/2 keys. Every layer: the gated FFN's 3 d f. The
head: v d; the embedding is a gather. Backward is twice the forward;
recomputation does not count.

The delta rule is counted from the recurrence as written, not from the
implementation, so the count is the same whatever chunk size or kernel
computes it: 6 d_k d_v flops a token a head forward, twice that again
backward; q, k, v, g and beta read and o written once (backward: those and
dO read, five gradients written). At d_k 96, d_v 192 that is 96 flops a
byte against the v5e's 240: the bytes bound it.

The full-attention layer's two flash kernels are counted from causal
attention as written: QK^T and PV over the keys at or before each query
(forward), and the backward's five products over the same pairs (the
scores again, dP = dO V^T, dV, dK, dQ). At head size 128 and T = 2048 the
flops bound both.
"""

LINEAR = "linear_attention"


def _layers(cfg):
    kinds = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    linear = sum(1 for k in kinds if k == LINEAR)
    return linear, len(kinds) - linear


def _linear_dims(cfg):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def forward_flops_per_token(cfg, seq_len):
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, dk, dv = _linear_dims(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    ffn = 2 * 3 * d * f
    linear = (2 * (2 * d * h * dk + 2 * d * h * dv + h * dv * d + 2 * d * h)
              + 2 * taps * h * (2 * dk + dv) + 6 * dk * dv * h)
    full = 2 * 4 * d * d + 4 * d * (seq_len + 1) / 2
    n_linear, n_full = _layers(cfg)
    return n_linear * (linear + ffn) + n_full * (full + ffn) + 2 * v * d


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, int(traffic["seq_len"]))


def _token_heads(cfg, traffic, batch):
    return batch * int(traffic["seq_len"]) * cfg["linear_num_value_heads"]


def delta_rule(cfg, traffic, batch):
    """(flops, bytes) one forward call of the gated delta rule needs: q, k,
    v and beta read and o written in the activations' 2 bytes, g in 4."""
    _, dk, dv = _linear_dims(cfg)
    n = _token_heads(cfg, traffic, batch)
    return n * 6 * dk * dv, n * ((2 * dk + 2 * dv) * 2 + 4 + 2)


def delta_rule_bwd(cfg, traffic, batch):
    """(flops, bytes) of one backward call: the forward's inputs and dO
    read, dq, dk, dv (2 bytes) and dg, dbeta (4 bytes) written."""
    _, dk, dv = _linear_dims(cfg)
    n = _token_heads(cfg, traffic, batch)
    return (n * 12 * dk * dv,
            n * ((2 * dk + dv) * 2 + 4 + 2 + dv * 2 + (2 * dk + dv) * 2 + 8))


def _attention_pairs(cfg, traffic, batch):
    """(heads x query-key pairs under the causal mask, elements of one
    (B, H, T, head) operand)."""
    t, h = int(traffic["seq_len"]), cfg["num_attention_heads"]
    dh = cfg["hidden_size"] // h
    return batch * h * t * (t + 1) / 2 * dh, batch * h * t * dh


def flash_fwd(cfg, traffic, batch):
    """(flops, bytes) one forward call of the flash kernel needs under
    differentiation: two products a pair; q, k, v read and o written in 2
    bytes, the rows' log-sum-exp written in 4."""
    pairs, elems = _attention_pairs(cfg, traffic, batch)
    rows = elems / (cfg["hidden_size"] // cfg["num_attention_heads"])
    return 4 * pairs, 4 * elems * 2 + rows * 4


def flash_bwd(cfg, traffic, batch):
    """(flops, bytes) of one backward call: five products a pair; q, k, v
    and dO read, dQ, dK, dV written (2 bytes), log-sum-exp and
    delta = rowsum(dO * O) read (4 bytes)."""
    pairs, elems = _attention_pairs(cfg, traffic, batch)
    rows = elems / (cfg["hidden_size"] // cfg["num_attention_heads"])
    return 10 * pairs, 7 * elems * 2 + 2 * rows * 4
