"""Operations a training step of the Nemotron-H decoder needs, from its
shapes, and the operations and bytes of its kernels.

Per token, forward: 2 flops a weight of every matrix the token passes. An
`M` layer: the in-projection (2 d (2 H P + 2 G N + H)), the out-projection
(2 H P d), the short convolution's K taps a channel (2 K (H P + 2 G N)) and
the recurrence as written, 5 N P a head (the decay's product N P, the
write dt x B^T and the read S^T C a multiply and an add a state element
each). An `E` layer: the shared expert 4 d f_s (two matrices), the router
2 d E over all E experts it scores, and the routed experts held here,
4 d f_e a (token, expert) pair, **at the uniform expectation** of
top_k x held / E pairs a token (6 x 8 / 128 = 0.375: with weights drawn at
random every expert is as likely as another; the step's own count is
`moe_pairs_per_token`). A `*` layer of H query heads over G key/value heads
of dh: q (2 d H dh), k and v (2 d G dh each), the output projection
(2 H dh d) and causal attention's 2 (QK^T) + 2 (PV) flops per query head,
dim and key over on average (T + 1) / 2 keys. The head: 2 v d; the
embedding is a gather; softplus, SiLU, the gated norm and the skip D x are
elementwise and count for nothing. Backward is twice the forward;
recomputation does not count.

`ssd` / `ssd_bwd`: one call of the state-space scan **from the recurrence
as written**, the same whatever chunk size or kernel computes it: 5 N P
flops a token a head forward, twice that backward; x read and y written
once (2 bytes), dt once (4 bytes), B and C once a GROUP (2 bytes: the
heads of a group share them); backward those and dy read, dx, dB, dC
(2 bytes), ddt and dA's per-token part (4 bytes each) written. At N 128,
P 64, 8 heads a group that is 126 flops a byte against the v5e's 240: the
bytes bound both.

`flash_fwd` / `flash_bwd`: causal attention as written, as
laguna-s-2.1-train/flops.py counts it: q, dO and the outputs per query
head, k, v and their gradients per key/value head, once each.

`moe_gmm`: the grouped products of one expert layer, forward and backward,
from a count of pairs handed in, whatever implements them: two products a
pair forward (up, down), each again for the input's and for the weight's
gradient backward; each pair's row read and written in 2 bytes, the held
experts' weights read once a pass and their gradients written once.
"""

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def _pattern(cfg):
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def _mamba_dims(cfg):
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"])


def pairs_per_token_expected(cfg):
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_num_experts"])


def forward_flops_per_token(cfg, seq_len):
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, p, g, n = _mamba_dims(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = _pattern(cfg)
    mamba = (2 * d * (2 * h * p + 2 * g * n + h) + 2 * h * p * d
             + 2 * cfg["conv_kernel"] * (h * p + 2 * g * n) + 5 * n * p * h)
    experts = (4 * d * cfg["moe_shared_expert_intermediate_size"]
               + 2 * d * cfg["router_num_experts"]
               + pairs_per_token_expected(cfg) * 4 * d
               * cfg["moe_intermediate_size"])
    attention = (2 * d * (hq * dh + 2 * hk * dh) + 2 * hq * dh * d
                 + 4 * hq * dh * (seq_len + 1) / 2)
    return (kinds.count(MAMBA) * mamba + kinds.count(EXPERTS) * experts
            + kinds.count(ATTENTION) * attention + 2 * v * d)


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, int(traffic["seq_len"]))


def _scan(cfg, traffic, batch):
    """(tokens, flops a token of the recurrence, elements a token of x, of
    dt, of B or C)."""
    h, p, g, n = _mamba_dims(cfg)
    return (batch * int(traffic["seq_len"]), 5 * n * p * h, h * p, h, g * n)


def ssd(cfg, traffic, batch):
    """(flops, bytes) one forward call of the scan needs."""
    tokens, flops, x, dt, bc = _scan(cfg, traffic, batch)
    return tokens * flops, tokens * (2 * x * 2 + dt * 4 + 2 * bc * 2)


def ssd_bwd(cfg, traffic, batch):
    """(flops, bytes) of one backward call."""
    tokens, flops, x, dt, bc = _scan(cfg, traffic, batch)
    return (tokens * 2 * flops,
            tokens * (4 * x * 2 + 3 * dt * 4 + 4 * bc * 2))


def _attention(cfg, traffic, batch):
    """(query-key pairs x head dim over all query heads, elements of a
    per-query-head operand, of a per-key/value-head operand, rows)."""
    t, dh = int(traffic["seq_len"]), cfg["head_dim"]
    h = cfg["num_attention_heads"]
    return (batch * h * t * (t + 1) / 2 * dh, batch * h * t * dh,
            batch * cfg["num_key_value_heads"] * t * dh, batch * h * t)


def flash_fwd(cfg, traffic, batch):
    """(flops, bytes) the attention layer's forward call needs under
    differentiation: two products a pair; q read and o written per query
    head, k and v read per key/value head (2 bytes), the rows' log-sum-exp
    written (4)."""
    pairs, per_q, per_kv, rows = _attention(cfg, traffic, batch)
    return 4 * pairs, (2 * per_q + 2 * per_kv) * 2 + rows * 4


def flash_bwd(cfg, traffic, batch):
    """(flops, bytes) of its backward call: five products a pair; q and dO
    read and dQ written per query head, k, v read and dK, dV written per
    key/value head (2 bytes), log-sum-exp and delta read (4)."""
    pairs, per_q, per_kv, rows = _attention(cfg, traffic, batch)
    return 10 * pairs, (3 * per_q + 4 * per_kv) * 2 + 2 * rows * 4


def moe_gmm(cfg, traffic, batch, pairs):
    """(flops, bytes) of one expert layer's grouped products, forward and
    backward, at `pairs` (token, expert) pairs."""
    d, f, held = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["n_routed_experts"])
    one_pass = pairs * (2 * d + 2 * f) * 2 + 2 * held * d * f * 2
    return 3 * pairs * 4 * d * f, 3 * one_pass
