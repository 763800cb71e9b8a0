"""Operations a training step of the Laguna decoder needs, from its shapes,
and the operations and bytes of its kernels.

Per token, forward: 2 flops a weight of every matrix the token passes. An
attention layer of H query heads over G key/value heads of dh: q (2 d H dh),
k and v (2 d G dh each), the gate (2 d H), the output projection
(2 H dh d), and causal attention's 2 (QK^T) + 2 (PV) flops per query head,
dim and key, over on average (T + 1) / 2 keys in a full layer and
mean_t min(t + 1, window) in a window layer (the mask as written). The dense
layer's gated FFN 6 d f. An expert layer: the shared expert 6 d f_s, the
router 2 d E over all E experts it scores, and the routed experts held
here, 6 d f_e a (token, expert) pair, **at the uniform expectation** of
top_k x held / E pairs a token (10 x 8 / 256 = 0.3125: with weights drawn
at random every expert is as likely as another; the step's own count is
`moe_pairs_per_token`). The head: 2 v d; the embedding is a gather; the
rotation is elementwise and counts for nothing. Backward is twice the
forward; recomputation does not count.

The flash kernels are counted from attention as written: QK^T and PV over
the (query, key) pairs the mask leaves (forward), and the backward's five
products over the same pairs; q, dO and the outputs per query head, k, v and
their gradients per key/value head, once each. `flash_fwd` / `flash_bwd` are
one full-attention call (`num_attention_heads` query heads), `flash_win_fwd`
/ `flash_win_bwd` one window call (the window layers' count).

`moe_gmm`: the grouped products of one expert layer, forward and backward,
from a count of pairs handed in, whatever implements them: three products a
pair forward (gate, up, down), each again for the input's and for the
weight's gradient backward; each pair's row read and written in 2 bytes,
the held experts' weights read once a pass and their gradients written once.
"""

FULL, SLIDING = "full_attention", "sliding_attention"


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def _keys_per_query(t, window=0):
    """Mean over the row's queries of the keys the mask leaves each."""
    if not window or window >= t:
        return (t + 1) / 2
    return (window * (window + 1) / 2 + (t - window) * window) / t


def pairs_per_token_expected(cfg):
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_num_experts"])


def forward_flops_per_token(cfg, seq_len):
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    g = cfg["num_key_value_heads"]
    total = 2 * v * d
    for kind, h, mlp in _layers(cfg):
        window = cfg["sliding_window"] if kind == SLIDING else 0
        total += 2 * d * (h * dh + 2 * g * dh + h) + 2 * h * dh * d
        total += 4 * h * dh * _keys_per_query(seq_len, window)
        if mlp == "dense":
            total += 6 * d * cfg["intermediate_size"]
        else:
            total += (6 * d * cfg["shared_expert_intermediate_size"]
                      + 2 * d * cfg["router_num_experts"]
                      + pairs_per_token_expected(cfg) * 6 * d
                      * cfg["moe_intermediate_size"])
    return total


def train_flops_per_item(cfg, traffic):
    return 3 * forward_flops_per_token(cfg, int(traffic["seq_len"]))


def _attention(cfg, traffic, batch, kind):
    """(query-key pairs x head dim over all query heads, elements of a
    per-query-head operand, of a per-key/value-head operand, rows)."""
    t, dh = int(traffic["seq_len"]), cfg["head_dim"]
    h = next(n for k, n, _ in _layers(cfg) if k == kind)
    window = cfg["sliding_window"] if kind == SLIDING else 0
    return (batch * h * t * _keys_per_query(t, window) * dh,
            batch * h * t * dh, batch * cfg["num_key_value_heads"] * t * dh,
            batch * h * t)


def _fwd(cfg, traffic, batch, kind):
    pairs, per_q, per_kv, rows = _attention(cfg, traffic, batch, kind)
    return 4 * pairs, (2 * per_q + 2 * per_kv) * 2 + rows * 4


def _bwd(cfg, traffic, batch, kind):
    pairs, per_q, per_kv, rows = _attention(cfg, traffic, batch, kind)
    return 10 * pairs, (3 * per_q + 4 * per_kv) * 2 + 2 * rows * 4


def flash_fwd(cfg, traffic, batch):
    """(flops, bytes) one full-attention forward call needs under
    differentiation: two products a pair; q read and o written per query
    head, k and v read per key/value head (2 bytes), the rows' log-sum-exp
    written (4)."""
    return _fwd(cfg, traffic, batch, FULL)


def flash_bwd(cfg, traffic, batch):
    """(flops, bytes) of one full-attention backward call: five products a
    pair; q and dO read and dQ written per query head, k, v read and dK, dV
    written per key/value head (2 bytes), log-sum-exp and delta read (4)."""
    return _bwd(cfg, traffic, batch, FULL)


def flash_win_fwd(cfg, traffic, batch):
    """As `flash_fwd`, one window call: the pairs a window of
    `sliding_window` keys leaves under the causal mask."""
    return _fwd(cfg, traffic, batch, SLIDING)


def flash_win_bwd(cfg, traffic, batch):
    return _bwd(cfg, traffic, batch, SLIDING)


def moe_gmm(cfg, traffic, batch, pairs):
    """(flops, bytes) of one expert layer's grouped products, forward and
    backward, at `pairs` (token, expert) pairs."""
    d, f, held = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                  cfg["num_experts"])
    one_pass = pairs * (2 * d + 3 * f) * 2 + 3 * held * d * f * 2
    return 3 * pairs * 6 * d * f, 3 * one_pass
