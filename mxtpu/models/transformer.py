"""Decoder-only transformer language model (symbol factory).

The reference era (MXNet 0.11) predates transformers — its sequence
baseline is the LSTM bucketing LM (example/rnn/lstm_bucketing.py). This
family is the long-context flagship this framework treats as first-class:
attention runs through the flash kernels (ops/attention.py
`_contrib_FlashAttention`): the forward saves its output and each row's
log-sum-exp, O(T) numbers a head, and the backward kernel recomputes the
scores block by block in VMEM — no T^2 tensor reaches HBM in either
direction at sequence lengths the backward tiles (multiples of 128 up to
4096; others take the reference's VJP, which materializes the scores).
The same graph trains sequence-parallel via
`mxtpu.parallel.ring_attention`/`ulysses_attention` over a 'seq' mesh
axis (tests/test_parallel.py, __graft_entry__.dryrun_multichip).

The graph is built by `mxtpu.models.decoder`, the family's one builder,
with LayerNorm before each sublayer, a ReLU FFN and learned positions.

Layout discipline: tokens (B, T) -> embeddings (B, T, D); attention in
(B, H, T, dh); every matmul is a FullyConnected(flatten=False) along the
last axis so XLA tiles them onto the MXU in bf16.
"""
from . import decoder


def get_symbol(vocab_size, seq_len, num_layers=2, num_heads=4, d_model=128,
               d_ff=None, dropout=0.0, max_len=None, dtype=None):
    """Causal LM: data (B, T) int tokens -> SoftmaxOutput over (B*T, vocab).

    Train with label = data shifted left by one (next-token prediction),
    flattened to (B*T,).

    ``max_len`` sizes the learned positional table independently of this
    symbol's seq_len, so BucketingModule buckets of different lengths
    share ONE ``pos_emb`` (the transformer analogue of the LSTM bucketing
    LM's shared parameters — each bucket slices the common table).

    ``dtype='bfloat16'`` casts activations to bf16 right after the
    embedding (token ids stay f32 — bf16 integers are exact only to 256)
    and casts the logits back to f32 before the softmax. The block
    weights follow the activation dtype via the bidirectional InferType
    rule, so every matmul tiles onto the MXU in bf16; optimizer state
    stays f32 (mxtpu/module/fused.py).
    """
    return decoder.build(
        vocab_size, seq_len, [decoder.FULL] * num_layers, num_heads, d_model,
        d_ff or 4 * d_model, norm="layer_pre", ffn="relu",
        positions="learned", dropout=dropout, max_len=max_len, dtype=dtype)
