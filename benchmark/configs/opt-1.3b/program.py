"""The system under test for `opt-1.3b`: a paged decode bundle of the OPT
decoder for `DecodeSession(arena="paged")`, built from mxtpu.symbol ops.

mxtpu ships no decode bundle for its own transformer family
(serving/decode/model.py's `attn_step_symbol` has no LayerNorm, no positions
and a fixed 2 x embed FFN), and a model is the user's input to the decode
server, so this file builds the step and prefill graphs in the bundle format
`attn_decode_fixture` returns, with the same input names: pre-LayerNorm
blocks, FFN at the published width, final LayerNorm, learned positions looked
up from the count of cached positions that the session's masks give.
Parameter names follow mxtpu.models.transformer, so the plain reference of
`opt-1.3b-train` serves here too. Activations, weights and the KV cache are
`cfg["dtype"]`; scores, softmax and logits are float32.
"""
import numpy as np


def _proj(sym, x, layer, tag, n):
    return sym.FullyConnected(data=x, num_hidden=int(n),
                              name="l%d_%s" % (layer, tag))


def _count(sym, mask, width):
    """Ones in each row of a 0/1 mask, as (rows,): the mask's dot product
    with itself (`sum(axis=)` loses its axis through the graph's JSON)."""
    m = sym.Reshape(mask, shape=(-1, 1, int(width)))
    return sym.Reshape(sym.batch_dot(m, m, transpose_b=True), shape=(-1,))


def _embed(sym, cfg, data, pos_idx):
    v, e = cfg["vocab_size"], cfg["hidden_size"]
    n = cfg["max_position_embeddings"]
    x = sym.Reshape(sym.Embedding(data=data, input_dim=v, output_dim=e,
                                  name="tok_emb"), shape=(-1, e))
    table = sym.Reshape(sym.Variable("pos_emb", shape=(1, n, e)), shape=(n, e))
    pos = sym.Embedding(data=pos_idx, weight=table, input_dim=n, output_dim=e)
    return sym.Cast(x + sym.Cast(pos, dtype="float32"), dtype=cfg["dtype"])


def _head(sym, cfg, x):
    x = sym.LayerNorm(x, name="ln_f")
    logits = sym.FullyConnected(data=x, num_hidden=cfg["vocab_size"],
                                name="lm_head")
    return sym.Cast(logits, dtype="float32")


def _ffn(sym, cfg, x, i):
    ln = sym.LayerNorm(x, name="l%d_ln2" % i)
    ff = sym.Activation(_proj(sym, ln, i, "ff1", cfg["ffn_dim"]),
                        act_type="relu")
    return x + _proj(sym, ff, i, "ff2", cfg["hidden_size"])


def step_symbol(cfg, max_blocks, block_size):
    """One decode step for B sequences: inputs `data` (B, 1), `attn_mask`
    (B, T) over the cached positions, `kv_k_<i>` / `kv_v_<i>` (B, max_blocks,
    block, heads, dim); outputs logits (B, V) and the new k, v rows.

    The cache views are read once each, in the layout the arena hands them
    over: (B, T, heads x dim). Each head's query sits in its own block of a
    (heads, heads x dim) matrix (`head_mask`), so one batched product gives
    every head's scores and one more every head's output, with no transposed
    or masked copy of a view: 32 times the attention flops (1.3 GFLOP a layer
    at 8 x 1280 positions, nothing on the chip) for a sixth of the bytes. A
    masked position's weight is exactly 0 after the softmax, so what the pool
    holds there (zeros, or a finished sequence's finite rows) adds nothing.
    """
    from mxtpu import symbol as sym
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d, dt = e // h, cfg["dtype"]
    t = int(max_blocks) * int(block_size)
    scale = 1.0 / float(np.sqrt(d))
    data, mask = sym.Variable("data"), sym.Variable("attn_mask")
    head_mask = sym.Variable("head_mask", shape=(1, h, e))
    x = _embed(sym, cfg, data, _count(sym, mask, t))
    mask_h = sym.broadcast_axis(sym.Reshape(mask, shape=(-1, 1, t)),
                                axis=(1,), size=(h,))
    # (`slice_axis(end=)` stays a string through the graph's JSON; `slice`
    # parses its tuples)
    ones_h = sym.slice(sym.Reshape(mask, shape=(-1, 1, t)) * 0.0 + 1.0,
                       begin=(None, None, 0), end=(None, None, h))  # (B, 1, heads)
    ones_h = sym.Cast(ones_h, dtype=dt)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        kc = sym.Reshape(sym.Variable("kv_k_%d" % i), shape=(-1, t, e))
        vc = sym.Reshape(sym.Variable("kv_v_%d" % i), shape=(-1, t, e))
        ln = sym.LayerNorm(x, name="l%d_ln1" % i)
        q, k, v = (_proj(sym, ln, i, w, e) for w in ("q", "k", "v"))
        q_b = sym.broadcast_mul(sym.Reshape(q, shape=(-1, 1, e)), head_mask)
        s_cache = sym.Cast(sym.batch_dot(q_b, kc, transpose_b=True),
                           dtype="float32") * scale           # (B, heads, T)
        s_cache = sym.where(mask_h, s_cache, mask_h * 0.0 - 1e30)
        s_self = sym.Cast(sym.batch_dot(q_b, sym.Reshape(k, shape=(-1, e, 1))),
                          dtype="float32") * scale            # (B, heads, 1)
        p = sym.Cast(sym.softmax(sym.Concat(s_cache, s_self, dim=2), axis=-1),
                     dtype=dt)
        p_cache = sym.slice(p, begin=(None, None, 0), end=(None, None, t))
        p_self = sym.slice(p, begin=(None, None, t), end=(None, None, t + 1))
        o = sym.batch_dot(p_cache, vc) + sym.broadcast_mul(
            p_self, sym.Reshape(v, shape=(-1, 1, e)))         # (B, heads, e)
        attn = sym.Reshape(sym.batch_dot(
            ones_h, sym.broadcast_mul(o, head_mask)), shape=(-1, e))
        x = x + _proj(sym, attn, i, "proj", e)
        x = _ffn(sym, cfg, x, i)
        rows += [sym.Reshape(k, shape=(-1, h, d)),
                 sym.Reshape(v, shape=(-1, h, d))]
    return sym.Group([_head(sym, cfg, x)] + rows)


def prefill_symbol(cfg, max_blocks, block_size):
    """One prefill chunk of C prompt tokens of one sequence; inputs as
    `attn_prefill_symbol` documents them."""
    from mxtpu import symbol as sym
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d, dt = e // h, cfg["dtype"]
    t = int(max_blocks) * int(block_size)
    scale = 1.0 / float(np.sqrt(d))
    data = sym.Variable("data")
    mask_cache = sym.Variable("attn_mask_cache")
    mask_chunk = sym.Variable("attn_mask_chunk")
    kv_valid = sym.Variable("kv_valid_cache")
    chunk_valid = sym.Variable("chunk_valid")
    # a row's position: cached positions plus its place in the chunk (the
    # chunk mask is causal, so its row sum is that place, counted from 1)
    chunk_row = sym.Reshape(sym.batch_dot(
        sym.expand_dims(mask_chunk, axis=1), sym.expand_dims(mask_chunk, axis=1),
        transpose_b=True), shape=(-1,))
    x = _embed(sym, cfg, data, _count(sym, mask_cache, t) + chunk_row - 1.0)
    mc_h = sym.broadcast_axis(sym.expand_dims(mask_cache, axis=0),
                              axis=(0,), size=(h,))
    mk_h = sym.broadcast_axis(sym.expand_dims(mask_chunk, axis=0),
                              axis=(0,), size=(h,))
    vm_cache = sym.broadcast_axis(sym.expand_dims(
        sym.broadcast_axis(sym.Reshape(kv_valid, shape=(t, 1)),
                           axis=(1,), size=(d,)), axis=0),
        axis=(0,), size=(h,))
    vm_chunk = sym.broadcast_axis(sym.expand_dims(
        sym.broadcast_axis(chunk_valid, axis=(1,), size=(d,)), axis=0),
        axis=(0,), size=(h,))
    zc = sym.Cast(vm_cache * 0.0, dtype=dt)
    zk = sym.Cast(vm_chunk * 0.0, dtype=dt)
    rows = []
    for i in range(cfg["num_hidden_layers"]):
        kc, vc = sym.Variable("kv_k_%d" % i), sym.Variable("kv_v_%d" % i)
        ln = sym.LayerNorm(x, name="l%d_ln1" % i)
        q, k, v = (_proj(sym, ln, i, w, e) for w in ("q", "k", "v"))
        q_h, k_h, v_h, kc_h, vc_h = (sym.transpose(
            sym.Reshape(a, shape=(-1, h, d)), axes=(1, 0, 2))
            for a in (q, k, v, kc, vc))
        s_c = sym.Cast(sym.batch_dot(q_h, kc_h, transpose_b=True),
                       dtype="float32") * scale
        s_c = sym.where(mc_h, s_c, mc_h * 0.0 - 1e30)
        s_k = sym.Cast(sym.batch_dot(q_h, k_h, transpose_b=True),
                       dtype="float32") * scale
        s_k = sym.where(mk_h, s_k, mk_h * 0.0 - 1e30)
        p = sym.Cast(sym.softmax(sym.Concat(s_c, s_k, dim=2), axis=-1),
                     dtype=dt)
        vcat = sym.Concat(sym.where(vm_cache, vc_h, zc),
                          sym.where(vm_chunk, v_h, zk), dim=1)
        attn = sym.Reshape(sym.transpose(sym.batch_dot(p, vcat),
                                         axes=(1, 0, 2)), shape=(-1, e))
        x = x + _proj(sym, attn, i, "proj", e)
        x = _ffn(sym, cfg, x, i)
        rows += [sym.Reshape(k, shape=(-1, h, d)),
                 sym.Reshape(v, shape=(-1, h, d))]
    return sym.Group([_head(sym, cfg, x)] + rows)


def bundle(cfg, weights):
    """The `paged` bundle of DecodeSession: graphs, shapes, kv specs and the
    parameters (`weights`: {name: device array}, already in their dtypes)."""
    import jax.numpy as jnp
    import mxtpu as mx
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d = e // h
    mb, blk = int(cfg["max_blocks_per_seq"]), int(cfg["block_size"])
    t = mb * blk
    step = step_symbol(cfg, mb, blk)
    prefill = prefill_symbol(cfg, mb, blk)
    kv_specs = []
    for i in range(cfg["num_hidden_layers"]):
        kv_specs += [{"name": "kv_k_%d" % i, "shape": (h, d), "dtype": cfg["dtype"]},
                     {"name": "kv_v_%d" % i, "shape": (h, d), "dtype": cfg["dtype"]}]
    step_shapes = {"data": (1, 1), "attn_mask": (1, t)}
    prefill_shapes = {"data": (1, 1), "attn_mask_cache": (1, t),
                      "attn_mask_chunk": (1, 1), "kv_valid_cache": (1, t),
                      "chunk_valid": (1, 1)}
    axes = {"data": (0,), "attn_mask_cache": (0,), "attn_mask_chunk": (0, 1),
            "chunk_valid": (0,), "kv_valid_cache": ()}
    for s in kv_specs:
        step_shapes[s["name"]] = (1, mb, blk, h, d)
        prefill_shapes[s["name"]] = (1, mb, blk, h, d)
        axes[s["name"]] = ()
    params = {"arg:" + k: mx.nd.NDArray(v) for k, v in weights.items()}
    blocks = jnp.repeat(jnp.eye(h, dtype=cfg["dtype"]), d, axis=1)
    params["arg:head_mask"] = mx.nd.NDArray(blocks.reshape(1, h, e))
    return {"step_symbol_json": step.tojson(),
            "step_example_shapes": step_shapes,
            "prefill_symbol_json": prefill.tojson(),
            "prefill_example_shapes": prefill_shapes,
            "prefill_bucket_axes": axes,
            "params": params,
            "kv_specs": kv_specs, "block_size": blk,
            "max_blocks_per_seq": mb, "meta": {"family": "opt"}}


def session(cfg, weights):
    """The DecodeSession as the configuration states it."""
    from mxtpu.serving.decode import DecodeSession
    b = bundle(cfg, weights)
    chunk = int(cfg["prefill_chunk_tokens"])
    return DecodeSession(
        b["step_symbol_json"], b["params"], b["step_example_shapes"], [],
        arena="paged", paged=b, buckets=tuple(cfg["step_buckets"]),
        slot_capacity=int(cfg["slot_capacity"]),
        prefill_chunk_tokens=chunk, prefill_buckets=(chunk,),
        admission=None, max_queue=int(cfg["max_queue"]),
        warmup=bool(cfg.get("session_warmup", True)),
        default_timeout=float(cfg["request_timeout_s"]),
        version_tag="opt-1.3b")
