"""Ring attention: sequence/context parallelism over the device mesh.

Beyond reference parity (the reference has no attention op at all — SURVEY.md §5
'Long-context'), but first-class here per the TPU design brief: long sequences
shard over a 'seq' mesh axis; K/V blocks rotate around the ring with
lax.ppermute while each device accumulates its queries' attention in
numerically-stable flash style (running max / normalizer). Communication is
neighbor-to-neighbor so it rides ICI links at full bandwidth and overlaps with
the per-block matmuls on the MXU.

blockwise_attention is the single-device analogue (lax.scan over K/V chunks):
O(T) memory attention for long context on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, m_prev, l_prev, o_prev, mask=None, scale=1.0):
    """One flash-attention accumulation step.

    q: (B, Tq, H, D); k,v: (B, Tk, H, D); running stats per (B, Tq, H).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_cur = jnp.max(s, axis=-1)  # (B, H, Tq)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_cur = jnp.sum(p, axis=-1)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + l_cur
    o_cur = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o_prev * alpha.transpose(0, 2, 1)[..., None] + o_cur
    return m_new, l_new, o_new


def blockwise_attention(q, k, v, block_size=512, causal=False,
                        axis_name=None):
    """Memory-efficient attention on one device: scan over K/V blocks.

    Shapes: q (B, Tq, H, D), k/v (B, Tk, H, D). Returns (B, Tq, H, D).
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    nblk = max(1, -(-Tk // block_size))
    pad = nblk * block_size - Tk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nblk, block_size, H, D).transpose(1, 0, 2, 3, 4)
    q_idx = jnp.arange(Tq)

    def body(carry, blk):
        m, l, o = carry
        kblk, vblk, bi = blk
        k_idx = bi * block_size + jnp.arange(block_size)
        mask = (k_idx[None, :] < Tk)
        if causal:
            mask = mask & (k_idx[None, :] <= q_idx[:, None])
        mask = mask[None, None, :, :]  # (1,1,Tq,Tk_blk)
        m, l, o = _block_attn(q, kblk, vblk, m, l, o, mask=mask, scale=scale)
        return (m, l, o), None

    m0 = jnp.full((B, H, Tq), NEG_INF, q.dtype)
    l0 = jnp.zeros((B, H, Tq), q.dtype)
    o0 = jnp.zeros_like(q)
    if axis_name is not None:  # inside shard_map: carries must be varying
        m0 = lax.pcast(m0, axis_name, to="varying")
        l0 = lax.pcast(l0, axis_name, to="varying")
    (m, l, o), _ = lax.scan(body, (m0, l0, o0),
                            (kb, vb, jnp.arange(nblk)))
    return o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]


def ring_attention(q, k, v, mesh=None, axis_name="seq", causal=False):
    """Sequence-parallel attention: q/k/v sharded on T over ``axis_name``.

    Each device holds a T/p slice; K/V rotate p times via ppermute. Inside jit
    with the arrays sharded on the sequence axis, call this to get exact
    attention over the full sequence with only neighbor communication.
    """
    if mesh is None:
        from .mesh import current_mesh
        mesh = current_mesh()
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    spec = P(None, axis_name, None, None)

    def local_fn(ql, kl, vl):
        B, Tl, H, D = ql.shape
        scale = 1.0 / jnp.sqrt(D).astype(ql.dtype)
        my = lax.axis_index(axis_name)
        q_idx = my * Tl + jnp.arange(Tl)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

        def body(i, carry):
            m, l, o, kc, vc = carry
            src_rank = (my - i) % axis_size
            k_idx = src_rank * Tl + jnp.arange(Tl)
            if causal:
                mask = (k_idx[None, :] <= q_idx[:, None])[None, None]
            else:
                mask = None
            m, l, o = _block_attn(ql, kc, vc, m, l, o, mask=mask, scale=scale)
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return (m, l, o, kc, vc)

        m0 = lax.pcast(jnp.full((B, H, Tl), NEG_INF, ql.dtype), axis_name,
                       to="varying")
        l0 = lax.pcast(jnp.zeros((B, H, Tl), ql.dtype), axis_name,
                       to="varying")
        o0 = jnp.zeros_like(ql)
        m, l, o, _, _ = lax.fori_loop(0, axis_size, body, (m0, l0, o0, kl, vl))
        return o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)


def ulysses_attention(q, k, v, mesh=None, axis_name="seq", causal=False):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Alternative context-parallel strategy to ring_attention: q/k/v arrive
    sharded on the sequence axis (B, T/p, H, D); an all-to-all re-shards
    them to (B, T, H/p, D) so every device runs FULL-sequence attention
    over its head slice, then a second all-to-all restores sequence
    sharding. Two collectives total instead of p ppermute steps — better
    when heads >= devices and the interconnect favors fewer, larger
    transfers.
    """
    if mesh is None:
        from .mesh import current_mesh
        mesh = current_mesh()
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    spec = P(None, axis_name, None, None)

    def local_fn(ql, kl, vl):
        B, Tl, H, D = ql.shape
        assert H % axis_size == 0, \
            "ulysses needs heads (%d) divisible by axis size (%d)" % (
                H, axis_size)
        scale = 1.0 / jnp.sqrt(D).astype(ql.dtype)

        # both exchanges use split_axis == concat_axis (jax's all_to_all
        # reverse-mode mis-books cotangent shapes when they differ), with
        # explicit transposes putting the exchanged axis at position 1
        def to_heads(x):
            # (B, Tl, H, D) -> (B, p*Tl, H/p, D): split heads (group-major)
            # across the axis, gather the full sequence
            x = x.reshape(B, Tl, axis_size, H // axis_size, D)
            x = x.transpose(0, 2, 1, 3, 4)      # (B, p=head-group, Tl, ...)
            x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=1,
                               tiled=False)     # axis1 -> seq-block owner
            return x.reshape(B, axis_size * Tl, H // axis_size, D)

        def to_seq(x):
            # inverse: (B, T, H/p, D) -> (B, Tl, H, D)
            T = x.shape[1]
            x = x.reshape(B, axis_size, T // axis_size, H // axis_size, D)
            x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=1,
                               tiled=False)     # axis1 -> head-group owner
            x = x.transpose(0, 2, 1, 3, 4)      # (B, Tl, p, H/p, D)
            return x.reshape(B, T // axis_size, H, D)

        qh, kh, vh = to_heads(ql), to_heads(kl), to_heads(vl)
        # full-sequence attention on the local head slice (flash-style
        # streaming so long context stays O(T) memory)
        out = blockwise_attention(qh, kh, vh, block_size=512,
                                  causal=causal, axis_name=axis_name)
        return to_seq(out)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
