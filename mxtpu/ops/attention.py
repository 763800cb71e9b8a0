"""Flash attention as a Pallas TPU kernel.

Beyond reference parity (the reference has no attention operator —
SURVEY.md §5 'Long-context'), but the hot op of any long-context model, so
it gets the full TPU treatment per /opt/skills/guides/pallas_guide.md:

- grid (batch*heads, q_blocks, kv_blocks), iterated sequentially on-core
  so VMEM scratch (running max / normalizer / accumulator) carries the
  online-softmax state across the kv dimension;
- q@k^T and p@v on the MXU with f32 accumulation (preferred_element_type);
- causal masking per block via broadcasted iotas;
- output written once, on the last kv block, normalized by the running sum.

Backward runs through a jax.custom_vjp whose residual-free bwd recomputes
with the pure-jnp reference (identical math) — the standard
recompute-in-bwd tradeoff flash attention makes anyway.

Which forward runs is decided per compiled program, from the platform the
program is lowered for (``lax.platform_dependent``): on ``tpu`` always the
Mosaic kernel; on ``cpu`` the same kernel in interpret mode for small
shapes (tests) and the jnp reference otherwise. No other platform has a
branch, so lowering for one is an error rather than a quiet substitute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register

NEG_INF = -1e30


def _reference(q, k, v, scale, causal):
    """Pure-jnp oracle. (BH, T, D) layout. Materializes the T^2 score
    matrix — tests and small shapes only."""
    s = jnp.einsum("btd,bsd->bts", q, k).astype(jnp.float32) * scale
    if causal:
        t = s.shape[1]
        srng = s.shape[2]
        mask = jnp.arange(srng)[None, :] <= jnp.arange(t)[:, None]
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p.astype(v.dtype), v)


def _streaming(q, k, v, scale, causal, block=512):
    """lax.scan flash-style attention, (BH, T, D) layout: O(T) residuals,
    so its VJP is the memory-efficient backward recompute path."""
    bh, t, d = q.shape
    s_len = k.shape[1]
    nblk = -(-s_len // block)
    pad = nblk * block - s_len
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0))) if pad else k
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0))) if pad else v
    kb = kp.reshape(bh, nblk, block, d).transpose(1, 0, 2, 3)
    vb = vp.reshape(bh, nblk, block, d).transpose(1, 0, 2, 3)
    q_idx = jnp.arange(t)

    def body(carry, blk):
        m_prev, l_prev, o_prev = carry
        kc, vc, bi = blk
        s = jnp.einsum("btd,bsd->bts", q, kc).astype(jnp.float32) * scale
        k_idx = bi * block + jnp.arange(block)
        valid = k_idx[None, :] < s_len
        if causal:
            valid = valid & (k_idx[None, :] <= q_idx[:, None])
        s = jnp.where(valid[None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1)
        o_new = o_prev * alpha[..., None] + jnp.einsum(
            "bts,bsd->btd", p, vc.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((bh, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, t), jnp.float32)
    o0 = jnp.zeros((bh, t, d), jnp.float32)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0),
                                (kb, vb, jnp.arange(nblk)))
    return (o / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, block_q, block_k, kv_len):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    # causal: a kv block strictly above the diagonal contributes nothing —
    # skip its matmuls entirely (halves the causal FLOPs)
    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _accumulate():
        q = q_ref[0]  # (block_q, D)
        k = k_ref[0]  # (block_k, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(cols <= rows, s, NEG_INF)
        v_blk = v_ref[0]
        if kv_len % block_k != 0:
            # tail block: padded KV columns must not enter the softmax,
            # and padded V rows may be garbage/NaN — 0 * NaN = NaN, so
            # zero them instead of relying on p == 0
            s = jnp.where(cols < kv_len, s, NEG_INF)
            vrows = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)
            v_blk = jnp.where(vrows < kv_len, v_blk, 0)

        m_prev = m_ref[:, :1]                      # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                     # (block_q, block_k) f32
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] /
                    jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


def _flash_call(q, k, v, scale, causal, block_q, block_k, interpret):
    bh, t, d = q.shape
    s_len = k.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, s_len)
    grid = (bh, pl.cdiv(t, block_q), pl.cdiv(s_len, block_k))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               kv_len=s_len)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _forward(q, k, v, scale, causal, block_q, block_k):
    """The forward for the platform this program is compiled for. Jitted
    so the choice follows the operands' device even when called eagerly
    (a CPU-committed operand on a chip host must not reach Mosaic)."""
    def on_tpu(q, k, v):
        return _flash_call(q, k, v, scale, causal, block_q, block_k,
                           interpret=False)

    def on_cpu(q, k, v):
        # interpret mode exercises the kernel logic on CPU for small
        # problems; big CPU shapes take the reference path
        if q.shape[0] * q.shape[1] * k.shape[1] <= 1 << 22:
            return _flash_call(q, k, v, scale, causal, block_q, block_k,
                               interpret=True)
        return _reference(q, k, v, scale, causal)

    return jax.lax.platform_dependent(q, k, v, tpu=on_tpu, cpu=on_cpu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q, k, v, scale, causal, block_q, block_k):
    return _forward(q, k, v, scale, causal, block_q, block_k)


def _flash3_fwd(q, k, v, scale, causal, block_q, block_k):
    return _flash3(q, k, v, scale, causal, block_q, block_k), (q, k, v)


def _flash3_bwd(scale, causal, block_q, block_k, res, g):
    q, k, v = res
    # recompute through the streaming implementation: its scan keeps O(T)
    # residuals, so long-context training never materializes T^2 scores
    _, vjp = jax.vjp(lambda a, b, c: _streaming(a, b, c, scale, causal,
                                                block=block_k),
                     q, k, v)
    return vjp(g)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=512,
                    block_k=1024):
    """Multi-head attention, (B, H, T, D) layout (B/H merged internally)."""
    b, h, t, d = q.shape
    s_len = k.shape[2]
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (d ** 0.5)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, s_len, d)
    vf = v.reshape(b * h, s_len, d)
    out = _flash3(qf, kf, vf, scale, bool(causal), int(block_q),
                  int(block_k))
    return out.reshape(b, h, t, d)


def _flash_op(a, q, k, v):
    return flash_attention(q, k, v, causal=a.causal,
                           sm_scale=(a.sm_scale if a.sm_scale != 0.0
                                     else None),
                           block_q=a.block_q, block_k=a.block_k)


register("_contrib_FlashAttention", _flash_op,
         arg_names=["query", "key", "value"],
         attrs={"causal": False, "sm_scale": 0.0, "block_q": 512,
                "block_k": 1024},
         aliases=("flash_attention",))
