"""Flash attention as Pallas TPU kernels, forward and backward.

Beyond reference parity (the reference has no attention operator —
SURVEY.md §5 'Long-context'), but the hot op of any long-context model, so
it gets the full TPU treatment per /opt/skills/guides/pallas_guide.md.

Queries are (B, H, T, D) and keys and values (B, G, S, D) with G dividing
H: query head j reads key/value head j // (H / G). The kernels index K and
V by group (a key/value head's block index is the query head's over H / G,
so consecutive grid steps of one group fetch it once); nothing is repeated
in HBM. ``window`` > 0, under a causal mask, lets a query see the `window`
keys that end in its own; such calls are named ``mxtpu_flash_win_fwd`` /
``mxtpu_flash_win_bwd``.

Forward, for the shapes the backward tiles (``_fwd_blocks``: T and S
multiples of 128, T = S under a causal mask, a head's K and V in VMEM):

- grid (batch*heads, q_blocks); the head's K and V stay resident and the
  kernel walks the key blocks itself. Under a causal mask a query block
  is several key blocks long (the whole sequence at the LM cells' sizes):
  a ``fori_loop`` visits the key blocks under its diagonal, then the ones
  the diagonal crosses, the only ones masked, follow as straight-line
  code, each against the queries from its own first on; the rest is
  neither computed nor a grid step;
- scores are held transposed, (block_k, block_q), as the backward holds
  them: the running maximum and sum are rows and their reductions run down
  the sublanes. They and the (D, block_q) accumulator are the loop's own
  values, float32;
- k@q^T and v^T@p on the MXU with f32 accumulation
  (preferred_element_type), ``p`` cast to the operands' dtype;
- the output is normalized by the running sum and turned to (block_q, D)
  once a query block; under differentiation the same step writes each
  row's log-sum-exp (float32), the one number the backward needs to
  rebuild the softmax;
- ``block_q`` / ``block_k`` follow T, S, D and the dtype
  (``_fwd_blocks``), as the backward's follow the shape; a caller's own
  are kept where they tile;
- under a window (a multiple of 128) queries and keys take one block size,
  the largest of 512, 256, 128 that divides T and the window: a query
  block visits the key block the window's lower edge crosses (masked), the
  ones between whole, and its own (masked on the diagonal); nothing before
  the edge block is a loop trip.

Other shapes (a ragged key length, a causal mask with T != S, a caller's
blocks that do not divide T and S or, under a causal mask, each other)
take the grid kernel: grid (batch*heads, q_blocks,
kv_blocks) iterated sequentially on-core, the online-softmax state in VMEM
scratch across the kv dimension, every live block masked by iotas, padded
key columns kept out of the softmax.

Backward (``jax.custom_vjp``): the residuals are ``(q, k, v, o, lse)``,
O(T) numbers a head. Nothing of size T x S is saved or written to HBM. One
kernel per head recomputes the scores block by block as
``p = exp(s - lse)``, with ``delta = rowsum(dO * O)`` taken from ``o``,
and accumulates dQ, dK and dV in VMEM in float32 (scores, ``lse``,
``delta`` and every accumulator are float32; ``p`` and ``dS`` are cast to
the operands' dtype for the MXU, as the forward casts ``p``). Under a
causal mask the blocks above the diagonal are never visited and only the
blocks on it are masked; under a window a key block's loop ends at the
query block `window` keys on, the second masked block. With fewer key/value
heads than query heads the grid is (key/value heads, query heads a group),
the second axis in order, and dK and dV gather over the group's query heads
in float32 scratch that holds the whole head, written out after the
group's last. Its block sizes follow T, S, D and the dtype
(``_bwd_blocks``); a caller's ``block_q`` / ``block_k`` are the forward's
alone. Shapes it does not tile (sequence lengths that are no multiple
of 128, a causal mask with T != S, a head too long for VMEM) save
``(q, k, v)`` and take the VJP of the jnp reference, which materializes
the scores.

Which program runs is decided per compiled program, from the shapes and
from the platform the program is lowered for (``lax.platform_dependent``):
on ``tpu`` always the Mosaic kernels; on ``cpu`` the same kernels in
interpret mode for small shapes (tests) and the jnp reference otherwise.
No other platform has a branch, so lowering for one is an error rather
than a quiet substitute.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .registry import register

NEG_INF = -1e30

# the kernels' names: a Mosaic call's HLO instruction is named after its
# kernel, which is how a trace reader tells the forward from the backward
FWD_KERNEL_NAME = "mxtpu_flash_fwd"
BWD_KERNEL_NAME = "mxtpu_flash_bwd"
# calls under a sliding window have names of their own, so that a reader of
# the two above goes on reading full-attention calls only
WIN_FWD_KERNEL_NAME = "mxtpu_flash_win_fwd"
WIN_BWD_KERNEL_NAME = "mxtpu_flash_win_bwd"

# scoped VMEM asked for the kernels that keep a whole head resident (the
# backward, and the forward's K and V): under a third of a v5e core's
# 128 MiB
_HEAD_VMEM_BYTES = 40 << 20



def _per_query_head(q, kv):
    """A key/value operand (B*G, S, D) as each of q's B*H heads reads it:
    query head j of a row reads key/value head j // (H / G). The oracle
    alone repeats; the kernels index by group."""
    rep = q.shape[0] // kv.shape[0]
    return kv if rep == 1 else jnp.repeat(kv, rep, axis=0)


def _scores(q, k, scale, causal, window=0):
    s = jnp.einsum("btd,bsd->bts", q, _per_query_head(q, k)
                   ).astype(jnp.float32) * scale
    if causal:
        rows = jnp.arange(s.shape[1])[:, None]
        cols = jnp.arange(s.shape[2])[None, :]
        mask = cols <= rows
        if window:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask[None], s, NEG_INF)
    return s


def _reference(q, k, v, scale, causal, window=0):
    """Pure-jnp oracle. (BH, T, D) queries over (BG, S, D) keys and values.
    Materializes the T^2 score matrix — tests and small shapes only."""
    p = jax.nn.softmax(_scores(q, k, scale, causal, window), axis=-1)
    return jnp.einsum("bts,bsd->btd", p.astype(v.dtype),
                      _per_query_head(q, v))


def _reference_vjp(q, k, v, g, scale, causal, window=0):
    _, vjp = jax.vjp(
        lambda a, b, c: _reference(a, b, c, scale, causal, window), q, k, v)
    return vjp(g)


def _interpretable(q, k):
    """Small enough for Pallas' interpret mode on the CPU."""
    return q.shape[0] * q.shape[1] * k.shape[1] <= 1 << 22


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_q, block_k,
                kv_len, window=0):
    # outputs (o and, under differentiation, lse), then the scratch
    o_ref, lse_ref = refs[0], (refs[1] if len(refs) == 5 else None)
    acc_ref, m_ref, l_ref = refs[-3:]
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
    if window:
        # nor does one whose last key is out of the first query's window
        live = live & ((j + 1) * block_k - 1 > i * block_q - window)

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
            seen = cols <= rows
            if window:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
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
        if lse_ref is not None:
            # m and l are columns broadcast over 128 lanes; the backward
            # reads lse as a row over the queries, so turn it here
            l_all = l_ref[...]
            lse = m_ref[...] + jnp.log(jnp.where(l_all == 0, 1.0, l_all))
            lse_ref[0, 0] = lse.T[:1]


def _flash_call(q, k, v, scale, causal, block_q, block_k, interpret,
                with_lse=False, window=0):
    """The forward kernel: o, and with `with_lse` also the rows'
    log-sum-exp, float32 (BH, T)."""
    bh, t, d = q.shape
    s_len = k.shape[1]
    rep = bh // k.shape[0]
    block_q = min(block_q, t)
    block_k = min(block_k, s_len)
    nq = pl.cdiv(t, block_q)
    grid = (bh, nq, pl.cdiv(s_len, block_k))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               kv_len=s_len, window=window)
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        # one (1, block_q) row a q block: its last two dims are the
        # array's own, so any block_q is a legal block
        out_shape.append(jax.ShapeDtypeStruct((bh, nq, 1, block_q),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, block_q),
                                      lambda b, i, j: (b, i, 0, 0)))
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // rep, j, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name=WIN_FWD_KERNEL_NAME if window else FWD_KERNEL_NAME,
    )(q, k, v)
    if not with_lse:
        return out[0]
    return out[0], out[1].reshape(bh, nq * block_q)[:, :t]


def _tile(n):
    """The largest of 512, 256, 128 that divides n."""
    return next(b for b in (512, 256, 128) if n % b == 0)


def _window_block(t, window):
    """The one block size of a windowed call, queries and keys alike: the
    largest of 512, 256, 128 that divides both T and the window, so that
    the window's lower edge and the diagonal each cross one key block a
    query block; None where the window is no multiple of 128."""
    if window % 128:
        return None
    return _tile(math.gcd(t, window))


def _fwd_blocks(t, s_len, d, itemsize, causal, block_q=0, block_k=0,
                window=0):
    """(block_q, block_k) of the walked forward, or None where the shape
    takes the grid kernel: the backward's predicate (`_bwd_blocks`), and a
    caller's own blocks only where they tile the shape and, under a causal
    mask, block_q is a multiple of block_k. Left to the shape, block_k is
    the largest of 512, 256, 128 that divides S, and block_q the same of T
    or, under a causal mask, the largest of 2048 down to 128 that divides
    T and fits: the key blocks the diagonal crosses are straight-line
    code, which a v5e runs faster than the loop (PERF.md section 6,
    PR 31). Under a window both are `_window_block`, whatever the caller's."""
    if t % 128 or s_len % 128 or (causal and t != s_len):
        return None
    if window:
        blk = _window_block(t, window)
        if blk is None or _walk_vmem(s_len, d, itemsize, blk,
                                     blk) > _HEAD_VMEM_BYTES:
            return None
        return blk, blk
    block_k = min(block_k, s_len) or _tile(s_len)
    if block_q:
        wide = (min(block_q, t),)
    else:
        wide = (2048, 1024) * causal + (512, 256, 128)
    for block_q in wide:
        if (block_q % 128 or block_k % 128 or t % block_q or s_len % block_k
                or (causal and block_q % block_k)):
            continue
        if _walk_vmem(s_len, d, itemsize, block_q,
                      block_k) <= _HEAD_VMEM_BYTES:
            return block_q, block_k
    return None


def _walk_vmem(s_len, d, itemsize, block_q, block_k):
    """Scoped VMEM the walked forward asks for: a third over what is
    resident, the head's k and v and a block of q and o, double-buffered
    (lanes padded to 128), the float32 accumulator, and four block-pair
    temporaries (scores, p and their casts); never under Mosaic's own
    16 MiB. Asking for more than it needs costs the program around it: XLA
    counts the request against what it may keep in VMEM between
    operations (the LM cell's forward alone, compiled for a described v5e:
    32.5 MiB of temporaries at 16 MiB, 64.5 at 40)."""
    lanes = -(-d // 128) * 128
    resident = (4 * (s_len + block_q) * lanes * itemsize
                + block_q * lanes * 4 + 4 * block_q * block_k * 4)
    return max(16 << 20, resident * 4 // 3)


def _grid_blocks(t, s_len, block_q, block_k):
    """The grid kernel's blocks: the caller's or 512 x 1024, clamped to the
    sequence lengths."""
    return min(block_q or 512, t), min(block_k or 1024, s_len)


def _live_share(t, s_len, block_q, block_k, causal, walked, window=0):
    """Scores a forward with these blocks computes, over all of them: under
    a causal mask the grid kernel takes every block pair the diagonal
    touches whole, the walked one a key block against the queries from the
    block's own first on; under a window neither visits a key block that
    lies before every query's window."""
    if not causal:
        return 1.0
    nq, nk = pl.cdiv(t, block_q), pl.cdiv(s_len, block_k)
    rows = sum(min(block_q, (i + 1) * block_q - j * block_k)
               if walked and not window else block_q
               for i in range(nq)
               for j in range(min(nk, pl.cdiv((i + 1) * block_q, block_k)))
               if not window or (j + 1) * block_k - 1 > i * block_q - window)
    return rows / (nq * block_q * nk)


def _walk_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, scale, causal,
                 block_q, block_k, window=0):
    """One (head, query block): the head's K and V are resident and the
    kernel walks the key blocks itself. Scores are held transposed,
    (keys, queries), as the backward holds them: the running maximum and
    sum are then rows, their reductions run down the sublanes and the
    log-sum-exp needs no turn. They and the accumulator (D, block_q) are the
    loop's own values; the output is normalized and turned once, at the
    end."""
    i = pl.program_id(1)
    q = q_ref[0]
    d = q.shape[-1]
    a_bt = (((1,), (1,)), ((), ()))   # a @ b.T
    at_b = (((0,), (0,)), ((), ()))   # a.T @ b

    def visit(carry, keys, queries, mask):
        """The running softmax of the rows `queries` of q over `keys`.
        `mask`: None, "diagonal" (the block's first key is the first
        query's own: a key at or before its query) or "edge" (the block's
        first key is the first query's last but `window`: a key after the
        query's place, so inside its window)."""
        m, l, acc = carry
        k_j, v_j = k_ref[0, keys, :], v_ref[0, keys, :]
        s_t = jax.lax.dot_general(
            k_j, queries, a_bt, preferred_element_type=jnp.float32) * scale
        if mask:
            # the offsets cancel in both
            key = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
            query = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
            seen = key <= query if mask == "diagonal" else key > query
            s_t = jnp.where(seen, s_t, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_t, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_t = jnp.exp(s_t - m_new)
        l = alpha * l + jnp.sum(p_t, axis=0, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            v_j, p_t.astype(v_j.dtype), at_b,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    def block(j):
        return pl.ds(pl.multiple_of(j * block_k, block_k), block_k)

    def below(j, carry):
        return visit(carry, block(j), q, None)

    carry = (jnp.full((1, block_q), NEG_INF, jnp.float32),
             jnp.zeros((1, block_q), jnp.float32),
             jnp.zeros((d, block_q), jnp.float32))
    if causal and window:
        # block_q == block_k and the window is `reach` of them: a query
        # block sees the key block `reach` before it through the window's
        # lower edge, the ones between whole, and its own through the
        # diagonal; nothing before the edge block is visited. A row the
        # edge block hides whole carries exp(0) weights until the
        # diagonal's alpha = exp(-1e30 - m) wipes them: its own key is
        # always seen, last
        reach = window // block_k
        carry = jax.lax.fori_loop(
            0, (i >= reach).astype(jnp.int32),
            lambda _, c: visit(c, block(i - reach), q, "edge"), carry)
        carry = jax.lax.fori_loop(jnp.maximum(i - reach + 1, 0), i, below,
                                  carry)
        m, l, acc = visit(carry, block(i), q, "diagonal")
    elif causal:
        # block_q is a multiple of block_k. Key blocks under the diagonal
        # take the scores as they are and those above it are never
        # visited. The ones the diagonal crosses are straight-line code,
        # each against the queries from its own first on, so what lies
        # above the diagonal inside the query block is not computed
        # either, but for each strip's own upper triangle
        strips = block_q // block_k
        m, l, acc = jax.lax.fori_loop(0, i * strips, below, carry)
        for lo in range(0, block_q, block_k):
            keys = pl.ds(pl.multiple_of(i * block_q + lo, block_k), block_k)
            part = visit((m[:, lo:], l[:, lo:], acc[:, lo:]), keys,
                         q[lo:, :], "diagonal")
            m, l, acc = part if lo == 0 else tuple(
                jnp.concatenate([a[:, :lo], b], axis=1)
                for a, b in zip((m, l, acc), part))
    else:
        m, l, acc = jax.lax.fori_loop(0, k_ref.shape[1] // block_k, below,
                                      carry)
    o_ref[0] = (acc * (1.0 / l)).T.astype(o_ref.dtype)
    if lse_ref:
        lse_ref[0][0, 0] = m + jnp.log(l)


def _walk_call(q, k, v, scale, causal, block_q, block_k, interpret,
               with_lse, window=0):
    bh, t, d = q.shape
    s_len = k.shape[1]
    rep = bh // k.shape[0]
    nq = t // block_q
    kernel = functools.partial(_walk_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               window=window)
    # a key/value head is fetched once for the `rep` query heads that read
    # it: consecutive grid steps name the same block
    kv_spec = pl.BlockSpec((1, s_len, d), lambda b, i: (b // rep, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, nq, 1, block_q),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, 1, block_q),
                                      lambda b, i: (b, i, 0, 0)))
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bh, nq),
        in_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                  kv_spec, kv_spec],
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_walk_vmem(s_len, d, q.dtype.itemsize, block_q,
                                        block_k)),
        interpret=interpret,
        name=WIN_FWD_KERNEL_NAME if window else FWD_KERNEL_NAME,
    )(q, k, v)
    if not with_lse:
        return out[0]
    return out[0], out[1].reshape(bh, t)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _forward(q, k, v, scale, causal, block_q, block_k, with_lse, window=0):
    """The forward for the platform this program is compiled for. Jitted
    so the choice follows the operands' device even when called eagerly
    (a CPU-committed operand on a chip host must not reach Mosaic)."""
    blocks = _fwd_blocks(q.shape[1], k.shape[1], q.shape[2],
                         q.dtype.itemsize, causal, block_q, block_k, window)

    def kernel(q, k, v, interpret):
        if blocks is not None:
            return _walk_call(q, k, v, scale, causal, *blocks,
                              interpret=interpret, with_lse=with_lse,
                              window=window)
        return _flash_call(q, k, v, scale, causal,
                           *_grid_blocks(q.shape[1], k.shape[1], block_q,
                                         block_k),
                           interpret=interpret, with_lse=with_lse,
                           window=window)

    def on_tpu(q, k, v):
        return kernel(q, k, v, False)

    def on_cpu(q, k, v):
        # interpret mode exercises the kernel logic on CPU for small
        # problems; big CPU shapes take the reference path
        if _interpretable(q, k):
            return kernel(q, k, v, True)
        out = _reference(q, k, v, scale, causal, window)
        if not with_lse:
            return out
        return out, jax.nn.logsumexp(_scores(q, k, scale, causal, window),
                                     axis=-1)

    return jax.lax.platform_dependent(q, k, v, tpu=on_tpu, cpu=on_cpu)


def _bwd_blocks(t, s_len, d, itemsize, causal, window=0, rep=1):
    """(block_q, block_k) of the backward kernel, or None where it does not
    tile. The largest of 512, 256, 128 that divides: fewer, longer loop
    iterations won on a v5e (PERF.md section 6, PR 27), and at T=1024 a
    causal mask still skips one block pair of four. Under a window,
    `_window_block`. `rep` query heads a key/value head add the group's
    float32 dK and dV to what is resident."""
    if t % 128 or s_len % 128 or (causal and t != s_len):
        return None
    block_q = _window_block(t, window) if window else _tile(t)
    if block_q is None:
        return None
    block_k = block_q if causal else _tile(s_len)
    # a whole head is resident: q, dO, dQ and k, v, dK, dV double-buffered
    # (lanes padded to 128), dQ's float32 accumulator, and eight
    # block-pair temporaries (scores, p, dP, dS and their casts)
    lanes = -(-d // 128) * 128
    resident = (2 * (3 * t + 4 * s_len) * lanes * itemsize
                + t * lanes * 4 + 8 * block_q * block_k * 4
                + (rep > 1) * 2 * s_len * lanes * 4)
    if resident > _HEAD_VMEM_BYTES * 3 // 4:
        return None
    return block_q, block_k


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale, causal, block_q, block_k, window=0, rep=1):
    """One query head. Scores are held transposed, (block_k, block_q): lse
    and delta then broadcast as rows, and dV = P^T dO and dK = dS^T Q are
    plain matmuls. lse_ref and delta_ref are (1, T / block_q, block_q).

    With `rep` > 1 query heads a key/value head the grid is (key/value
    heads, rep), the second axis in order: dk_acc and dv_acc hold the whole
    head (S, D) in float32, take every query head's share and are written
    out after the group's last; K and V are read where they lie, never
    repeated."""
    t = q_ref.shape[1]
    nq, nk = t // block_q, k_ref.shape[1] // block_k
    a_bt = (((1,), (1,)), ((), ()))   # a @ b.T
    at_b = (((0,), (0,)), ((), ()))   # a.T @ b
    dq_acc[...] = jnp.zeros_like(dq_acc)
    grouped = rep > 1
    if grouped:
        @pl.when(pl.program_id(1) == 0)
        def _first_of_group():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    def kv_block(j, _):
        cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k_j, v_j = k_ref[0, cols, :], v_ref[0, cols, :]
        # where dK and dV of this key block gather
        here = (cols, slice(None)) if grouped else (Ellipsis,)
        if not grouped:
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def pair(i, mask):
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            q_i, do_i = q_ref[0, rows, :], do_ref[0, rows, :]
            s_t = jax.lax.dot_general(
                k_j, q_i, a_bt, preferred_element_type=jnp.float32) * scale
            if mask:
                # block_q == block_k; on the diagonal i == j, on the
                # window's edge i == j + window / block_k: the offsets
                # cancel in both
                key = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
                query = jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
                seen = key <= query if mask == "diagonal" else key > query
                s_t = jnp.where(seen, s_t, NEG_INF)
            p_t = jnp.exp(s_t - lse_ref[0, pl.ds(i, 1), :])
            dv_acc[here] += jnp.dot(p_t.astype(do_i.dtype), do_i,
                                    preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(v_j, do_i, a_bt,
                                       preferred_element_type=jnp.float32)
            # dS less the softmax scale, which dQ and dK take once a block
            ds_t = (p_t * (dp_t - delta_ref[0, pl.ds(i, 1), :])
                    ).astype(q_i.dtype)
            dk_acc[here] += jnp.dot(ds_t, q_i,
                                    preferred_element_type=jnp.float32)
            dq_acc[rows, :] += jax.lax.dot_general(
                ds_t, k_j, at_b, preferred_element_type=jnp.float32)

        def below(i, _):
            pair(i, None)
            return 0

        if causal:
            # queries before this kv block see none of it: start on the
            # diagonal, the one block pair that needs the mask
            pair(j, "diagonal")
            if window:
                # and the queries a window on see none of it either: the
                # last that do look through the window's lower edge
                reach = window // block_k

                def edge(_, c):
                    pair(j + reach, "edge")
                    return c

                jax.lax.fori_loop(j + 1, jnp.minimum(j + reach, nq), below, 0)
                jax.lax.fori_loop(0, (j + reach < nq).astype(jnp.int32),
                                  edge, 0)
            else:
                jax.lax.fori_loop(j + 1, nq, below, 0)
        else:
            jax.lax.fori_loop(0, nq, below, 0)
        if not grouped:
            dk_ref[0, cols, :] = (dk_acc[...] * scale).astype(dk_ref.dtype)
            dv_ref[0, cols, :] = dv_acc[...].astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, nk, kv_block, 0)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)
    if grouped:
        @pl.when(pl.program_id(1) == rep - 1)
        def _last_of_group():
            dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
              interpret, window=0):
    bh, t, d = q.shape
    s_len = k.shape[1]
    rep = bh // k.shape[0]
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               window=window, rep=rep)
    if rep == 1:
        grid, semantics = (bh,), ("parallel",)

        def per_q(b):
            return (b, 0, 0)

        per_kv = per_q
    else:
        grid, semantics = (bh // rep, rep), ("parallel", "arbitrary")

        def per_q(g, r):
            return (g * rep + r, 0, 0)

        def per_kv(g, r):
            return (g, 0, 0)

    q_spec = pl.BlockSpec((1, t, d), per_q)
    kv_spec = pl.BlockSpec((1, s_len, d), per_kv)
    row_spec = pl.BlockSpec((1, t // block_q, block_q), per_q)
    rows = (bh, t // block_q, block_q)
    kv_rows = s_len if rep > 1 else block_k
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, kv_spec, kv_spec],
        scratch_shapes=[
            pltpu.VMEM((t, d), jnp.float32),
            pltpu.VMEM((kv_rows, d), jnp.float32),
            pltpu.VMEM((kv_rows, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_HEAD_VMEM_BYTES),
        interpret=interpret,
        name=WIN_BWD_KERNEL_NAME if window else BWD_KERNEL_NAME,
    )(q, k, v, do, lse.reshape(rows), delta.reshape(rows))


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _backward(q, k, v, o, lse, g, scale, causal, blocks, window=0):
    """(dq, dk, dv) by the backward kernel, for the platform this program
    is compiled for (as `_forward`)."""
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1)

    def on_tpu(q, k, v, g, lse, delta):
        return tuple(_bwd_call(q, k, v, g, lse, delta, scale, causal,
                               *blocks, interpret=False, window=window))

    def on_cpu(q, k, v, g, lse, delta):
        if _interpretable(q, k):
            return tuple(_bwd_call(q, k, v, g, lse, delta, scale, causal,
                                   *blocks, interpret=True, window=window))
        return _reference_vjp(q, k, v, g, scale, causal, window)

    return jax.lax.platform_dependent(q, k, v, g, lse, delta,
                                      tpu=on_tpu, cpu=on_cpu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3(q, k, v, scale, causal, block_q, block_k, window=0):
    return _forward(q, k, v, scale, causal, block_q, block_k, False, window)


def _bwd_blocks_of(q, k, causal, window):
    return _bwd_blocks(q.shape[1], k.shape[1], q.shape[2], q.dtype.itemsize,
                       causal, window, q.shape[0] // k.shape[0])


def _flash3_fwd(q, k, v, scale, causal, block_q, block_k, window):
    walked = _fwd_blocks(q.shape[1], k.shape[1], q.shape[2],
                         q.dtype.itemsize, causal, block_q, block_k, window)
    telemetry.counter(
        "attention_fwd_builds",
        labels={"path": "grid" if walked is None
                else "walk_window" if window else "walk"},
        help="differentiated attention forward passes traced, by the kernel "
             "their shape takes").inc()
    if _bwd_blocks_of(q, k, causal, window) is None:
        out = _forward(q, k, v, scale, causal, block_q, block_k, False,
                       window)
        return out, (q, k, v)
    out, lse = _forward(q, k, v, scale, causal, block_q, block_k, True,
                        window)
    return out, (q, k, v, out, lse)


def _flash3_bwd(scale, causal, block_q, block_k, window, res, g):
    q, k, v = res[:3]
    blocks = _bwd_blocks_of(q, k, causal, window)
    telemetry.counter(
        "attention_bwd_builds",
        labels={"path": "reference" if blocks is None
                else "kernel_window" if window else "kernel"},
        help="attention backward passes traced, by the path their shape "
             "takes").inc()
    if blocks is None:
        return _reference_vjp(q, k, v, g, scale, causal, window)
    return _backward(*res, g, scale, causal, blocks, window)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=0,
                    block_k=0, window=0):
    """Multi-head attention: q (B, H, T, D) over k, v (B, G, S, D), G
    dividing H: query head j reads key/value head j // (H / G) (B and the
    heads merged internally; K and V are never repeated). `block_q` /
    `block_k` are the forward's tiles; 0 leaves them to the shape.
    `window` > 0, under a causal mask, lets a query see the `window` keys
    that end in its own."""
    b, h, t, d = q.shape
    groups, s_len = k.shape[1], k.shape[2]
    if h % groups or v.shape[1] != groups:
        raise ValueError("flash_attention: %d query heads over %d key and "
                         "%d value heads" % (h, groups, v.shape[1]))
    scale = float(sm_scale) if sm_scale is not None else 1.0 / (d ** 0.5)
    causal, block_q, block_k = bool(causal), int(block_q), int(block_k)
    window = int(window)
    if window and not causal:
        raise ValueError("flash_attention: a window needs the causal mask")
    if window >= s_len:
        window = 0      # every key at or before a query is within it
    walked = _fwd_blocks(t, s_len, d, q.dtype.itemsize, causal, block_q,
                         block_k, window)
    bq, bk = walked or _grid_blocks(t, s_len, block_q, block_k)
    telemetry.gauge("flash_fwd_block_q", help="query rows a block of the "
                    "flash forward (the last call traced)").set(bq)
    telemetry.gauge("flash_fwd_block_k", help="keys a block of the flash "
                    "forward (the last call traced)").set(bk)
    live = _live_share(t, s_len, bq, bk, causal, walked is not None, window)
    telemetry.gauge(
        "flash_win_live_block_share" if window
        else "flash_fwd_live_block_share",
        help="scores the flash forward %scomputes over all T x S of them "
             "(the last call traced)" % ("under a window " * bool(window))
    ).set(live)
    telemetry.gauge("attention_window", help="keys a query sees under the "
                    "causal mask, 0 for all (the last call traced)"
                    ).set(window)
    telemetry.gauge("attention_kv_groups", help="key/value heads of the "
                    "last attention call traced (its query heads share "
                    "them)").set(groups)
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * groups, s_len, d)
    vf = v.reshape(b * groups, s_len, d)
    out = _flash3(qf, kf, vf, scale, causal, block_q, block_k, window)
    return out.reshape(b, h, t, d)


def _flash_op(a, q, k, v):
    return flash_attention(q, k, v, causal=a.causal,
                           sm_scale=(a.sm_scale if a.sm_scale != 0.0
                                     else None),
                           block_q=a.block_q, block_k=a.block_k,
                           window=a.window)


register("_contrib_FlashAttention", _flash_op,
         arg_names=["query", "key", "value"],
         attrs={"causal": False, "sm_scale": 0.0, "block_q": 0,
                "block_k": 0, "window": 0},
         aliases=("flash_attention",))
