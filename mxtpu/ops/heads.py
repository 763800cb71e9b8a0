"""What lies between a projection's matmul and a flash kernel, and between a
flash kernel and the output projection, as two operators with their own
gradients: every tensor crosses HBM once each way in its own dtype, the
float32 arithmetic stays in VMEM.

``_contrib_HeadNormRotary(x, gamma)``: x (B, T, n dh), a projection's result,
becomes (B, n, T, dh), the layout ``_contrib_FlashAttention`` reads: each
head's dh dims normed by their root mean square (``eps``, times the one
learned vector ``gamma`` (dh)), then turned by the position's angles over the
first ``rotary_dims`` dims, halves paired (i, i + r/2), as
``_contrib_RotaryEmbedding`` defines the table (``rope_type="none"``: no
turn). The head transpose is the blocks' index maps. The turn is

    y = z C + roll(z, dh - r/2) S_a + roll(z, r/2) S_b

with C = [cos, cos, 1], S_a = [-sin, 0, 0], S_b = [0, sin, 0] over the lanes
of a head (one roll and S_a + S_b where r = dh): no slice, no concatenate.
Its gradient (``jax.custom_vjp``) keeps x alone, recomputes the inverse root
mean square, turns dY back by the same form with the sines negated (the
transpose) and returns dX (B, T, n dh) and gamma's gradient as float32
partial sums a block, added up outside.

``_contrib_HeadGate(att, g)``: att (B, H, T, dh), the kernel's output, and the
gate's logits g (B, T, H) become (B, T, H dh) = att * sigmoid(g), again
transposed by the index maps. Its gradient reads dOut, att (the buffer the
flash backward keeps anyway) and g, and returns dAtt (B, H, T, dh) and dg
(B, T, H), a float32 row sum over dh.

Mean square, scaling, rotation, sigmoid and products are float32 whatever
the operands are; each result is rounded once.

Which program runs follows the shape and the platform the program is lowered
for (``lax.platform_dependent``), as in attention.py: on ``tpu`` the Mosaic
kernels (``mxtpu_head_prep_fwd`` / ``_bwd``, ``mxtpu_head_gate_fwd`` /
``_bwd``) where the shape tiles (T a multiple of 128 rows, dh of 128 lanes),
the plain ``jax.numpy`` bodies below elsewhere and on ``cpu``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .registry import Required, register
from .rotary import _tables, inv_freq

PREP_FWD_KERNEL_NAME = "mxtpu_head_prep_fwd"
PREP_BWD_KERNEL_NAME = "mxtpu_head_prep_bwd"
GATE_FWD_KERNEL_NAME = "mxtpu_head_gate_fwd"
GATE_BWD_KERNEL_NAME = "mxtpu_head_gate_bwd"

F32 = jnp.float32


def _lowered(tiles, kernel, plain, *args):
    """`kernel(*args)` where the shape tiles and the program is lowered for
    the TPU, `plain(*args)` elsewhere."""
    if tiles is None:
        return plain(*args)
    return lax.platform_dependent(*args, tpu=kernel, cpu=plain)


def _vmem(resident):
    """Scoped VMEM a call asks for: what is resident and a third, never
    under Mosaic's own 16 MiB (attention.py `_walk_vmem`: a request counts
    against what XLA keeps in VMEM between operations)."""
    return max(16 << 20, resident * 4 // 3)


# ------------------------------------------------------------ the rotation


def _shifts(dh, half):
    """The lane rolls of the turn: one where the halves fill the head."""
    if not half:
        return ()
    return (half,) if 2 * half == dh else (dh - half, half)


def _rope_freqs(dh, rotary_dims=0, rope_type="default", theta=10000.0,
                factor=1.0, original_max_position=0, beta_fast=32.0,
                beta_slow=1.0):
    """The inverse frequencies of ``_contrib_RotaryEmbedding``'s attributes
    as a hashable tuple, r/2 of them (`rotary_dims` 0: r = dh); none under
    `rope_type` ``"none"``."""
    if rope_type == "none":
        return ()
    r = int(rotary_dims) or dh
    if r > dh:
        raise ValueError("head_norm_rotary: %d rotary dims of a head of %d"
                         % (r, dh))
    return tuple(float(f) for f in inv_freq(
        r, rope_type, theta, factor, original_max_position, beta_fast,
        beta_slow))


def _turn_tables(freqs, t, dh, scale):
    """(C, S...), each (T, dh) float32: the turn of `_shifts` as lane-wise
    factors, one S a roll. Negate the S for the transpose."""
    half = len(freqs)
    if not half:
        return ()
    cos, sin = _tables(freqs, t, scale)
    rest = jnp.ones((t, dh - 2 * half), F32)
    zero = jnp.zeros_like
    c = jnp.concatenate([cos, cos, rest], axis=1)
    s_a = jnp.concatenate([-sin, zero(sin), zero(rest)], axis=1)
    s_b = jnp.concatenate([zero(sin), sin, zero(rest)], axis=1)
    return (c, s_a + s_b) if 2 * half == dh else (c, s_a, s_b)


def _turn(z, tables, shifts, roll):
    """z (..., dh) float32 turned; `tables` broadcast against it."""
    if not tables:
        return z
    out = z * tables[0]
    for s, shift in zip(tables[1:], shifts):
        out = out + roll(z, shift, z.ndim - 1) * s
    return out


def _back(tables):
    return tables[:1] + tuple(-s for s in tables[1:])


# ------------------------------------------------- head preparation, plain


def _prep_plain(x, gamma, tables, n, eps, shifts):
    b, t, _ = x.shape
    dh = gamma.shape[-1]
    xf = x.astype(F32).reshape(b, t, n, dh)
    z = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * gamma
    y = _turn(z, tuple(tb[:, None, :] for tb in tables), shifts, jnp.roll)
    return y.transpose(0, 2, 1, 3).astype(x.dtype)


def _prep_bwd_plain(dy, x, gamma, tables, n, eps, shifts):
    """(dx, dgamma (1, dh) float32); `tables` already the transpose's."""
    b, t, _ = x.shape
    dh = gamma.shape[-1]
    dz = _turn(dy.astype(F32), tables, shifts, jnp.roll).transpose(0, 2, 1, 3)
    xf = x.astype(F32).reshape(b, t, n, dh)
    rstd = lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    xh = xf * rstd
    dxh = dz * gamma
    dx = rstd * (dxh - xh * jnp.mean(dxh * xh, -1, keepdims=True))
    return (dx.reshape(x.shape).astype(x.dtype),
            jnp.sum(dz * xh, axis=(0, 1, 2)).reshape(1, dh))


# ----------------------------------------------- head preparation, kernels


def _prep_blocks(t, n, dh, itemsize):
    """(rows, heads) of a grid step, or None where the shape does not tile:
    a head's dims are the lanes of a block and the rows its sublanes."""
    if t % 128 or dh % 128:
        return None
    most = max(128, 1024 // itemsize)
    rows = next(r for r in (512, 256, 128) if r <= most and t % r == 0)
    return rows, next(h for h in (8, 4, 2, 1) if n % h == 0)


def _head_lanes(j, dh):
    """Head j's lanes of a (rows, heads * dh) block, j a loop index. The
    kernels loop over a step's heads instead of unrolling them: unrolled
    (8 heads a step here, all 72 in the gate) they ran 0.2% of the Laguna
    step faster and cost 6 s of every set-up in tracing (PERF.md section 6,
    PR 33)."""
    return pl.ds(pl.multiple_of(j * dh, dh), dh)


def _prep_fwd_kernel(x_ref, gamma_ref, *refs, heads, dh, eps, shifts):
    table_refs, o_ref = refs[:-1], refs[-1]

    def head(j, carry):
        tables = tuple(r[...] for r in table_refs)
        x = x_ref[0, :, _head_lanes(j, dh)].astype(F32)
        ms = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / dh)
        z = x * lax.rsqrt(ms + eps) * gamma_ref[...]
        o_ref[0, j] = _turn(z, tables, shifts, pltpu.roll).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, heads, head, 0)


def _prep_bwd_kernel(dy_ref, x_ref, gamma_ref, *refs, heads, dh, eps, shifts):
    table_refs = refs[:-2]
    dx_ref, dgamma_ref = refs[-2:]
    rows = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _first_heads():
        dgamma_ref[...] = jnp.zeros_like(dgamma_ref)

    def head(j, dgamma):
        tables = tuple(r[...] for r in table_refs)
        gamma = gamma_ref[...]
        lanes = _head_lanes(j, dh)
        dz = _turn(dy_ref[0, j].astype(F32), tables, shifts, pltpu.roll)
        x = x_ref[0, :, lanes].astype(F32)
        ms = jnp.sum(x * x, axis=-1, keepdims=True) * (1.0 / dh)
        rstd = lax.rsqrt(ms + eps)
        xh = x * rstd
        dxh = dz * gamma
        mean = jnp.sum(dxh * xh, axis=-1, keepdims=True) * (1.0 / dh)
        dx_ref[0, :, lanes] = (rstd * (dxh - xh * mean)).astype(dx_ref.dtype)
        # eight sublanes' partial sums: the rest of the sum is XLA's
        return dgamma + jnp.sum((dz * xh).reshape(rows // 8, 8, dh), axis=0)

    dgamma_ref[0, 0] += lax.fori_loop(0, heads, head,
                                      jnp.zeros((8, dh), F32))


def _table_specs(tables, rows, dh):
    # the same block for every group of heads: fetched once a row block
    return [pl.BlockSpec((rows, dh), lambda b, i, h: (i, 0)) for _ in tables]


def _prep_fwd_call(x, gamma, tables, n, eps, shifts, blocks, interpret=False):
    b, t, _ = x.shape
    dh = gamma.shape[-1]
    rows, heads = blocks
    kernel = functools.partial(_prep_fwd_kernel, heads=heads, dh=dh, eps=eps,
                               shifts=shifts)
    block = rows * heads * dh
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n, t, dh), x.dtype),
        grid=(b, t // rows, n // heads),
        in_specs=[pl.BlockSpec((1, rows, heads * dh),
                               lambda b, i, h: (b, i, h)),
                  pl.BlockSpec((1, dh), lambda b, i, h: (0, 0))]
        + _table_specs(tables, rows, dh),
        out_specs=pl.BlockSpec((1, heads, rows, dh),
                               lambda b, i, h: (b, h, i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_vmem(
                4 * block * x.dtype.itemsize
                + (2 * len(tables) + 4) * rows * dh * 4)),
        interpret=interpret,
        name=PREP_FWD_KERNEL_NAME,
    )(x, gamma, *tables)


def _prep_bwd_call(dy, x, gamma, tables, n, eps, shifts, blocks,
                   interpret=False):
    b, t, _ = x.shape
    dh = gamma.shape[-1]
    rows, heads = blocks
    kernel = functools.partial(_prep_bwd_kernel, heads=heads, dh=dh, eps=eps,
                               shifts=shifts)
    flat = pl.BlockSpec((1, rows, heads * dh), lambda b, i, h: (b, i, h))
    block = rows * heads * dh
    dx, dgamma = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, t // rows, 8, dh), F32)],
        grid=(b, t // rows, n // heads),
        in_specs=[pl.BlockSpec((1, heads, rows, dh),
                               lambda b, i, h: (b, h, i, 0)),
                  flat,
                  pl.BlockSpec((1, dh), lambda b, i, h: (0, 0))]
        + _table_specs(tables, rows, dh),
        out_specs=[flat,
                   # resident over a row block's groups of heads
                   pl.BlockSpec((1, 1, 8, dh), lambda b, i, h: (b, i, 0, 0))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem(
                6 * block * x.dtype.itemsize
                + (2 * len(tables) + 6) * rows * dh * 4)),
        interpret=interpret,
        name=PREP_BWD_KERNEL_NAME,
    )(dy, x, gamma, *tables)
    return dx, jnp.sum(dgamma, axis=(0, 1, 2)).reshape(1, dh)


# static: heads, eps, the inverse frequencies and the table's scale
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _prep_forward(x, gamma, n, eps, freqs, scale):
    b, t, width = x.shape
    dh = width // n
    shifts = _shifts(dh, len(freqs))
    tables = _turn_tables(freqs, t, dh, scale)
    gamma = gamma.astype(F32).reshape(1, dh)
    blocks = _prep_blocks(t, n, dh, x.dtype.itemsize)

    def plain(x, gamma, *tables):
        return _prep_plain(x, gamma, tables, n, eps, shifts)

    def kernel(x, gamma, *tables):
        return _prep_fwd_call(x, gamma, tables, n, eps, shifts, blocks)

    return _lowered(blocks, kernel, plain, x, gamma, *tables)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _prep_backward(dy, x, gamma, n, eps, freqs, scale):
    b, t, width = x.shape
    dh = width // n
    shifts = _shifts(dh, len(freqs))
    tables = _back(_turn_tables(freqs, t, dh, scale))
    gamma32 = gamma.astype(F32).reshape(1, dh)
    blocks = _prep_blocks(t, n, dh, x.dtype.itemsize)

    def plain(dy, x, gamma, *tables):
        return _prep_bwd_plain(dy, x, gamma, tables, n, eps, shifts)

    def kernel(dy, x, gamma, *tables):
        return _prep_bwd_call(dy, x, gamma, tables, n, eps, shifts, blocks)

    dx, dgamma = _lowered(blocks, kernel, plain, dy, x, gamma32, *tables)
    return dx, dgamma.reshape(gamma.shape).astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _prep(x, gamma, n, eps, freqs, scale):
    return _prep_forward(x, gamma, n, eps, freqs, scale)


def _path(blocks):
    return "composed" if blocks is None else "fused"


def _prep_vjp_fwd(x, gamma, n, eps, freqs, scale):
    blocks = _prep_blocks(x.shape[1], n, x.shape[2] // n, x.dtype.itemsize)
    telemetry.counter(
        "attention_prep_builds", labels={"path": _path(blocks)},
        help="differentiated head preparations (norm, rotation, head "
             "transpose of a q or a k) traced, by whether their shape takes "
             "the one-pass kernel on the chip").inc()
    telemetry.gauge(
        "attention_prep_saved_bytes",
        help="bytes one differentiated head preparation keeps for its "
             "backward: the projection it read (the last call traced)"
    ).set(x.size * x.dtype.itemsize)
    return _prep_forward(x, gamma, n, eps, freqs, scale), (x, gamma)


def _prep_vjp_bwd(n, eps, freqs, scale, res, dy):
    return _prep_backward(dy, *res, n, eps, freqs, scale)


_prep.defvjp(_prep_vjp_fwd, _prep_vjp_bwd)


def head_norm_rotary(x, gamma, num_heads, eps=1e-6, rotary_dims=0,
                     rope_type="default", theta=10000.0, factor=1.0,
                     original_max_position=0, beta_fast=32.0, beta_slow=1.0,
                     scale=1.0):
    """x (B, T, n dh) as (B, n, T, dh): each head normed over its dims by
    `gamma` (dh) and `eps`, its first `rotary_dims` dims (0: all) turned by
    their positions as ``_contrib_RotaryEmbedding`` turns them
    (`rope_type` ``"none"``: not turned)."""
    n = int(num_heads)
    if x.ndim != 3 or x.shape[2] % n:
        raise ValueError("head_norm_rotary: %r is not (B, T, %d heads)"
                         % (x.shape, n))
    freqs = _rope_freqs(x.shape[2] // n, rotary_dims, rope_type, theta,
                        factor, original_max_position, beta_fast, beta_slow)
    return _prep(x, gamma, n, float(eps), freqs, float(scale))


def _prep_op(a, x, gamma):
    return head_norm_rotary(
        x, gamma, a.num_heads, a.eps, a.rotary_dims, a.rope_type, a.theta,
        a.factor, a.original_max_position, a.beta_fast, a.beta_slow, a.scale)


def _prep_args(a, shapes):
    return [shapes[0], (shapes[0][-1] // int(a.num_heads),)]


register("_contrib_HeadNormRotary", _prep_op, arg_names=["data", "gamma"],
         attrs={"num_heads": Required(int), "eps": 1e-6, "rotary_dims": 0,
                "rope_type": "default", "theta": 10000.0, "factor": 1.0,
                "original_max_position": 0, "beta_fast": 32.0,
                "beta_slow": 1.0, "scale": 1.0},
         infer_args=_prep_args, aliases=("head_norm_rotary",))


# ------------------------------------------------------------ the head gate


def _gate_plain(att, g):
    b, h, t, dh = att.shape
    sig = jax.nn.sigmoid(g.astype(F32))[..., None]
    out = att.astype(F32).transpose(0, 2, 1, 3) * sig
    return out.reshape(b, t, h * dh).astype(att.dtype)


def _gate_bwd_plain(do, att, g):
    """(datt, dg float32)."""
    b, h, t, dh = att.shape
    sig = jax.nn.sigmoid(g.astype(F32))
    do = do.astype(F32).reshape(b, t, h, dh)
    datt = (do * sig[..., None]).transpose(0, 2, 1, 3).astype(att.dtype)
    rows = jnp.sum(do * att.astype(F32).transpose(0, 2, 1, 3), axis=-1)
    return datt, rows * sig * (1.0 - sig)


def _gate_rows(t, h, dh, itemsize):
    """Rows of a grid step, every head of them, or None where the shape does
    not tile: the largest of 512, 256, 128 that divides T and keeps the
    backward's three double-buffered blocks under 15 MiB (on the chip the
    forward read the same at 128 and 256 rows: PERF.md section 6, PR 33)."""
    if t % 128 or dh % 128:
        return None
    return next(r for r in (512, 256, 128) if t % r == 0 and (
        6 * r * h * dh * itemsize <= 15 << 20 or r == 128))


def _gate_column(sig, head, j):
    """Column j of sig (rows, H) as (rows, 1), j a loop index: a masked sum
    over the lanes."""
    return jnp.sum(jnp.where(head == j, sig, 0.0), axis=-1, keepdims=True)


def _gate_fwd_kernel(att_ref, g_ref, o_ref, *, heads, dh):
    sig = jax.nn.sigmoid(g_ref[0].astype(F32))
    head = lax.broadcasted_iota(jnp.int32, sig.shape, 1)

    def one(j, carry):
        o_ref[0, :, _head_lanes(j, dh)] = (
            att_ref[0, j].astype(F32) * _gate_column(sig, head, j)
        ).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, heads, one, 0)


def _gate_bwd_kernel(do_ref, att_ref, g_ref, datt_ref, dg_ref, *, heads, dh):
    sig = jax.nn.sigmoid(g_ref[0].astype(F32))
    head = lax.broadcasted_iota(jnp.int32, sig.shape, 1)

    def one(j, dg):
        do = do_ref[0, :, _head_lanes(j, dh)].astype(F32)
        datt_ref[0, j] = (do * _gate_column(sig, head, j)
                          ).astype(datt_ref.dtype)
        row = jnp.sum(do * att_ref[0, j].astype(F32), axis=-1, keepdims=True)
        return jnp.where(head == j, row, dg)

    dg = lax.fori_loop(0, heads, one, jnp.zeros_like(sig))
    dg_ref[0] = dg * sig * (1.0 - sig)


def _gate_specs(h, rows, dh):
    return (pl.BlockSpec((1, h, rows, dh), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, rows, h * dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, rows, h), lambda b, i: (b, i, 0)))


def _gate_fwd_call(att, g, rows, interpret=False):
    b, h, t, dh = att.shape
    by_head, flat, per_head = _gate_specs(h, rows, dh)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, heads=h, dh=dh),
        out_shape=jax.ShapeDtypeStruct((b, t, h * dh), att.dtype),
        grid=(b, t // rows),
        in_specs=[by_head, per_head],
        out_specs=flat,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem(
                4 * rows * h * dh * att.dtype.itemsize + 4 * rows * dh * 4)),
        interpret=interpret,
        name=GATE_FWD_KERNEL_NAME,
    )(att, g)


def _gate_bwd_call(do, att, g, rows, interpret=False):
    b, h, t, dh = att.shape
    by_head, flat, per_head = _gate_specs(h, rows, dh)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, heads=h, dh=dh),
        out_shape=[jax.ShapeDtypeStruct(att.shape, att.dtype),
                   jax.ShapeDtypeStruct(g.shape, F32)],
        grid=(b, t // rows),
        in_specs=[flat, by_head, per_head],
        out_specs=[by_head, per_head],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem(
                6 * rows * h * dh * att.dtype.itemsize + 6 * rows * dh * 4)),
        interpret=interpret,
        name=GATE_BWD_KERNEL_NAME,
    )(do, att, g)


@jax.jit
def _gate_forward(att, g):
    b, h, t, dh = att.shape
    rows = _gate_rows(t, h, dh, att.dtype.itemsize)
    return _lowered(rows, lambda att, g: _gate_fwd_call(att, g, rows),
                    _gate_plain, att, g)


@jax.jit
def _gate_backward(do, att, g):
    b, h, t, dh = att.shape
    rows = _gate_rows(t, h, dh, att.dtype.itemsize)
    datt, dg = _lowered(
        rows, lambda do, att, g: tuple(_gate_bwd_call(do, att, g, rows)),
        _gate_bwd_plain, do, att, g)
    return datt, dg.astype(g.dtype)


@jax.custom_vjp
def _gate(att, g):
    return _gate_forward(att, g)


def _gate_vjp_fwd(att, g):
    b, h, t, dh = att.shape
    telemetry.counter(
        "attention_gate_builds",
        labels={"path": _path(_gate_rows(t, h, dh, att.dtype.itemsize))},
        help="differentiated head gates traced, by whether their shape "
             "takes the one-pass kernel on the chip").inc()
    return _gate_forward(att, g), (att, g)


def _gate_vjp_bwd(res, do):
    return _gate_backward(do, *res)


_gate.defvjp(_gate_vjp_fwd, _gate_vjp_bwd)


def head_gate(att, g):
    """att (B, H, T, dh) times sigmoid(g), g (B, T, H) a head's logit a
    token, as (B, T, H dh): what the output projection reads."""
    if att.ndim != 4 or g.shape != (att.shape[0], att.shape[2], att.shape[1]):
        raise ValueError("head_gate: %r gated by %r" % (att.shape, g.shape))
    return _gate(att, g)


def _gate_op(a, att, g):
    return head_gate(att, g)


register("_contrib_HeadGate", _gate_op, arg_names=["data", "gate"],
         aliases=("head_gate",), doc=head_gate.__doc__)
