"""What lies between a recurrent mixer's in-projection and its recurrence
kernel, and between the kernel and the out-projection, as two operators with
their own gradients: every (B, T, C) tensor crosses HBM once each way in its
own dtype, the float32 arithmetic stays in VMEM.

``short_conv(x, w, bias, k, act)``: the causal depthwise convolution of
``_contrib_CausalConv1D`` with its activation, y = act(sum_i w[:, i]
x[t - (k-1) + i] + bias), float32 inside, rounded once. The forward is the
plain ``jax.numpy`` body, K shifted multiply-adds that XLA runs in one pass.
Its gradient (``jax.custom_vjp``) keeps x, w and bias alone: the
pre-activation is K multiply-adds to recompute, dP = dY act'(p), dX[t] =
sum_i w[:, i] dP[t + (k-1) - i] in x's dtype, and the weight's and the
bias's gradients are float32 partial sums a block, added up outside. A time
block needs the k-1 rows before it (the taps of its first rows) and the dP
of the k-1 rows after it (whose taps are its own last rows and the k-1 rows
after): three blocks of 16 rows beside the three (rows, lanes) ones; before
a batch row's start and after its end there is nothing.

``gated_norm(x, gamma, gate, eps, groups, gate_first)``: the output norm of
the mixers, the mean square taken over each of ``groups`` equal groups of
the last axis. ``gate_first`` False is norm(x) * gamma * silu(gate) (Gated
DeltaNet); True is norm(x * silu(gate)) * gamma, the gate inside the mean
square, as Mamba-2 defines it. Forward one pass (x, gate in; out), backward
one pass from dOut, x and gate (the inverse root mean square recomputed),
dX and dGate in their dtypes, gamma's gradient float32 partial sums a block.
A group narrower than a lane tile's multiple shares a block with its
neighbour (two heads of 192 are three tiles) and the sums are masked; an
array whose last axis is one such group folds the axis before it in.

Which program runs follows the shape and the platform the program is lowered
for (``lax.platform_dependent``), as in heads.py: on ``tpu`` the Mosaic
kernels (``mxtpu_short_conv_bwd``, ``mxtpu_gated_norm_fwd`` / ``_bwd``)
where the shape tiles (T, or the rows of the norm, a multiple of 128;
channels a multiple of 128 lanes; a group, or two, whole lane tiles), the
plain bodies below elsewhere and on ``cpu``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .heads import _lowered, _path, _vmem

CONV_BWD_KERNEL_NAME = "mxtpu_short_conv_bwd"
NORM_FWD_KERNEL_NAME = "mxtpu_gated_norm_fwd"
NORM_BWD_KERNEL_NAME = "mxtpu_gated_norm_bwd"

F32 = jnp.float32
ACTS = ("none", "silu")
_HALO = 16      # rows of a neighbour a block sees: one bfloat16 tile
_LANES = 128
# rows a trip of a kernel's walk over its block: the compiler's bundles a row
# fall as the slab grows, until the spills take over
_CONV_SLAB = 128
_NORM_SLAB = 32


def _silu_grad(p, sigmoid=jax.nn.sigmoid):
    sig = sigmoid(p)
    return sig * (1.0 + p * (1.0 - sig))


def _sigmoid(v):
    """The logistic inside a kernel: one transcendental and three
    operations, where 1 / (1 + exp(-v)) brings an exact division's dozen."""
    return 0.5 * jnp.tanh(0.5 * v) + 0.5


def _row_blocks(t, bytes_a_row, most):
    """Rows of a grid step: the largest of 1024, 512, 256, 128 that divides
    `t` and keeps the step's double-buffered blocks under `most` bytes."""
    if t % 128:
        return None
    return next(r for r in (1024, 512, 256, 128)
                if t % r == 0 and (2 * r * bytes_a_row <= most or r == 128))


# ------------------------------------------------ short convolution, plain


def _conv_taps(x, k):
    """[x[t - (k-1) + i] for i < k], float32, nothing before the row's
    start."""
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return [xp[:, i:i + t, :].astype(F32) for i in range(k)]


def _conv_plain(x, w, bias, k, act):
    w32 = w.astype(F32)
    out = sum(tap * w32[:, i] for i, tap in enumerate(_conv_taps(x, k)))
    if bias is not None:
        out = out + bias.astype(F32)
    if act == "silu":
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


def _conv_bwd_plain(dy, x, w, bias, k, act):
    """(dx, dw (C, k) float32, dbias (C,) float32)."""
    t = x.shape[1]
    w32 = w.astype(F32)
    taps = _conv_taps(x, k)
    dp = dy.astype(F32)
    if act == "silu":
        dp = dp * _silu_grad(sum(tap * w32[:, i] for i, tap in enumerate(taps))
                             + (0.0 if bias is None else bias.astype(F32)))
    after = jnp.pad(dp, ((0, 0), (0, k - 1), (0, 0)))
    dx = sum(after[:, k - 1 - i:k - 1 - i + t, :] * w32[:, i]
             for i in range(k))
    dw = jnp.stack([jnp.sum(dp * tap, axis=(0, 1)) for tap in taps], axis=1)
    return dx.astype(x.dtype), dw, jnp.sum(dp, axis=(0, 1))


# ----------------------------------------------- short convolution, kernel


def _conv_blocks(t, c, k, itemsize):
    """(rows, lanes) of a grid step, or None where the shape does not tile:
    whole lane tiles of channels in blocks of up to four, or whole rows of
    channels that end in half a tile (2,880 are 22.5); T in blocks of 128
    rows or more; the k-1 rows of a neighbour inside one sublane tile."""
    if c % (_LANES // 2) or k - 1 > 8:
        return None
    lanes = next((w for w in (512, 384, 256, 128) if c % w == 0), c)
    rows = _row_blocks(t, 3 * lanes * itemsize, 12 << 20)
    return None if rows is None else (rows, lanes)


def _down(before, cur, s):
    """cur's rows moved down by s, the first s the last of `before` (8
    rows): cur[t - s]."""
    if not s:
        return cur
    return pltpu.roll(jnp.concatenate([before, cur], axis=0), s, 0)[8:]


def _up(cur, after, s):
    """cur[t + s], the last s rows the first of `after` (8 rows)."""
    if not s:
        return cur
    both = jnp.concatenate([cur, after], axis=0)
    return pltpu.roll(both, both.shape[0] - s, 0)[:cur.shape[0]]


def _fold(v):
    """(rows, lanes) -> eight sublanes' partial sums: the rest is XLA's."""
    return jnp.sum(v.reshape(v.shape[0] // 8, 8, v.shape[1]), axis=0)


def _conv_bwd_kernel(dy_ref, x_ref, x_before_ref, x_after_ref, dy_after_ref,
                     w_ref, bias_ref, dx_ref, dwb_ref, *, k, act):
    rows, width = x_ref.shape[1], x_ref.shape[2]
    step = pl.program_id(1)
    first, last = step == 0, step == pl.num_programs(1) - 1
    slab, slabs = _CONV_SLAB, rows // _CONV_SLAB

    def dpre(before, cur, dy, w, bias):
        """(dP, taps) of `cur`'s rows."""
        taps = [_down(before, cur, k - 1 - i) for i in range(k)]
        if act == "none":
            return dy, taps
        p = bias + sum(w[i] * taps[i] for i in range(k))
        return dy * _silu_grad(p, _sigmoid), taps

    def tile(lanes):
        """The block's columns `lanes` (a lane tile, or what is left of the
        channels behind the last whole one)."""
        def f32(ref, start, n):
            return ref[0, pl.ds(start, n), lanes].astype(F32)

        w = [w_ref[pl.ds(i, 1), lanes] for i in range(k)]
        bias = bias_ref[:, lanes]
        before = jnp.where(first, 0.0, f32(x_before_ref, 0, _HALO)[8:])
        dp_after, _ = dpre(f32(x_ref, rows - _HALO, _HALO)[8:],
                           f32(x_after_ref, 0, _HALO)[:8],
                           f32(dy_after_ref, 0, _HALO)[:8], w, bias)
        dp_after = jnp.where(last, 0.0, dp_after)

        def one(n, state):
            # from the block's last rows to its first: a slab's dX needs
            # the dP of the slab after it, which is carried
            dp_after, sums = state
            s = slabs - 1 - n
            start = pl.multiple_of(s * slab, slab)
            cur = f32(x_ref, start, slab)
            over = f32(x_ref, pl.multiple_of(
                jnp.maximum(start - _HALO, 0), _HALO), _HALO)[8:]
            dp, taps = dpre(jnp.where(s == 0, before, over), cur,
                            f32(dy_ref, start, slab), w, bias)
            dx = sum(w[i] * _up(dp, dp_after, k - 1 - i) for i in range(k))
            dx_ref[0, pl.ds(start, slab), lanes] = dx.astype(dx_ref.dtype)
            sums = tuple(a + _fold(dp * tap) for a, tap in zip(sums, taps)) \
                + (sums[k] + _fold(dp),)
            return dp[:8], sums

        zero = jnp.zeros((8, lanes.size), F32)
        _, sums = lax.fori_loop(0, slabs, one, (dp_after, (zero,) * (k + 1)))
        for i in range(k + 1):
            dwb_ref[0, 0, 8 * i:8 * i + 8, lanes] = sums[i]

    def whole(j, carry):
        tile(pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES))
        return carry

    lax.fori_loop(0, width // _LANES, whole, 0)
    if width % _LANES:
        tile(pl.ds(width - width % _LANES, width % _LANES))


def _conv_bwd_call(dy, x, w, bias, k, act, blocks, interpret=False):
    """(dx, dw (C, k) float32, dbias (C,) float32); w (C, k) and bias (C,)
    float32."""
    b, t, c = x.shape
    rows, lanes = blocks
    halos = rows // _HALO
    block = pl.BlockSpec((1, rows, lanes), lambda b, i, j: (b, i, j))
    before = pl.BlockSpec(
        (1, _HALO, lanes),
        lambda b, i, j: (b, jnp.maximum(i * halos - 1, 0), j))
    after = pl.BlockSpec(
        (1, _HALO, lanes),
        lambda b, i, j: (b, jnp.minimum((i + 1) * halos, t // _HALO - 1), j))
    dx, dwb = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, k=k, act=act),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, t // rows, 8 * (k + 1), c), F32)],
        grid=(b, t // rows, c // lanes),
        in_specs=[block, block, before, after, after,
                  pl.BlockSpec((k, lanes), lambda b, i, j: (0, j)),
                  pl.BlockSpec((1, lanes), lambda b, i, j: (0, j))],
        out_specs=[block,
                   pl.BlockSpec((1, 1, 8 * (k + 1), lanes),
                                lambda b, i, j: (b, i, 0, j))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_vmem(6 * rows * lanes * x.dtype.itemsize)),
        interpret=interpret,
        name=CONV_BWD_KERNEL_NAME,
    )(dy, x, x, x, dy, w.T, bias.reshape(1, c))
    sums = jnp.sum(dwb.reshape(b * (t // rows), k + 1, 8, c), axis=(0, 2))
    return dx, sums[:k].T, sums[k]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _conv_forward(x, w, bias, k, act):
    return _conv_plain(x, w, bias, k, act)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _conv_backward(dy, x, w, bias, k, act):
    b, t, c = x.shape
    blocks = _conv_blocks(t, c, k, x.dtype.itemsize)
    w32 = w.astype(F32)
    bias32 = jnp.zeros((c,), F32) if bias is None else bias.astype(F32)

    def plain(dy, x, w32, bias32):
        return _conv_bwd_plain(dy, x, w32, bias32, k, act)

    def kernel(dy, x, w32, bias32):
        return _conv_bwd_call(dy, x, w32, bias32, k, act, blocks)

    dx, dw, dbias = _lowered(blocks, kernel, plain, dy, x, w32, bias32)
    return dx, dw.astype(w.dtype), \
        None if bias is None else dbias.astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, bias, k, act):
    return _conv_forward(x, w, bias, k, act)


def _conv_vjp_fwd(x, w, bias, k, act):
    blocks = _conv_blocks(x.shape[1], x.shape[2], k, x.dtype.itemsize)
    telemetry.counter(
        "mixer_conv_builds", labels={"path": _path(blocks)},
        help="differentiated short convolutions (with their activation) "
             "traced, by whether their shape takes the one-pass backward "
             "kernel on the chip").inc()
    return _conv_forward(x, w, bias, k, act), (x, w, bias)


def _conv_vjp_bwd(k, act, res, dy):
    return _conv_backward(dy, *res, k, act)


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def short_conv(x, w, bias=None, kernel=4, act="none"):
    """act(causal depthwise convolution of x (B, T, C) by w (C, kernel) +
    bias (C,) or nothing), `act` ``"none"`` or ``"silu"``."""
    k = int(kernel)
    if act not in ACTS:
        raise ValueError("short_conv: act_type %r is none of %r" % (act, ACTS))
    if x.ndim != 3 or w.shape != (x.shape[2], k):
        raise ValueError("short_conv: %r by a filter %r of %d taps"
                         % (x.shape, w.shape, k))
    return _conv(x, w, bias, k, act)


# -------------------------------------------------------- gated norm, plain


def _norm_plain(x, g, gamma, n, eps, gate_first):
    """x, g (N, C), gamma (C,) float32, groups of n channels."""
    u = x.astype(F32)
    silu = jax.nn.silu(g.astype(F32))
    if gate_first:
        u = u * silu
    grouped = u.reshape(u.shape[0], -1, n)
    ms = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    out = (grouped * lax.rsqrt(ms + eps)).reshape(u.shape) * gamma
    if not gate_first:
        out = out * silu
    return out.astype(x.dtype)


def _norm_bwd_plain(do, x, g, gamma, n, eps, gate_first):
    """(dx, dgate, dgamma (C,) float32)."""
    shape, dtypes = x.shape, (x.dtype, g.dtype)
    x, g, do = x.astype(F32), g.astype(F32), do.astype(F32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    dsilu = sig * (1.0 + g * (1.0 - sig))
    u = x * silu if gate_first else x
    dn = do if gate_first else do * silu

    def groups(v):
        return v.reshape(shape[0], -1, n)

    rstd = lax.rsqrt(jnp.mean(jnp.square(groups(u)), -1, keepdims=True) + eps)
    uh = groups(u) * rstd
    duh = groups(dn * gamma)
    du = (rstd * (duh - uh * jnp.mean(duh * uh, -1, keepdims=True))
          ).reshape(shape)
    uh = uh.reshape(shape)
    if gate_first:
        dx, dg = du * silu, du * x * dsilu
    else:
        dx, dg = du, do * uh * gamma * dsilu
    return (dx.astype(dtypes[0]), dg.astype(dtypes[1]),
            jnp.sum(dn * uh, axis=0))


# ------------------------------------------------------- gated norm, kernel


def _norm_plan(shape, groups, itemsize):
    """How an array of `shape`, normed over `groups` groups of its last
    axis, is seen by the kernels: (N, C, n, fold, rows, lanes), N rows of C
    channels in groups of n, `fold` of the axis before the last folded into
    C, a grid step `rows` x `lanes`; None where the shape does not tile."""
    if len(shape) < 2 or shape[-1] % groups:
        return None
    n = shape[-1] // groups
    if n % _LANES == 0:
        lanes = n
    elif (2 * n) % _LANES == 0:
        lanes = 2 * n
    else:
        return None
    c, fold = shape[-1], 1
    if c % lanes:
        if len(shape) < 3:
            return None
        c, fold = c * shape[-2], shape[-2]
    if lanes > 1024 or c % lanes:
        return None
    rows_in_all = 1
    for d in shape:
        rows_in_all *= d
    rows_in_all //= c
    rows = _row_blocks(rows_in_all, 5 * lanes * itemsize, 12 << 20)
    return None if rows is None else (rows_in_all, c, n, fold, rows, lanes)


def _group_mean(v, n):
    """The mean of v (rows, lanes) over each group of n lanes, broadcast
    back over the lanes: one group a block, or two whose sums are masked."""
    if v.shape[1] == n:
        return jnp.sum(v, axis=-1, keepdims=True) * (1.0 / n)
    low = lax.broadcasted_iota(jnp.int32, v.shape, 1) < n
    a = jnp.sum(jnp.where(low, v, 0.0), axis=-1, keepdims=True)
    b = jnp.sum(jnp.where(low, 0.0, v), axis=-1, keepdims=True)
    return jnp.where(low, a, b) * (1.0 / n)



def _norm_fwd_kernel(x_ref, g_ref, gamma_ref, o_ref, *, n, eps, gate_first):
    gamma = gamma_ref[...]

    def one(s, carry):
        rows = pl.ds(pl.multiple_of(s * _NORM_SLAB, _NORM_SLAB), _NORM_SLAB)
        u = x_ref[rows, :].astype(F32)
        g = g_ref[rows, :].astype(F32)
        silu = g * _sigmoid(g)
        if gate_first:
            u = u * silu
        out = u * lax.rsqrt(_group_mean(u * u, n) + eps) * gamma
        if not gate_first:
            out = out * silu
        o_ref[rows, :] = out.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[0] // _NORM_SLAB, one, 0)


def _norm_bwd_kernel(do_ref, x_ref, g_ref, gamma_ref, dx_ref, dg_ref,
                     dgamma_ref, *, n, eps, gate_first):
    gamma = gamma_ref[...]

    def one(s, dgamma):
        rows = pl.ds(pl.multiple_of(s * _NORM_SLAB, _NORM_SLAB), _NORM_SLAB)
        x = x_ref[rows, :].astype(F32)
        g = g_ref[rows, :].astype(F32)
        do = do_ref[rows, :].astype(F32)
        sig = _sigmoid(g)
        silu = g * sig
        dsilu = sig * (1.0 + g * (1.0 - sig))
        u = x * silu if gate_first else x
        dn = do if gate_first else do * silu
        rstd = lax.rsqrt(_group_mean(u * u, n) + eps)
        uh = u * rstd
        duh = dn * gamma
        du = rstd * (duh - uh * _group_mean(duh * uh, n))
        if gate_first:
            dx, dg = du * silu, du * x * dsilu
        else:
            dx, dg = du, do * uh * gamma * dsilu
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        dg_ref[rows, :] = dg.astype(dg_ref.dtype)
        return dgamma + _fold(dn * uh)

    dgamma_ref[0] = lax.fori_loop(
        0, x_ref.shape[0] // _NORM_SLAB, one,
        jnp.zeros((8, x_ref.shape[1]), F32))


def _norm_specs(rows, lanes):
    return (pl.BlockSpec((rows, lanes), lambda i, j: (i, j)),
            pl.BlockSpec((1, lanes), lambda i, j: (0, j)))


def _norm_fwd_call(x, g, gamma, n, eps, gate_first, blocks, interpret=False):
    """x, g (N, C); gamma (1, C) float32."""
    rows, lanes = blocks
    block, vector = _norm_specs(rows, lanes)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, n=n, eps=eps,
                          gate_first=gate_first),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(x.shape[0] // rows, x.shape[1] // lanes),
        in_specs=[block, block, vector],
        out_specs=block,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem(6 * rows * lanes * x.dtype.itemsize)),
        interpret=interpret,
        name=NORM_FWD_KERNEL_NAME,
    )(x, g, gamma)


def _norm_bwd_call(do, x, g, gamma, n, eps, gate_first, blocks,
                   interpret=False):
    """(dx, dgate, dgamma (C,) float32)."""
    rows, lanes = blocks
    block, vector = _norm_specs(rows, lanes)
    dx, dg, dgamma = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, n=n, eps=eps,
                          gate_first=gate_first),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(g.shape, g.dtype),
                   jax.ShapeDtypeStruct((x.shape[0] // rows, 8, x.shape[1]),
                                        F32)],
        grid=(x.shape[0] // rows, x.shape[1] // lanes),
        in_specs=[block, block, block, vector],
        out_specs=[block, block,
                   pl.BlockSpec((1, 8, lanes), lambda i, j: (i, 0, j))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem(10 * rows * lanes * x.dtype.itemsize)),
        interpret=interpret,
        name=NORM_BWD_KERNEL_NAME,
    )(do, x, g, gamma)
    return dx, dg, jnp.sum(dgamma, axis=(0, 1))


def _norm_view(shape, groups, gamma, itemsize):
    """(plan, group width, (N, C), gamma as (C,) float32): the array as the
    kernels see it under its plan, or its own rows under none."""
    plan = _norm_plan(shape, groups, itemsize)
    n = shape[-1] // groups
    if plan is None:
        return plan, n, (-1, shape[-1]), gamma.astype(F32)
    rows, c, _, fold = plan[:4]
    return plan, n, (rows, c), jnp.tile(gamma.astype(F32), fold)


# static: eps, groups, gate_first
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _norm_forward(x, gamma, g, eps, groups, gate_first):
    plan, n, flat, gamma32 = _norm_view(x.shape, groups, gamma,
                                        x.dtype.itemsize)

    def plain(x, g, gamma32):
        return _norm_plain(x, g, gamma32, n, eps, gate_first)

    def kernel(x, g, gamma32):
        return _norm_fwd_call(x, g, gamma32.reshape(1, -1), n, eps,
                              gate_first, plan[4:])

    return _lowered(plan, kernel, plain, x.reshape(flat), g.reshape(flat),
                    gamma32).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _norm_backward(do, x, gamma, g, eps, groups, gate_first):
    plan, n, flat, gamma32 = _norm_view(x.shape, groups, gamma,
                                        x.dtype.itemsize)

    def plain(do, x, g, gamma32):
        return _norm_bwd_plain(do, x, g, gamma32, n, eps, gate_first)

    def kernel(do, x, g, gamma32):
        return _norm_bwd_call(do, x, g, gamma32.reshape(1, -1), n, eps,
                              gate_first, plan[4:])

    dx, dg, dgamma = _lowered(plan, kernel, plain, do.reshape(flat),
                              x.reshape(flat), g.reshape(flat), gamma32)
    dgamma = jnp.sum(dgamma.reshape(-1, gamma.shape[0]), axis=0)
    return (dx.reshape(x.shape), dgamma.astype(gamma.dtype),
            dg.reshape(g.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(x, gamma, g, eps, groups, gate_first):
    return _norm_forward(x, gamma, g, eps, groups, gate_first)


def _norm_vjp_fwd(x, gamma, g, eps, groups, gate_first):
    telemetry.counter(
        "mixer_norm_builds",
        labels={"path": _path(_norm_plan(x.shape, groups, x.dtype.itemsize))},
        help="differentiated gated output norms traced, by whether their "
             "shape takes the one-pass kernels on the chip").inc()
    return _norm_forward(x, gamma, g, eps, groups, gate_first), (x, gamma, g)


def _norm_vjp_bwd(eps, groups, gate_first, res, do):
    return _norm_backward(do, *res, eps, groups, gate_first)


_norm.defvjp(_norm_vjp_fwd, _norm_vjp_bwd)


def gated_norm(x, gamma, gate, eps=1e-6, groups=1, gate_first=False):
    """RMSNorm of x over each of `groups` groups of its last axis, times
    gamma (one number a channel) and silu(gate): the gate on the normed
    result, or, `gate_first`, on x inside the mean square."""
    groups = int(groups)
    if gate.shape != x.shape or x.shape[-1] % groups \
            or gamma.shape != (x.shape[-1],):
        raise ValueError("gated_norm: %r gated by %r, %d groups, gamma %r"
                         % (x.shape, gate.shape, groups, gamma.shape))
    return _norm(x, gamma, gate, float(eps), groups, bool(gate_first))
