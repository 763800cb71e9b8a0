"""Pallas epilogue kernel: BN-apply + ReLU + residual-add in ONE pass over
the activation (VERDICT r3 next #2 — test whether a hand-fused epilogue
beats XLA's own elementwise fusion on the bytes the ResNet train step
moves between a conv output and the next conv input).

The BN *apply* stage is an affine per-channel transform (scale/shift
folded from batch stats, gamma, beta — batch_norm-inl.h's normalize step);
fusing it with the activation and the block-join add means the conv
output is read ONCE and the block input written ONCE. XLA usually builds
the same fusion by itself; whether there is anything left on the table
is not measured on the chip (see PERF.md).

Layout: channel-minor (M, C) tiles, the TPU-native layout (C is the
128-lane axis). NCHW callers reshape/transpose outside.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, b_ref, r_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = x * s_ref[...] + b_ref[...]
    y = jnp.maximum(y, 0.0)
    if r_ref is not None:
        y = y + r_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m",))
def bn_apply_relu_add(x, scale, shift, residual=None, block_m=1024):
    """y = relu(x * scale + shift) [+ residual], one HBM pass.

    x (M, C) bf16/f32; scale/shift (C,) f32; residual optional (M, C).
    Mosaic-compiled when the program is lowered for ``tpu``; interpret
    mode only when it is lowered for ``cpu`` (the tests). Jitted so the
    choice follows the operands' device, not the process default.
    """
    m, c = x.shape
    block_m = min(block_m, m)
    grid = (pl.cdiv(m, block_m),)
    scale2 = scale.reshape(1, c).astype(jnp.float32)
    shift2 = shift.reshape(1, c).astype(jnp.float32)

    in_specs = [
        pl.BlockSpec((block_m, c), lambda i: (i, 0)),
        pl.BlockSpec((1, c), lambda i: (0, 0)),
        pl.BlockSpec((1, c), lambda i: (0, 0)),
    ]
    args = [x, scale2, shift2]
    if residual is not None:
        in_specs.append(pl.BlockSpec((block_m, c), lambda i: (i, 0)))
        args.append(residual)
        kern = _kernel
    else:
        def kern(x_ref, s_ref, b_ref, o_ref):
            return _kernel(x_ref, s_ref, b_ref, None, o_ref)

    def call(interpret):
        return pl.pallas_call(
            kern,
            out_shape=jax.ShapeDtypeStruct((m, c), x.dtype),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((block_m, c), lambda i: (i, 0)),
            interpret=interpret,
        )

    return jax.lax.platform_dependent(
        *args, tpu=call(False), cpu=call(True))


def bn_apply_relu_add_reference(x, scale, shift, residual=None):
    """The XLA-fused formulation the kernel competes with."""
    y = x.astype(jnp.float32) * scale.astype(jnp.float32) \
        + shift.astype(jnp.float32)
    y = jnp.maximum(y, 0.0)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return y.astype(x.dtype)


def fold_bn(gamma, beta, mean, var, eps=1e-5):
    """Fold BN statistics into the per-channel (scale, shift) the apply
    stage consumes: scale = gamma*rsqrt(var+eps), shift = beta-mean*scale
    (batch_norm-inl.h normalize step)."""
    scale = gamma * jax.lax.rsqrt(var + eps)
    return scale, beta - mean * scale
