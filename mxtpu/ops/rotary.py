"""Rotary position embedding as one operator with its own gradient.

``_contrib_RotaryEmbedding(x)`` turns the first ``rotary_dims`` dims of each
head of x (B, H, T, D) by the angle ``p * f_i``, p the position in the row
from 0, halves paired (i, i + r/2):

    (u1, u2) -> (u1 c - u2 s, u2 c + u1 s),  c = m cos(p f_i), s = m sin(p f_i)

with m = ``scale`` (YaRN's ``attention_factor``; 1 otherwise). The other
dims pass unrotated and unscaled. The inverse-frequency table is a constant
of the graph made once from the attributes (`inv_freq`), never a parameter:

- ``rope_type="default"``: f_i = theta^(-2i/r);
- ``rope_type="yarn"`` (Peng et al., arXiv:2309.00071): e_i = theta^(-2i/r),
  f_i = e_i / factor * ramp_i + e_i * (1 - ramp_i), ramp_i =
  clip((i - lo) / (hi - lo), 0, 1), lo = floor(c(beta_fast)), hi =
  ceil(c(beta_slow)), c(b) = r ln(original / (2 pi b)) / (2 ln theta), both
  clamped to [0, r - 1].

Angles, cos and sin are float32 whatever x is; the result is x's dtype. The
gradient is the rotation transposed (the same table, s negated), a
``jax.custom_vjp``, so nothing of the forward is kept but the table's
recipe.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from .registry import register


def inv_freq(rotary_dims, rope_type="default", theta=10000.0, factor=1.0,
             original_max_position=0, beta_fast=32.0, beta_slow=1.0):
    """The r/2 inverse frequencies, float32 (computed in float64)."""
    r = int(rotary_dims)
    if r <= 0 or r % 2:
        raise ValueError("rotary: %d dims cannot be paired" % r)
    e = float(theta) ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rope_type == "default":
        return e.astype(np.float32)
    if rope_type != "yarn":
        raise ValueError("rotary: unknown rope_type %r" % (rope_type,))

    def correction(beta):
        return (r * math.log(original_max_position / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    lo = max(math.floor(correction(beta_fast)), 0)
    hi = min(math.ceil(correction(beta_slow)), r - 1)
    if lo == hi:
        hi += 0.001     # the standard implementation's guard
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - lo) / (hi - lo),
                   0.0, 1.0)
    return (e / float(factor) * ramp + e * (1.0 - ramp)).astype(np.float32)


def _tables(freqs, t, scale):
    """(cos, sin), each (T, r/2) float32, scaled."""
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * jnp.asarray(freqs, jnp.float32)[None, :])
    return scale * jnp.cos(angle), scale * jnp.sin(angle)


def _turn(x, cos, sin):
    """The rotation of x (..., T, D) over its first 2 * cos.shape[-1] dims."""
    half = cos.shape[-1]
    xf = x.astype(jnp.float32)
    u1, u2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    return jnp.concatenate([u1 * cos - u2 * sin, u2 * cos + u1 * sin, rest],
                           axis=-1).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _rotary(x, freqs, scale):
    return _turn(x, *_tables(freqs, x.shape[-2], scale))


def _rotary_fwd(x, freqs, scale):
    return _rotary(x, freqs, scale), None


def _rotary_bwd(freqs, scale, _, g):
    # the transpose: the same turn backwards (the dims past r pass, as they
    # did forward)
    cos, sin = _tables(freqs, g.shape[-2], scale)
    return (_turn(g, cos, -sin),)


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def rotary_embedding(x, rotary_dims=0, rope_type="default", theta=10000.0,
                     factor=1.0, original_max_position=0, beta_fast=32.0,
                     beta_slow=1.0, scale=1.0):
    """x (B, H, T, D) with its heads' first `rotary_dims` dims (0: all D)
    turned by their positions."""
    d = x.shape[-1]
    r = int(rotary_dims) or d
    if r > d:
        raise ValueError("rotary: %d rotary dims of a head of %d" % (r, d))
    with telemetry.span("rotary.build", category="compile",
                        tags={"rope_type": rope_type, "dims": r}):
        freqs = tuple(float(f) for f in inv_freq(
            r, rope_type, theta, factor, original_max_position, beta_fast,
            beta_slow))
        return _rotary(x, freqs, float(scale))


def _rotary_op(a, x):
    return rotary_embedding(
        x, a.rotary_dims, a.rope_type, a.theta, a.factor,
        a.original_max_position, a.beta_fast, a.beta_slow, a.scale)


register("_contrib_RotaryEmbedding", _rotary_op, arg_names=["data"],
         attrs={"rotary_dims": 0, "rope_type": "default", "theta": 10000.0,
                "factor": 1.0, "original_max_position": 0,
                "beta_fast": 32.0, "beta_slow": 1.0, "scale": 1.0},
         aliases=("rotary_embedding",))
