"""Weights and batches from `--seed`, made on the device in one jitted call.

The program and the plain reference both get their first weights from here,
so neither takes anything the other has made. A spec is (name, shape, rule):
`normal:<std>` draws N(0, std), `he` draws N(0, sqrt(2 / fan_in)), `ones`
and `zeros` are constants. Every value is rounded to `round_to`
(`round_to_dtype`; the dtype the configuration states for its parameters) and
handed out as float32, so the program's cast to that dtype loses nothing and
both sides start alike.
"""
import math

import numpy as np


def seed_key(seed):
    """PRNG key for seeds beyond 32 signed bits (the driver's are large)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


_BITS = {"bfloat16": (8, 7), "float16": (5, 10), "float8_e4m3": (4, 3)}


def round_to_dtype(x, dtype):
    """x rounded to `dtype`'s values and kept in float32. An
    astype(dtype).astype(float32) pair is not used: XLA on the TPU may drop
    it (`xla_allow_excess_precision`), and did (PERF.md, PR 24)."""
    import jax
    if str(dtype) == "float32":
        return x
    e, m = _BITS[str(dtype)]
    return jax.lax.reduce_precision(x, exponent_bits=e, mantissa_bits=m)


def _std(rule, shape):
    if rule.startswith("normal:"):
        return float(rule.split(":", 1)[1])
    if rule == "he":
        fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else int(shape[0])
        return math.sqrt(2.0 / fan_in)
    raise ValueError("unknown weight rule %r" % rule)


def make(seed, specs, round_to="bfloat16", dtypes=None):
    """{name: device array} for the sorted specs, one program: float32, or
    the dtype `dtypes` names for a leaf (a served model's own dtypes, so no
    float32 copy of it is ever held)."""
    import jax
    import jax.numpy as jnp
    specs = sorted(specs)

    def build(key):
        out = {}
        for i, (name, shape, rule) in enumerate(specs):
            if rule == "ones":
                v = jnp.ones(shape, jnp.float32)
            elif rule == "zeros":
                v = jnp.zeros(shape, jnp.float32)
            else:
                v = _std(rule, shape) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            v = round_to_dtype(v, round_to)
            out[name] = v.astype(dtypes[name]) if dtypes else v
        return out

    return jax.jit(build)(jax.random.fold_in(seed_key(seed), 1))


def batch_key(seed):
    import jax
    return jax.random.fold_in(seed_key(seed), 2)
