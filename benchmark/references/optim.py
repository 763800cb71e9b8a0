"""Plain optimizers of the references: SGD with momentum and Adam as MXNet
0.11 defines them (`sgd_mom_update`, `adam_update` with the bias correction
folded into the learning rate), in float32. A parameter is rounded to the
dtype the configuration states it is kept in (`round_to_dtype`).
"""
import math

import jax.numpy as jnp

from benchmark.weights import round_to_dtype


def init(opt, params):
    if opt["name"] == "sgd":
        return {k: jnp.zeros_like(v) for k, v in params.items()}
    if opt["name"] == "adam":
        return {k: (jnp.zeros_like(v), jnp.zeros_like(v))
                for k, v in params.items()}
    raise ValueError(opt["name"])


def update(opt, t, params, grads, state, dtypes):
    """One step (t counts from 1). Returns (params, state); `first_moment`
    reads what the gradient norm is taken from."""
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)
    rescale = opt["rescale_grad"]
    new_p, new_s = {}, {}
    for k, p in params.items():
        g = grads[k] * rescale + wd * p
        if opt["name"] == "sgd":
            m = opt["momentum"] * state[k] - lr * g
            new_s[k] = m
            q = p + m
        else:
            b1, b2, eps = opt.get("beta1", 0.9), opt.get("beta2", 0.999), \
                opt.get("epsilon", 1e-8)
            lr_t = lr * math.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
            m = b1 * state[k][0] + (1 - b1) * g
            v = b2 * state[k][1] + (1 - b2) * jnp.square(g)
            new_s[k] = (m, v)
            q = p - lr_t * m / (jnp.sqrt(v) + eps)
        new_p[k] = round_to_dtype(q, dtypes.get(k, dtypes["default"]))
    return new_p, new_s


def first_moment(opt, state):
    if opt["name"] == "sgd":
        return state
    return {k: s[0] for k, s in state.items()}
