"""What both plain references share: the precision scope, the control's
rounding and the cross-entropy the program's `ce` metric reports.

The control of a bfloat16 configuration computes in fp8: every operand of a
matrix multiplication or convolution is rounded to 4 exponent and 3 mantissa bits
(`lax.reduce_precision`, which XLA may not drop) under a per-tensor scale
(amax / 240), with a straight-through gradient.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rounder(quant):
    """Operand rounding of the control, or the identity for the reference."""
    if quant is None:
        return lambda x: x
    if quant == "fp8":
        def q(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0   # largest
            r = jax.lax.reduce_precision(x / s, 4, 3) * s   # finite e4m3 value
            return x + jax.lax.stop_gradient(r - x)
        return q
    raise ValueError("unknown control precision %r" % quant)


def ce_sum(logits, labels, eps=1e-12):
    """(sum of the log-softmax cross-entropy, for the gradient; sum of
    -log(p + eps), as `mx.metric.CrossEntropy` counts it)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    return -jnp.sum(picked), jnp.sum(-jnp.log(jnp.exp(picked) + eps))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _cos_gap(a, b):
    """1 - cosine between two leaves, taken as half the squared distance of
    their directions (exact near 0, where 1 - cos cancels); 1 where either
    is all nought."""
    a, b = a.astype(jnp.float32).ravel(), b.astype(jnp.float32).ravel()
    na, nb = jnp.linalg.norm(a), jnp.linalg.norm(b)
    gap = 0.5 * jnp.sum(jnp.square(a / jnp.maximum(na, 1e-30)
                                   - b / jnp.maximum(nb, 1e-30)))
    return jnp.where((na > 0) & (nb > 0), gap, 1.0)


def cos_gaps(mine, theirs):
    """{leaf: 1 - cosine} between this side's first gradient (on the device)
    and the other side's (`theirs`, kept on the host since its step 1), a
    leaf at a time. A gap of norms grows with the square of rounding noise
    about a norm that is itself large; this one is measured against nothing,
    so a lower precision shows in it by orders, not by a factor."""
    return {k: float(_cos_gap(v, theirs[k])) for k, v in mine.items()
            if k in theirs}


def follow(block_loss, make_params, batch, opt, dtypes, steps=3,
           rows_per_block=None, keep_one_in=1, items_per_row=1,
           against=None, keep_first=False):
    """Follows the program's first `steps` steps on one batch.

    Returns the loss the `ce` metric reports at each step, the per-leaf norm
    of the optimizer's first moment after step 1 (the first gradient as the
    optimizer gets it) and the per-leaf norm of the parameters' change after
    the last step. `against` is the other side's first moment, per leaf on
    the host: `grad_cos_gap` is then each leaf's 1 - cosine with it.
    `keep_first` returns this side's, on the host, as `first_grad`. The
    gradient is summed over blocks of rows so that it fits
    beside the state; a model whose rows are coupled (batch normalisation)
    takes the whole batch as one block. `keep_one_in=2` plants a fault: only
    the first half of the rows is used and the mean taken over it ("half of
    the batch left out").
    """
    from . import optim
    rows = batch[0].shape[0]
    if keep_one_in > 1:
        rows //= keep_one_in
        batch = tuple(x[:rows] for x in batch)
        opt = dict(opt, rescale_grad=opt["rescale_grad"] * keep_one_in)
    rows_per_block = rows_per_block or rows
    grad_fn = jax.jit(jax.value_and_grad(block_loss, has_aux=True))
    add = jax.jit(lambda a, c: jax.tree.map(jnp.add, a, c),
                  donate_argnums=(0, 1))
    step_fn = jax.jit(
        lambda t, p, g, s: optim.update(opt, t, p, g, s, dtypes),
        static_argnums=0, donate_argnums=(1, 2, 3))
    norms = jax.jit(leaf_norms)
    delta = jax.jit(lambda a, c: leaf_norms({k: a[k] - c[k] for k in a}),
                    donate_argnums=(0, 1))
    params = make_params()
    state = optim.init(opt, params)
    out = {"loss": []}
    for step in range(1, steps + 1):
        grads, metric = None, 0.0
        for r in range(0, rows, rows_per_block):
            (_, m), g = grad_fn(params, *(x[r:r + rows_per_block]
                                          for x in batch))
            grads = g if grads is None else add(grads, g)
            metric = metric + float(m)
        out["loss"].append(metric / (rows * items_per_row))
        params, state = step_fn(step, params, grads, state)
        del grads
        if step == 1:
            first = optim.first_moment(opt, state)
            out["grad_norm"] = {k: float(v) for k, v in norms(first).items()}
            if against is not None:
                out["grad_cos_gap"] = cos_gaps(first, against)
            if keep_first:
                out["first_grad"] = jax.device_get(first)
            del first
    del state
    out["delta_norm"] = {k: float(v)
                         for k, v in delta(params, make_params()).items()}
    return out
