"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

Beyond reference parity: the closest the reference has is group2ctx
placement, which runs stages serially with cross-device copies
(SURVEY §2.4). Here stages are *pipelined*: the batch splits into
microbatches, every device owns one stage's parameters, and activations
ride `lax.ppermute` around the 'pipe' axis — after the fill phase all
stages compute concurrently on different microbatches, the classic GPipe
schedule expressed as a shard_map + scan program so XLA overlaps the
neighbor transfers (ICI) with stage compute.

The stage function is user-supplied: ``stage_fn(params, x) -> y`` with
per-stage params stacked on a leading axis (stage i's slice lives on pipe
device i).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(stage_params_list):
    """Stack a list of per-stage param pytrees on a new leading axis
    (shard that axis over 'pipe' when placing on the mesh)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params_list)


def pipeline_apply(stage_fn, stacked_params, x, mesh=None,
                   axis_name="pipe", num_microbatches=None,
                   batch_axis=None):
    """Run ``x`` through n_stages pipelined stages.

    stacked_params: pytree with leading stage axis, sharded over
    ``axis_name``. x: (batch, ...) input. Returns (batch, ...) output of
    the final stage.

    ``batch_axis`` composes pipeline with data parallelism (dp x pp): on
    a 2-D mesh like ('data', 'pipe') the batch dimension shards over
    ``batch_axis`` while stages shard over ``axis_name`` — each data-
    parallel row runs its own pipeline on its batch shard, and the stage
    params replicate across rows. None (default) keeps the input
    replicated (pure pp).

    Schedule: T = n_micro + n_stages - 1 ticks. At each tick every device
    runs its stage on the activation it holds, then activations rotate one
    hop so stage s+1 sees stage s's output next tick — steady-state keeps
    every stage busy.
    """
    if mesh is None:
        from .mesh import current_mesh
        mesh = current_mesh()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = axis_sizes[axis_name]
    batch = x.shape[0]
    n_micro = num_microbatches if num_microbatches is not None else n_stages
    assert n_micro >= 1, "num_microbatches must be >= 1"
    dp = axis_sizes[batch_axis] if batch_axis else 1
    assert batch % (n_micro * dp) == 0, \
        "batch must divide into microbatches on every data-parallel row"
    mb = batch // dp // n_micro

    pspec = P(axis_name)       # stage axis of the stacked params
    xspec = P(batch_axis) if batch_axis else P()

    def local_fn(params, xl):
        # params: this device's stage slice (leading axis length 1)
        params = jax.tree.map(lambda p: p[0], params)
        sidx = lax.axis_index(axis_name)
        micro = xl.reshape(n_micro, mb, *xl.shape[1:])
        n_ticks = n_micro + n_stages - 1
        perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            acts, outputs = carry
            # stage 0 injects microbatch t (or zeros after the fill phase)
            inject = jnp.where(t < n_micro,
                               micro[jnp.minimum(t, n_micro - 1)],
                               jnp.zeros((mb,) + xl.shape[1:], xl.dtype))
            cur = jnp.where(sidx == 0, inject, acts)
            out = stage_fn(params, cur)
            # the last stage emits microbatch (t - n_stages + 1)
            emit_idx = t - (n_stages - 1)
            is_emit = jnp.logical_and(sidx == n_stages - 1, emit_idx >= 0)
            outputs = lax.cond(
                is_emit,
                lambda o: o.at[jnp.maximum(emit_idx, 0)].set(out),
                lambda o: o, outputs)
            # rotate activations one hop forward for the next tick
            acts = lax.ppermute(out, axis_name, perm_fwd)
            return (acts, outputs), None

        out_shape = jax.eval_shape(stage_fn, params,
                                   jnp.zeros((mb,) + xl.shape[1:],
                                             xl.dtype))
        acts0 = jnp.zeros((mb,) + xl.shape[1:], xl.dtype)
        outputs0 = jnp.zeros((n_micro,) + out_shape.shape, out_shape.dtype)
        # with a composed data axis the activations vary over BOTH axes
        # (each data row pipelines its own shard)
        vary = (axis_name, batch_axis) if batch_axis else axis_name
        acts0 = lax.pcast(acts0, vary, to="varying")
        outputs0 = lax.pcast(outputs0, vary, to="varying")
        (acts, outputs), _ = lax.scan(tick, (acts0, outputs0),
                                      jnp.arange(n_ticks))
        # only the last stage holds real outputs; share them with everyone
        outputs = lax.psum(
            jnp.where(sidx == n_stages - 1, outputs, 0.0), axis_name)
        return outputs.reshape(xl.shape[0], *out_shape.shape[1:])

    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(jax.tree.map(lambda _: pspec,
                                                stacked_params), xspec),
                         out_specs=xspec)(stacked_params, x)
