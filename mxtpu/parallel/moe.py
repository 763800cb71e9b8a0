"""Expert parallelism: a mixture-of-experts layer sharded over a mesh axis.

Beyond reference parity (the reference has no MoE constructs —
SURVEY §2.4 checklist), but part of the required TPU-first parallelism
surface. Design: experts shard over the 'expert' axis; tokens route to
experts with top-1 gating; an `all_to_all` carries each device's tokens
to the devices owning their experts and a second one brings results back
— the standard expert-parallel exchange, riding ICI.

Scores, top-k and weights come from the router of the Symbol-level expert
layer (`mxtpu.ops.moe.route`), so the repo has one router; the dropless,
sorted dispatch of that layer (`_contrib_MoEExperts`) runs one chip's share
without an exchange, and this module keeps the capacity dispatch and the
all-to-all.

Capacity is fixed (static shapes for XLA): each expert takes
``capacity_factor * tokens / n_experts`` tokens; overflow tokens pass
through unchanged (standard MoE overflow semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.moe import route

__all__ = ["moe_apply", "moe_apply_topk", "load_balancing_loss"]


def moe_apply(expert_fn, expert_params, gate_logits, x, mesh=None,
              axis_name="expert", capacity_factor=2.0):
    """Top-1 MoE over expert-parallel devices.

    expert_params: pytree with leading expert-shard axis (n_local experts
    per device), sharded over ``axis_name``. gate_logits: (tokens,
    n_experts_total) replicated. x: (tokens, d) replicated. Returns
    (tokens, d): expert outputs scaled by gate probability, overflow and
    unrouted tokens passed through.
    """
    if mesh is None:
        from .mesh import current_mesh
        mesh = current_mesh()
    n_dev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    tokens, d = x.shape
    n_experts = gate_logits.shape[1]
    assert n_experts % n_dev == 0
    n_local = n_experts // n_dev
    capacity = max(1, int(capacity_factor * tokens / n_experts))

    def local_fn(params, gates, xl):
        # the repo's one router (mxtpu/ops/moe.py): softmax scores, the
        # best expert, its probability as the weight
        gate_w, chosen = route(gates, 1, score_func="softmax",
                               norm_topk=False)
        choice, gate_p = chosen[:, 0], gate_w[:, 0]      # (tokens,)

        # slot assignment: position of each token within its expert queue
        onehot = jax.nn.one_hot(choice, n_experts, dtype=jnp.int32)
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)
        slot = jnp.take_along_axis(pos_in_expert, choice[:, None],
                                   axis=1)[:, 0]        # (tokens,)
        keep = slot < capacity

        # dispatch buffer: (n_experts, capacity, d), built densely
        disp = jnp.zeros((n_experts, capacity, d), x.dtype)
        tok_idx = jnp.arange(tokens)
        disp = disp.at[choice, jnp.minimum(slot, capacity - 1)].add(
            jnp.where(keep[:, None], xl, 0.0)[tok_idx])

        # exchange: every device keeps its own experts' queues
        # (n_dev, n_local, capacity, d) -> all_to_all over expert axis
        disp = disp.reshape(n_dev, n_local, capacity, d)
        recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
        # recv: (n_dev, n_local, capacity, d) = every source's tokens for
        # MY experts; merge sources (slots are disjoint per source? no —
        # every device computed the same routing, so queues are identical:
        # take one copy)
        my_tokens = recv[0]                              # (n_local, cap, d)

        out = jax.vmap(expert_fn)(params, my_tokens)     # (n_local, cap, d)

        # return results to every device (gather over the axis)
        all_out = lax.all_gather(out, axis_name)         # (n_dev, n_local, cap, d)
        all_out = all_out.reshape(n_experts, capacity, d)

        # undo routing: each kept token reads its slot from its expert
        gathered = all_out[choice, jnp.minimum(slot, capacity - 1)]
        routed = jnp.where(keep[:, None], gathered * gate_p[:, None], xl)
        return routed

    pspec = jax.tree.map(lambda _: P(axis_name), expert_params)
    # The routed output is computed identically on every device (routing is
    # a pure function of the replicated gates, and all_gather hands every
    # device the full expert-output table), but JAX's varying-axes checker
    # cannot prove replication through all_to_all/all_gather — so the VMA
    # check is disabled for this map; test_moe_expert_parallel asserts the
    # exact values instead.
    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(pspec, P(), P()),
                         out_specs=P(), check_vma=False)(expert_params,
                                                         gate_logits, x)


def load_balancing_loss(gate_logits, choice_onehot):
    """Switch/GShard auxiliary loss: n_experts * sum_e f_e * p_e, where
    f_e = fraction of routing decisions sent to expert e and p_e = mean
    gate probability of e. Minimized (=1) at a uniform assignment."""
    probs = jax.nn.softmax(gate_logits, axis=-1)
    n_experts = gate_logits.shape[-1]
    f = jnp.mean(choice_onehot.astype(probs.dtype), axis=tuple(
        range(choice_onehot.ndim - 1)))
    p = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return n_experts * jnp.sum(f * p)


def moe_apply_topk(expert_fn, expert_params, gate_logits, x, k=2, mesh=None,
                   axis_name="expert", capacity_factor=2.0):
    """Top-k MoE over expert-parallel devices.

    Same exchange as ``moe_apply`` (all_to_all dispatch over the expert
    axis) with k routing decisions per token, GShard slot priority (all
    rank-0 choices claim capacity before rank-1, ...), gate weights
    normalized over the selected experts, and the Switch auxiliary
    load-balancing loss returned alongside the output.

    Returns (out (tokens, d), aux_loss scalar).
    """
    if mesh is None:
        from .mesh import current_mesh
        mesh = current_mesh()
    n_dev = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    tokens, d = x.shape
    n_experts = gate_logits.shape[1]
    assert n_experts % n_dev == 0
    n_local = n_experts // n_dev
    capacity = max(1, int(capacity_factor * tokens * k / n_experts))

    def local_fn(params, gates, xl):
        # the repo's one router (mxtpu/ops/moe.py): softmax scores, the k
        # best, weights renormalized over the chosen
        weights, topi = route(gates, k, score_func="softmax")  # (tokens, k)

        # GShard priority: rank-0 decisions claim slots first. Build the
        # flattened decision list in rank-major order and cumsum it.
        flat_choice = topi.T.reshape(-1)                  # (k*tokens,)
        onehot = jax.nn.one_hot(flat_choice, n_experts, dtype=jnp.int32)
        slot_flat = (jnp.cumsum(onehot, axis=0) - 1)
        slot_flat = jnp.take_along_axis(
            slot_flat, flat_choice[:, None], axis=1)[:, 0]
        slot = slot_flat.reshape(k, tokens).T             # (tokens, k)
        choice = topi                                     # (tokens, k)
        keep = slot < capacity

        disp = jnp.zeros((n_experts, capacity, d), x.dtype)
        for j in range(k):
            disp = disp.at[choice[:, j],
                           jnp.minimum(slot[:, j], capacity - 1)].add(
                jnp.where(keep[:, j][:, None], xl, 0.0))

        disp = disp.reshape(n_dev, n_local, capacity, d)
        recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
        my_tokens = recv[0]                               # replicated routing
        out = jax.vmap(expert_fn)(params, my_tokens)
        all_out = lax.all_gather(out, axis_name).reshape(
            n_experts, capacity, d)

        combined = jnp.zeros_like(xl)
        any_kept = jnp.zeros((tokens,), bool)
        for j in range(k):
            got = all_out[choice[:, j],
                          jnp.minimum(slot[:, j], capacity - 1)]
            combined = combined + jnp.where(
                keep[:, j][:, None], got * weights[:, j][:, None], 0.0)
            any_kept = any_kept | keep[:, j]
        routed = jnp.where(any_kept[:, None], combined, xl)

        aux = load_balancing_loss(
            gates, jax.nn.one_hot(topi[:, 0], n_experts))
        return routed, aux

    pspec = jax.tree.map(lambda _: P(axis_name), expert_params)
    return jax.shard_map(local_fn, mesh=mesh,
                         in_specs=(pspec, P(), P()),
                         out_specs=(P(), P()), check_vma=False)(
                             expert_params, gate_logits, x)
