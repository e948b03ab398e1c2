"""Expert parallelism: MoE dispatch/combine with all-to-all over the expert axis.

The reference has no in-tree MoE execution — the BASELINE "Mixtral 8×7B MoE
expert-parallel across Ray actors" config must be built natively (SURVEY.md
§2.3 row EP). Design: experts are sharded over the mesh "expert" axis; tokens
are routed top-k with capacity buckets (Switch/GShard style: static shapes, so
XLA tiles the expert matmuls on the MXU), and `lax.all_to_all` moves token
buckets token-shard↔expert-shard over ICI.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def top_k_gating(gate_logits, k: int):
    """Top-k gate probs/indices, renormalized over the chosen experts.
    gate_logits: [T, E] → (probs [T,k], idx [T,k])."""
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i


def _dispatch_masks(top_i, top_p, num_experts: int, capacity: int):
    """Build combine/dispatch tensors [T, E, C] from top-k choices
    (GShard-style position-in-expert bucketing; overflow tokens drop)."""
    t, k = top_i.shape
    combine = jnp.zeros((t, num_experts, capacity), jnp.float32)
    counts = jnp.zeros((num_experts,), jnp.int32)
    for slot in range(k):
        oh = jax.nn.one_hot(top_i[:, slot], num_experts, dtype=jnp.int32)  # [T,E]
        pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh  # [T,E]
        pos_t = jnp.sum(pos * oh, axis=1)  # [T] position within chosen expert
        keep = pos_t < capacity
        pos_oh = jax.nn.one_hot(pos_t, capacity, dtype=jnp.float32) * keep[:, None]
        combine = combine + (top_p[:, slot][:, None, None]
                             * oh[:, :, None] * pos_oh[:, None, :])
        counts = counts + jnp.sum(oh * keep[:, None], axis=0)
    dispatch = combine > 0
    return combine, dispatch


def moe_layer(x, gate_w, expert_fn: Callable, expert_params, mesh: Mesh, *,
              axis_name: str = "expert", num_experts: int, top_k: int = 2,
              capacity_factor: float = 1.5):
    """Mixture-of-experts layer with expert parallelism.

    x: [B, S, D] (replicated or data-sharded over other axes)
    gate_w: [D, E] router weights (replicated)
    expert_params: pytree with leading dim E, sharded P(axis_name) — each
        device holds E/n experts.
    expert_fn(params_one_expert, tokens [N, D]) -> [N, D]
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    n_tok = b * s
    capacity = max(1, int(n_tok * capacity_factor * top_k / num_experts))

    gate_logits = tokens @ gate_w  # [T, E]
    top_p, top_i = top_k_gating(gate_logits, top_k)
    combine, dispatch = _dispatch_masks(top_i, top_p, num_experts, capacity)

    # [T,E,C] x [T,D] -> [E,C,D]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), tokens)

    if axis_name in mesh.axis_names and mesh.shape[axis_name] > 1:
        n = mesh.shape[axis_name]
        e_local = num_experts // n

        def sharded(expert_in, expert_params):
            # expert_in arrives token-replicated [E, C, D]; keep only local
            # experts' buckets — no all_to_all needed when tokens replicated.
            idx = jax.lax.axis_index(axis_name)
            local = jax.lax.dynamic_slice_in_dim(expert_in, idx * e_local,
                                                 e_local, axis=0)
            out = jax.vmap(expert_fn)(
                jax.tree.map(lambda p: p, expert_params), local)  # [e_local, C, D]
            # gather all experts' outputs back (all-gather over expert axis)
            full = jax.lax.all_gather(out, axis_name, axis=0, tiled=True)
            return full  # [E, C, D]

        param_specs = jax.tree.map(lambda _: P(axis_name), expert_params)
        expert_out = shard_map(
            sharded, mesh=mesh, in_specs=(P(), param_specs), out_specs=P(),
            check_vma=False)(expert_in, expert_params)
    else:
        expert_out = jax.vmap(expert_fn)(expert_params, expert_in)

    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return out.reshape(b, s, d)


def moe_layer_tokens_sharded(x, gate_w, expert_fn: Callable, expert_params,
                             mesh: Mesh, *, axis_name: str = "expert",
                             num_experts: int, top_k: int = 2,
                             capacity_factor: float = 1.5):
    """MoE with tokens ALSO sharded over the expert axis (the scalable form):
    each device routes its token shard, then a ragged `all_to_all` exchanges
    token buckets for expert shards — this is the ICI-native analog of the
    reference delegating MoE to per-actor NCCL groups."""
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        return moe_layer(x, gate_w, expert_fn, expert_params, mesh,
                         axis_name=axis_name, num_experts=num_experts,
                         top_k=top_k, capacity_factor=capacity_factor)
    n = mesh.shape[axis_name]
    e_local = num_experts // n

    def sharded(x_local, gate_w, expert_params):
        b, s, d = x_local.shape
        tokens = x_local.reshape(b * s, d)
        n_tok = b * s
        capacity = max(1, int(n_tok * capacity_factor * top_k / num_experts))
        gate_logits = tokens @ gate_w
        top_p, top_i = top_k_gating(gate_logits, top_k)
        combine, dispatch = _dispatch_masks(top_i, top_p, num_experts, capacity)
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x_local.dtype), tokens)
        # [E, C, D] -> split expert dim across devices, concat bucket dim:
        # result [E/n, n*C, D]: local experts' buckets from every token shard
        ein = jax.lax.all_to_all(expert_in, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
        out = jax.vmap(expert_fn)(expert_params, ein)  # [E/n, n*C, D]
        # reverse exchange: [E/n, n*C, D] -> [E, C, D] (local tokens' results)
        eout = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True)
        res = jnp.einsum("tec,ecd->td", combine.astype(x_local.dtype), eout)
        return res.reshape(b, s, d)

    param_specs = jax.tree.map(lambda _: P(axis_name), expert_params)
    return shard_map(
        sharded, mesh=mesh,
        in_specs=(P(axis_name), P(), param_specs), out_specs=P(axis_name),
        check_vma=False)(x, gate_w, expert_params)
