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


# ---------------------------------------------------------------------------
# serving: dropless routing, experts grouped and multiplied group by group
# ---------------------------------------------------------------------------

def route_sigmoid_top_k(g, router, bias, top_k: int, *,
                        norm_topk_prob: bool = True, scaling: float = 1.0,
                        norm_eps: float = 1e-6):
    """Sigmoid scores over ALL experts, the ``top_k`` largest of score +
    bias chosen, combine weights from the scores alone (the bias selects
    and never weighs), normalised over the chosen (their sum +
    ``norm_eps``: the published codes differ in it). g: [N, D]; router:
    [D, E]; bias: [E] float32 or None. Returns (idx [N, k] int32,
    w [N, k] float32)."""
    logits = jnp.dot(g, router, preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    sel = s if bias is None else s + bias
    _, idx = jax.lax.top_k(sel, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), w * scaling


def route_softmax_top_k(g, router, top_k: int, *,
                        norm_topk_prob: bool = True):
    """Softmax over ALL experts in float32, the ``top_k`` most probable
    chosen, combine weights their probabilities, renormalised over the
    chosen where ``norm_topk_prob``. g: [N, D]; router: [D, E]. Returns
    (idx [N, k] int32, w [N, k] float32)."""
    logits = jnp.dot(g, router, preferred_element_type=jnp.float32)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def _tile(n: int, cap: int) -> int:
    """The whole dimension, or the largest ``n / 2**j`` under ``cap``."""
    while n > cap and n % 2 == 0:
        n //= 2
    return n


def grouped_swiglu(xs, w_gate, w_up, w_down, sizes):
    """SwiGLU of rows sorted by expert: rows ``sum(sizes[:e])`` ..
    ``sum(sizes[:e + 1])`` of xs [M, D] go through expert e's weights
    (w_gate / w_up [E, D, F], w_down [E, F, D]).

    Three calls of the Pallas grouped matmul ``gmm`` (jax.experimental.
    pallas.ops.tpu.megablox), under the scope ``grouped_ffn``: it walks
    (row tile, group) pairs, so an expert's weights are read once a row
    tile that holds rows of it (once a call at decode widths) and an
    expert with no row is never read. Chosen over ``jax.lax.ragged_dot``
    by measurement on a v5e at the published widths (PERF.md section 6,
    PR 35: 1.01 ms against 1.99 at 256 rows, 1.30 against 3.08 at 2,048).
    Rows are padded to whole row tiles; rows past ``sum(sizes)`` (the
    padding, picks of experts held elsewhere) are never visited and come
    out as whatever memory held: the caller leaves them out. Off the TPU
    the kernel runs interpreted. Returns [M, D] float32."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.ops import paged_attention as paged_ops

    m = xs.shape[0]
    tm = min(128, -(-m // 16) * 16)
    xs = jnp.pad(xs, ((0, -m % tm), (0, 0)))
    interpret = paged_ops.interpret_default()   # one rule for every kernel

    def product(a, w, dtype):
        return gmm(a, w, sizes, preferred_element_type=dtype,
                   tiling=(tm, _tile(w.shape[1], 2048),
                           _tile(w.shape[2], 1024)), interpret=interpret)

    with jax.named_scope("grouped_ffn"):
        gate = product(xs, w_gate, xs.dtype)
        up = product(xs, w_up, xs.dtype)
        return product(jax.nn.silu(gate) * up, w_down, jnp.float32)[:m]


def expert_share(g, idx, w, experts: dict, held: range):
    """The part of a routed layer's output that the experts ``held`` give.

    g [N, D] the normed tokens; idx / w [N, k] the routing over ALL
    experts (:func:`route_sigmoid_top_k` or :func:`route_softmax_top_k`:
    the router is counted once, outside); experts: w_gate / w_up [len(held), D, F], w_down
    [len(held), F, D]: only the held experts' weights. Dropless: every
    (token, expert) pick with the expert in ``held`` is computed, however
    many land on one expert. Picks are sorted by expert, multiplied group
    by group, put back in token order and summed with their weights; picks
    of experts held elsewhere sort last and are left out of the sum.
    The shares of a partition of the experts add up to the whole layer.
    Returns [N, D] float32."""
    n, k = idx.shape
    n_held = len(held)
    local = idx - held.start
    mine = (local >= 0) & (local < n_held)
    key = jnp.where(mine, local, n_held).reshape(-1)              # [N*k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    ys = grouped_swiglu(g[order // k], experts["w_gate"], experts["w_up"],
                        experts["w_down"], sizes)                 # [N*k, D]
    back = jnp.argsort(order)
    picks = ys[back].reshape(n, k, -1)
    return jnp.sum(jnp.where(mine[..., None], picks * w[..., None], 0.0),
                   axis=1)
