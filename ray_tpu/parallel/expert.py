"""Expert parallelism: MoE dispatch/combine with all-to-all over the expert axis.

The reference has no in-tree MoE execution — the BASELINE "Mixtral 8×7B MoE
expert-parallel across Ray actors" config must be built natively (SURVEY.md
§2.3 row EP). Design: experts are sharded over the mesh "expert" axis; tokens
are routed top-k with capacity buckets (Switch/GShard style: static shapes, so
XLA tiles the expert matmuls on the MXU), and `lax.all_to_all` moves token
buckets token-shard↔expert-shard over ICI.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _grouped():
    """``ray_tpu/ops/grouped_matmul.py``, at first use: it pulls Pallas in
    (1.1 s of imports), and a process that imports a model to read its
    configuration (a driver, a proxy, the benchmark's parent) multiplies
    nothing."""
    from ray_tpu.ops import grouped_matmul
    return grouped_matmul


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


def _columns(k: int, n: int) -> int:
    """Columns of one grid step of a product [rows, k] x [k, n]: all n, or
    the largest ``n / 2**j`` that keeps the matrix's tile at 2,048 x 1,024
    numbers (two of them are in flight) and is still whole lane tiles of
    128. The kernel keeps the WHOLE of k in one tile, so a k that leaves
    no such width (above 16,384, or an n that cannot be halved that far:
    no family served here comes near) is refused and not run over the
    VMEM's budget."""
    tn = n
    while k * tn > 2048 * 1024 and tn % 256 == 0:
        tn //= 2
    if k * tn > 2048 * 1024:
        raise ValueError(
            f"a grouped product of [{k}, {n}] matrices finds no column tile "
            f"of whole 128s under {2048 * 1024} numbers: the kernel does "
            "not tile k")
    return tn


def product_visits(sizes, m: int):
    """The times ONE grouped product of a call of ``m`` rows passes an
    expert's matrix through the MXU, from the rows each expert got: sizes
    [..., E] (numpy or jax; leading dimensions are calls of m rows each
    and are summed). A touched expert is one, and one more for each
    further chunk of its rows (``grouped.chunk_rows``: 128 at any width a
    cell runs)."""
    grouped = _grouped()
    c = grouped.chunk_rows(grouped.aligned_rows(m, sizes.shape[-1]))
    return (-(-sizes // c)).sum()


def grouped_swiglu(xs, w_gate, w_up, w_down, sizes, interpret=None):
    """SwiGLU of rows grouped by expert: xs [A, D] in the ALIGNED layout
    (``grouped.aligned_order``: expert e's ``sizes[e]`` rows from
    ``sum(ceil(sizes[:e] / 16) * 16)`` on) go through expert e's weights
    (w_gate / w_up [E, D, F], w_down [E, F, D]).

    Two calls of the Pallas grouped matmul of ``ray_tpu/ops/
    grouped_matmul.py`` under the scope ``grouped_ffn``: gate, up and the
    activation in the first (both matrices of an expert in one grid step,
    its rows read once, the gated rows never in HBM as two halves), down
    in the second. Each walks the experts that HAVE rows, one grid step an
    expert, so an expert's weights are read once a call and pass through
    the MXU once (once more for each further 128 rows of it), an expert
    with no row is never read, and no row moves that is no expert's. The
    megablox ``gmm`` it replaced (PR 35 chose that over
    ``jax.lax.ragged_dot``: 1.01 ms against 1.99 at 256 rows) read the
    weights once too, but pushed them through the MXU again, the memory
    idle, wherever an expert's rows crossed one of its row tiles of 128:
    nearly every tile's edge at SDAR's 32 and 16 rows an expert, one edge
    in thirty at LFM2's and JoyAI's decode widths (PERF.md section 6, "PR
    49 and PR 50", has every shape's reading on a v5e). Off the TPU the
    kernel runs interpreted (``interpret``: None asks the one rule every
    kernel asks). Returns [A, D] float32 in the same layout; rows of no
    expert (the padding, picks of experts held elsewhere) are never
    visited and come out as whatever memory held: the caller leaves them
    out."""
    if interpret is None:
        from ray_tpu.ops import paged_attention as paged_ops
        interpret = paged_ops.interpret_default()
    grouped = _grouped()
    d, f = w_gate.shape[1:]
    how = dict(c=grouped.chunk_rows(xs.shape[0]), interpret=interpret)
    with jax.named_scope("grouped_ffn"):
        walk, n = grouped.walk_of(sizes)
        h = grouped.grouped_matmul(
            xs, w_gate, walk, n, tn=_columns(d, f), out_dtype=xs.dtype,
            rhs_up=w_up, **how)
        return grouped.grouped_matmul(
            h, w_down, walk, n, tn=_columns(f, d), out_dtype=jnp.float32,
            **how)


@functools.partial(jax.jit, static_argnames=("held", "interpret"))
def _share(g, idx, w, w_gate, w_up, w_down, *, held, interpret):
    """:func:`expert_share`'s body. Jitted with the layer's weights as
    OPERANDS: a program that walks its layers calls it once a routed layer
    and pass, and the whole share (sort, gathers, both kernels, the sum)
    is then traced once a process and lowered once a program (PR 45's
    lesson)."""
    n, k = idx.shape
    n_held = len(held)
    local = idx - held.start
    mine = (local >= 0) & (local < n_held)
    key = jnp.where(mine, local, n_held).reshape(-1)              # [N*k]
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                    dtype=jnp.int32)
    take, lie = _grouped().aligned_order(key, sizes)
    ys = grouped_swiglu(g[jnp.minimum(take, n * k - 1) // k], w_gate, w_up,
                        w_down, sizes, interpret)                 # [A, D]
    picks = ys[lie].reshape(n, k, -1)
    return jnp.sum(jnp.where(mine[..., None], picks * w[..., None], 0.0),
                   axis=1)


def expert_share(g, idx, w, experts: dict, held: range):
    """The part of a routed layer's output that the experts ``held`` give.

    g [N, D] the normed tokens; idx / w [N, k] the routing over ALL
    experts (:func:`route_sigmoid_top_k` or :func:`route_softmax_top_k`:
    the router is counted once, outside); experts: w_gate / w_up [len(held), D, F], w_down
    [len(held), F, D]: only the held experts' weights. Dropless: every
    (token, expert) pick with the expert in ``held`` is computed, however
    many land on one expert. ONE stable sort lays the picks out by expert,
    every expert's on a multiple of 16 rows (the padding rides in the sort
    as picks of its own: ``grouped.aligned_order``); they are multiplied
    expert by expert (:func:`grouped_swiglu`: every expert that has a pick
    is one grid step of each kernel, whatever the call's width), put back
    in token order and summed with their weights; picks of experts held
    elsewhere sort last, are never visited and are left out of the sum.
    The shares of a partition of the experts add up to the whole layer.
    Returns [N, D] float32."""
    from ray_tpu.ops import paged_attention as paged_ops

    return _share(g, idx, w, experts["w_gate"], experts["w_up"],
                  experts["w_down"], held=held,
                  interpret=paged_ops.interpret_default())  # one rule for every kernel
