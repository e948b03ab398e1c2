"""Ring attention: context-parallel exact attention over the ICI ring.

The reference ships NO sequence/context parallelism (SURVEY.md §5.7 — verified
absent; long context is delegated to vLLM/user code). Per the parity
requirement this framework implements it natively: the sequence is sharded over
the mesh "context" axis; each device holds a Q/K/V shard and K/V blocks rotate
around the ring with `ppermute` while a streaming-softmax accumulator builds
exact attention (blockwise attention à la Ring Attention, Liu et al.).

The per-block step is `_block_attn` below: two einsums and a softmax that XLA
compiles (not the Pallas flash kernels of `ray_tpu.ops.attention`, which this
module does not call); XLA schedules the ppermute asynchronously against the
next block's matmuls.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, *, q_offset, k_offset, causal, sm_scale):
    """One (q-shard × kv-block) attention contribution with streaming-softmax
    stats. Shapes: q [B,Tq,H,D], k/v [B,Tk,H,D]. Returns (out, m, l)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sm_scale  # [B,H,Tq,Tk]
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B,H,Tq]
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)  # [B,H,Tq]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)  # [B,Tq,H,D]
    return out, m_safe, l, jnp.isfinite(m)


def _merge(acc, new):
    """Streaming-softmax merge of two partial attention results."""
    o1, m1, l1, any1 = acc
    o2, m2, l2, any2 = new
    m = jnp.maximum(jnp.where(any1, m1, -jnp.inf), jnp.where(any2, m2, -jnp.inf))
    m_safe = jnp.where(any1 | any2, m, 0.0)
    c1 = jnp.where(any1, jnp.exp(m1 - m_safe), 0.0)
    c2 = jnp.where(any2, jnp.exp(m2 - m_safe), 0.0)
    l = l1 * c1 + l2 * c2
    o = o1 * c1.transpose(0, 2, 1)[..., None] + o2 * c2.transpose(0, 2, 1)[..., None]
    return o, m_safe, l, any1 | any2


def _ring_attention_sharded(q, k, v, *, axis_name: str, causal: bool,
                            sm_scale: float, block_fn: Callable):
    """Runs inside shard_map: q/k/v are the local sequence shards."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]  # kv rotates to the next device

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1]), 0.0, jnp.float32)  # [B,H,Tq]
    l0 = jnp.zeros_like(m0)
    any0 = jnp.zeros(m0.shape, bool)

    def step(i, carry):
        acc, kv = carry
        k_blk, v_blk = kv
        src = (idx - i) % n  # whose kv block we currently hold
        new = block_fn(q, k_blk, v_blk,
                       q_offset=idx * t_local, k_offset=src * t_local,
                       causal=causal, sm_scale=sm_scale)
        acc = _merge(acc, new)
        kv = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
        return acc, kv

    (o, m, l, anyv), _ = jax.lax.fori_loop(
        0, n, step, ((o0, m0, l0, any0), (k, v)))
    l_safe = jnp.where(l > 0, l, 1.0)
    out = o / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis_name: str = "context",
                   causal: bool = True, sm_scale: float | None = None,
                   block_fn: Callable | None = None):
    """Exact attention with the sequence sharded over ``axis_name``.

    q/k/v: [batch, seq, heads, head_dim], seq sharded over the context axis.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if block_fn is None:
        block_fn = _block_attn
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        out, m, l, anyv = _block_attn(q, k, v, q_offset=0, k_offset=0,
                                      causal=causal, sm_scale=sm_scale)
        l_safe = jnp.where(l > 0, l, 1.0)
        return (out / l_safe.transpose(0, 2, 1)[..., None]).astype(q.dtype)
    seq_spec = P(None, axis_name, None, None)
    fn = functools.partial(_ring_attention_sharded, axis_name=axis_name,
                           causal=causal, sm_scale=sm_scale, block_fn=block_fn)
    return shard_map(
        fn, mesh=mesh, in_specs=(seq_spec, seq_spec, seq_spec),
        out_specs=seq_spec, check_vma=False)(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis_name: str = "context",
                      causal: bool = True, sm_scale: float | None = None,
                      attn_fn: Callable | None = None):
    """Ulysses/DeepSpeed-style sequence parallelism: all-to-all re-shards
    sequence ↔ heads so each device runs full-sequence attention on a head
    subset, then re-shards back (SURVEY.md §5.7 alternative form). Requires
    heads % context_size == 0."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5

    def full_attn(q, k, v):
        out, m, l, anyv = _block_attn(q, k, v, q_offset=0, k_offset=0,
                                      causal=causal, sm_scale=sm_scale)
        l_safe = jnp.where(l > 0, l, 1.0)
        return (out / l_safe.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        return full_attn(q, k, v)

    if attn_fn is None:
        attn_fn = full_attn

    def inner(q, k, v):
        # [B, T/n, H, D] --a2a--> [B, T, H/n, D]
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                      tiled=True)

        out = attn_fn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
        return heads_to_seq(out)

    seq_spec = P(None, axis_name, None, None)
    return shard_map(inner, mesh=mesh, in_specs=(seq_spec,) * 3,
                         out_specs=seq_spec, check_vma=False)(q, k, v)
