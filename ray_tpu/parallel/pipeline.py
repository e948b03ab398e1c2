"""Pipeline parallelism: GPipe-style microbatched execution over a mesh axis.

The reference has no in-tree training pipeline parallelism — it delegates to
vLLM's pipeline_parallel_size for serving (SURVEY.md §2.3 row PP). Here PP is
native: layers are grouped into S stages whose parameters live on the
"pipeline" mesh axis; activations flow stage→stage with `lax.ppermute` inside a
`shard_map`, and jax autodiff differentiates straight through the permute (the
backward pass is the reverse ring) — no hand-written send/recv schedule.

Schedule: GPipe with M microbatches over S stages, M + S - 1 ticks. Bubble
fraction (S-1)/(M+S-1) — pick M >= 4·S. The stage loop is a `lax.fori_loop`,
so the program is O(1) in compiled size regardless of M.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(init_fn: Callable, n_stages: int, rng, *args):
    """Init per-stage params with a leading stage dim: vmap over stage index.
    ``init_fn(rng, stage_idx, *args) -> params`` pytree."""
    rngs = jax.random.split(rng, n_stages)
    return jax.vmap(lambda r, i: init_fn(r, i, *args))(rngs, jnp.arange(n_stages))


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh, *,
                   axis_name: str = "pipeline", num_microbatches: int | None = None):
    """Run ``x`` through S pipeline stages.

    stage_fn(params_slice, microbatch) -> microbatch (same shape/dtype)
    stage_params: pytree with leading dim S, sharded P(axis_name, ...)
    x: [batch, ...] — batch is split into M microbatches.
    """
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        one = jax.tree.map(lambda p: p[0], stage_params)
        return stage_fn(one, x)
    n_stages = mesh.shape[axis_name]
    m = num_microbatches or (4 * n_stages)
    batch = x.shape[0]
    if batch % m != 0:
        raise ValueError(f"batch {batch} not divisible by {m} microbatches")
    mb = batch // m
    xs = x.reshape(m, mb, *x.shape[1:])

    def sharded(params, xs):
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        stage = jax.lax.axis_index(axis_name)
        # send each stage's output to the next; the wrap-around edge carries
        # garbage that stage 0 ignores (it reads fresh microbatches)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        out_buf = jnp.zeros_like(xs)
        state = jnp.zeros_like(xs[0])

        def tick(t, carry):
            state, out_buf = carry
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, m - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, mb_in, state)
            out = stage_fn(params, inp)
            # last stage writes microbatch t-(S-1) when valid
            write_idx = t - (n_stages - 1)
            do_write = (stage == n_stages - 1) & (write_idx >= 0)
            out_buf = jax.lax.cond(
                do_write,
                lambda b: jax.lax.dynamic_update_index_in_dim(
                    b, out, jnp.maximum(write_idx, 0), axis=0),
                lambda b: b, out_buf)
            state = jax.lax.ppermute(out, axis_name, perm)
            return state, out_buf

        _, out_buf = jax.lax.fori_loop(0, m + n_stages - 1, tick, (state, out_buf))
        # only the last stage holds real outputs; broadcast over the axis
        out_buf = jnp.where(stage == n_stages - 1, out_buf, 0.0)
        return jax.lax.psum(out_buf, axis_name)

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    out = shard_map(
        sharded, mesh=mesh, in_specs=(param_specs, P()), out_specs=P(),
        check_vma=False)(stage_params, xs)
    return out.reshape(batch, *x.shape[1:])
