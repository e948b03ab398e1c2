"""SPMD training step: sharded init + jitted update over an ICI×DCN mesh.

This is the in-framework replacement for the reference's delegated training
step machinery (torch DDP wrap at
/root/reference/python/ray/train/torch/train_loop_utils.py:153, FSDP
passthrough :171-185, DeepSpeed examples): instead of wrapping a module with a
communication library, parameters/optimizer state carry `NamedSharding`s over
the mesh and `jax.jit` emits the collectives (grad psum over data axes,
all-gather/reduce-scatter for fsdp) on ICI.

Design notes (TPU-first):
- params are initialized *directly sharded* (`jit` with out_shardings) so an
  8B model never materializes replicated on one host;
- the step donates the previous state, so param+opt memory is reused in-place;
- loss/grad math runs in the model dtype (bf16) with fp32 accumulation where
  the model chooses; the optimizer state is fp32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import (
    batch_sharding,
    infer_fsdp_sharding,
    logical_to_shardings,
    replicated,
    rule_shardings,
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Minimal train state pytree (params + optimizer + step counter)."""

    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer: optax.GradientTransformation):
        return cls(params=params, opt_state=optimizer.init(params),
                   step=jnp.zeros((), jnp.int32))


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      decay_steps: int = 10_000,
                      grad_clip: float = 1.0,
                      name: str = "adamw") -> optax.GradientTransformation:
    """Optimizer + cosine schedule + global-norm clip.

    name="adamw" is the Llama-pretrain recipe the BASELINE configs assume;
    name="adafactor" is the TPU-native memory saver (factored second moment
    — T5/PaLM recipe): adam's fp32 m+v cost 8 bytes/param (12 GB for 1.5B,
    most of a v5e chip's HBM), adafactor's factored state is ~0 — the
    difference between OOM and headroom for remat policies / larger models
    on one chip."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(decay_steps, warmup_steps + 1))
    if name == "adafactor":
        # NO weight decay here: optax.adafactor's weight_decay_rate is NOT
        # learning-rate-scaled (0.1 would shrink params 10% per step) —
        # the T5/PaLM adafactor recipe trains without decoupled decay
        return optax.chain(
            optax.clip_by_global_norm(grad_clip),
            optax.adafactor(sched, min_dim_size_to_factor=128),
        )
    if name != "adamw":
        raise ValueError(f"unknown optimizer {name!r} (adamw | adafactor)")
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def state_shardings(params_logical_axes, params_shape, mesh,
                    optimizer: optax.GradientTransformation,
                    rules: dict | None = None,
                    partition_rules=None):
    """Shardings for a full TrainState.

    Param shardings come from ONE of three sources, in priority order:
    regex ``partition_rules`` ((pattern, PartitionSpec) pairs matched
    against slash-joined param paths via the shared
    ``parallel.sharding.match_partition_rules`` — the same machinery the
    TP serving engine uses), logical-axis annotations, or shape-driven
    FSDP inference. Optimizer state shards like the params it mirrors
    (adam mu/nu are param-shaped); scalars/schedules replicate.
    """
    if partition_rules is not None:
        p_sh = rule_shardings(partition_rules, params_shape, mesh)
    elif params_logical_axes is not None:
        p_sh = logical_to_shardings(params_logical_axes, mesh, rules)
    else:
        p_sh = infer_fsdp_sharding(params_shape, mesh)

    # Build optimizer state shape via eval_shape, then map param-shaped leaves
    # to the matching param sharding and everything else to replicated.
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    flat_params, _ = jax.tree_util.tree_flatten(params_shape)
    flat_sh, _ = jax.tree_util.tree_flatten(
        p_sh, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding))
    by_shape = {}
    for leaf, sh in zip(flat_params, flat_sh):
        by_shape.setdefault((tuple(leaf.shape), jnp.dtype(leaf.dtype).name), sh)

    def opt_leaf(leaf):
        key = (tuple(getattr(leaf, "shape", ())),
               jnp.dtype(getattr(leaf, "dtype", jnp.float32)).name)
        return by_shape.get(key, replicated(mesh))

    # A param-shaped opt leaf gets the param's sharding only if shapes match
    # one-to-one; collisions fall back to replicated-safe behavior above.
    opt_sh = jax.tree.map(opt_leaf, opt_shape)
    return TrainState(params=p_sh, opt_state=opt_sh,
                      step=replicated(mesh))


def sharded_create_state(init_params_fn: Callable[[], Any],
                         optimizer: optax.GradientTransformation,
                         mesh, params_logical_axes=None,
                         rules: dict | None = None,
                         partition_rules=None) -> tuple[TrainState, Any]:
    """Initialize a TrainState directly sharded on the mesh (ZeRO-style init:
    no replicated materialization). Returns (state, state_shardings)."""
    params_shape = jax.eval_shape(init_params_fn)
    sh = state_shardings(params_logical_axes, params_shape, mesh, optimizer,
                         rules, partition_rules)

    def init():
        params = init_params_fn()
        return TrainState.create(params, optimizer)

    state = jax.jit(init, out_shardings=sh)()
    return state, sh


def make_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    mesh, sh: TrainState, *, donate: bool = True):
    """Build the jitted SPMD train step.

    loss_fn(params, batch) -> scalar loss.
    Returns step(state, batch) -> (state, metrics dict).
    """
    b_sh = batch_sharding(mesh)

    def step(state: TrainState, batch):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        # a profiler trace names the update's device ops by this scope (the
        # model's own, embed / norm / attn / mlp / lm_head / loss, are in
        # models/llama.py)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        new = TrainState(params=params, opt_state=opt_state,
                         step=state.step + 1)
        return new, {"loss": loss, "grad_norm": gnorm, "step": new.step}

    in_batch = jax.tree.map(lambda _: b_sh, jax.tree.structure((0,)))
    del in_batch  # batch sharding applied via in_shardings below
    return jax.jit(
        step,
        in_shardings=(sh, None),
        out_shardings=(sh, None),
        donate_argnums=(0,) if donate else (),
    )


def shard_batch(batch, mesh):
    """Device-put a host batch sharded over the data axes (dim 0)."""
    b_sh = batch_sharding(mesh)

    def put(x):
        extra = getattr(x, "ndim", 1) - 1
        sh = batch_sharding(mesh, extra_dims=extra)
        return jax.device_put(x, sh)

    return jax.tree.map(put, batch)


def make_mesh(n_devices: int | None = None, devices=None,
              **spec_kw) -> jax.sharding.Mesh:
    """Convenience: infer a MeshSpec over the visible devices and build it."""
    if devices is None:
        devices = jax.devices()
    n = n_devices or len(devices)
    spec = MeshSpec.infer(n, **spec_kw)
    return build_mesh(spec, devices[:n])
