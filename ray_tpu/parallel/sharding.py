"""Sharding strategies: DP / FSDP / TP as sharding-spec builders.

This replaces the reference's per-strategy wrapper machinery
(/root/reference/python/ray/train/torch/train_loop_utils.py:153 prepare_model
→ DDP; :171-185 FSDP passthrough; vLLM tensor_parallel_size delegation) with
in-framework sharding rules (SURVEY.md §2.3): parameters and optimizer state
carry `jax.sharding.NamedSharding`s over the mesh; XLA inserts the collectives.

Two APIs:
- logical-axis rules (flax-style): modules annotate params with logical axis
  names; `logical_to_shardings` maps them onto mesh axes by rule table.
- shape-driven FSDP: `infer_fsdp_sharding` shards the largest divisible dim of
  every array over the fsdp axis — works for any pytree of params with zero
  model annotations (the analog of torch FSDP's parameter flattening, but
  static and compiler-visible).
"""

from __future__ import annotations

import re
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# default logical-axis rule table (megatron-style TP + fsdp weight sharding)
DEFAULT_RULES: tuple[tuple[str, str | None], ...] = (
    ("batch", "data"),
    ("fsdp_batch", ("replica", "data", "fsdp")),
    ("sequence", "context"),
    ("embed", "fsdp"),          # weight dim sharded by fsdp (zero-3 style)
    ("mlp", "tensor"),          # ffn hidden dim -> tensor parallel
    ("heads", "tensor"),        # attention heads -> tensor parallel
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("layers", None),
    ("stage", "pipeline"),
)


def rules_dict(extra: dict[str, Any] | None = None) -> dict[str, Any]:
    d = dict(DEFAULT_RULES)
    if extra:
        d.update(extra)
    return d


def spec_from_logical(logical_axes: tuple[str | None, ...],
                      rules: dict[str, Any], mesh: Mesh) -> P:
    """Map ('embed','mlp') → PartitionSpec('fsdp','tensor'), dropping mesh axes
    of size 1 (so the same model code runs on any mesh)."""
    out = []
    for ax in logical_axes:
        mapped = rules.get(ax) if ax is not None else None
        if mapped is None:
            out.append(None)
            continue
        if isinstance(mapped, str):
            mapped_axes = (mapped,)
        else:
            mapped_axes = tuple(mapped)
        mapped_axes = tuple(a for a in mapped_axes
                            if a in mesh.axis_names and mesh.shape[a] > 1)
        if not mapped_axes:
            out.append(None)
        elif len(mapped_axes) == 1:
            out.append(mapped_axes[0])
        else:
            out.append(mapped_axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_to_shardings(logical_tree, mesh: Mesh,
                         rules: dict[str, Any] | None = None):
    """Tree of logical-axis tuples → tree of NamedShardings."""
    rules = rules or rules_dict()
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, spec_from_logical(tuple(axes), rules, mesh)),
        logical_tree, is_leaf=lambda x: isinstance(x, tuple))


def infer_fsdp_sharding(params_shapes, mesh: Mesh, axis: str = "fsdp",
                        min_bytes: int = 2 ** 12):
    """Shape-driven FSDP: for each array, shard the largest dim divisible by
    the fsdp axis size; replicate small arrays (the in-framework equivalent of
    the reference's delegated FSDP/ZeRO, SURVEY.md §2.3 row 2)."""
    if axis not in mesh.axis_names or mesh.shape[axis] == 1:
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), params_shapes)
    n = mesh.shape[axis]

    def one(leaf):
        shape = getattr(leaf, "shape", None)
        if shape is None or not shape:
            return NamedSharding(mesh, P())
        size = int(np.prod(shape)) * getattr(leaf, "dtype", np.dtype("f4")).itemsize
        if size < min_bytes:
            return NamedSharding(mesh, P())
        # largest dim divisible by n wins; ties -> first
        best = -1
        best_dim = -1
        for i, d in enumerate(shape):
            if d % n == 0 and d > best_dim:
                best, best_dim = i, d
        if best < 0:
            return NamedSharding(mesh, P())
        spec = [None] * len(shape)
        spec[best] = axis
        del spec[best + 1:]  # trailing Nones are implicit
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, params_shapes)


def _path_name(path) -> str:
    """Pytree key path → a slash-joined name regex rules match against
    (dict keys and sequence indices both render: ``layers/attn/wq``)."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def match_partition_rules(rules, params):
    """Regex partition rules → tree of PartitionSpecs (the T5X/EasyLM
    idiom). ``rules`` is an ordered sequence of ``(pattern, spec)``; the
    FIRST pattern that ``re.search``-matches a leaf's slash-joined tree
    path wins. Scalars always get ``P()`` (nothing to shard); every
    non-scalar leaf must match some rule — a silent replicate-by-default
    hides typos in the rule table, so an unmatched leaf raises.

    Shared by train (``spmd.state_shardings(partition_rules=...)``) and
    serve (the TP engine's weight shardings): one implementation, one
    set of semantics for how a param name selects its layout."""
    rules = tuple((pat, spec if isinstance(spec, P) else P(*spec))
                  for pat, spec in rules)

    def get_spec(path, leaf):
        name = _path_name(path)
        if not getattr(leaf, "shape", ()):
            return P()
        for pattern, spec in rules:
            if re.search(pattern, name):
                return spec
        raise ValueError(f"partition rule not found for param: {name}")

    return jax.tree_util.tree_map_with_path(get_spec, params)


def prune_spec(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes of size 1 (or absent) from a PartitionSpec, so one
    rule table serves any mesh — the regex-rule twin of the dropping
    ``spec_from_logical`` does for logical-axis rules."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes
                     if a in mesh.axis_names and mesh.shape[a] > 1)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def rule_shardings(rules, params, mesh: Mesh):
    """``match_partition_rules`` + mesh application in one call: tree of
    params (or ShapeDtypeStructs) → tree of NamedShardings."""
    specs = match_partition_rules(rules, params)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, prune_spec(s, mesh)),
        specs, is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh, *, extra_dims: int = 0) -> NamedSharding:
    """Inputs sharded over every data-parallel axis on dim 0."""
    dp_axes = tuple(a for a in ("replica", "data", "fsdp")
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    spec = (dp_axes if dp_axes else None,) + (None,) * extra_dims
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_init(init_fn: Callable, mesh: Mesh, shardings) -> Callable:
    """Jit an init function with output shardings so parameters are created
    directly sharded (never materialized replicated — the ZeRO-init analog)."""
    return jax.jit(init_fn, out_shardings=shardings)


def num_dp_shards(mesh: Mesh) -> int:
    n = 1
    for a in ("replica", "data", "fsdp"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
