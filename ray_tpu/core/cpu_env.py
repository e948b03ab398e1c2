"""Keep a process off the TPU: the CPU JAX backend, chosen by environment.

The stock libtpu honours ``JAX_PLATFORMS``: with ``cpu`` first, jax never
opens the chips, so a CPU-pool worker (or the multichip dryrun's virtual
mesh child) cannot take them from the one process that needs them.

Kept in a leaf module with no jax import so callers can build a child's
environment before jax is ever touched in the parent.
"""

from __future__ import annotations

import os
from typing import Mapping, MutableMapping, Optional


def force_cpu_env(env: MutableMapping[str, str]) -> MutableMapping[str, str]:
    """Mutate ``env`` in place so a child can only initialize the CPU
    backend (forced, not setdefault: the ambient value may name the TPU)."""
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    return env


def first_platform(env: Optional[Mapping[str, str]] = None) -> str:
    """The platform jax tries first in a process started from ``env``
    (default: this one's): the head of ``JAX_PLATFORMS``, "" if unset."""
    if env is None:
        env = os.environ
    return (env.get("JAX_PLATFORMS") or "").split(",")[0]


def pinned_to_cpu(env: Optional[Mapping[str, str]] = None) -> bool:
    """Whether such a process has jax held to the CPU — it can neither use
    nor take a chip."""
    return first_platform(env) == "cpu"
