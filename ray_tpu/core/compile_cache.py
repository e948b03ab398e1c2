"""Where JAX's persistent compilation cache lives.

One rule for every process that compiles — test runs, benches, the chip
smoke, worker subprocesses: ``JAX_COMPILATION_CACHE_DIR`` from outside
wins and nothing else is set in code; otherwise the cache is a fixed
directory inside the checkout (``.jax_cache/<platform>``, git-ignored), so
two runs from the same tree share compiled programs. Never ``/tmp``, a pid
or a timestamp. The platform subdirectory (the first entry of
``JAX_PLATFORMS``) keeps CPU test entries apart from what a chip run reads.

No jax import here: a parent builds a child's environment with
``configure(env)`` before jax is ever touched.
"""

from __future__ import annotations

import os
import sys
from typing import MutableMapping, Optional

from ray_tpu.core.config import package_parent_path
from ray_tpu.core.cpu_env import first_platform

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def configure(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Make ``env`` (default: this process's environment) name a cache
    directory and return it. Call before the process that compiles starts;
    for this process, before importing jax where possible — if jax is
    already imported and the directory was defaulted here, jax's config is
    pointed at it too."""
    own = env is None
    if own:
        env = os.environ
    path = env.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(package_parent_path(), ".jax_cache",
                        first_platform(env) or "default")
    env[ENV_VAR] = path
    if own and "jax" in sys.modules:
        # jax read the (then unset) variable at import
        sys.modules["jax"].config.update("jax_compilation_cache_dir", path)
    return path
