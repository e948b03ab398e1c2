"""ray_tpu.util — utility APIs (reference: python/ray/util/)."""

from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.queue import Empty, Full, Queue

_PROFILING = ("annotate", "profile_trace", "save_device_memory_profile")

__all__ = ["ActorPool", "Empty", "Full", "Queue", *_PROFILING]


def __getattr__(name: str):
    # the profiling helpers live in ray_tpu.observability.profiling, which
    # imports ray_tpu.util.metrics: resolved on first use, so that either
    # module can be the first one imported
    if name in _PROFILING:
        from ray_tpu.observability import profiling
        return getattr(profiling, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
