"""Test fixtures.

Mirrors the reference's conftest keystones
(/root/reference/python/ray/tests/conftest.py — ray_start_regular:590,
ray_start_cluster:680): a single-node runtime fixture and an in-process
multi-node Cluster fixture. JAX tests run on a virtual 8-device CPU mesh
(SURVEY.md §4: keep everything runnable CPU-only).
"""

import os

# Must be set before jax import anywhere in the test process.
os.environ["JAX_PLATFORMS"] = "cpu"  # force: ambient env may say otherwise
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the jit-heavy suites (parallel, train,
# serve_llm, rllib) spend most of their wall time compiling the same tiny
# programs every run; cache them across files, runs AND worker subprocesses
# (the environment inherits). The directory is the package's one rule
# (ray_tpu/core/compile_cache.py): JAX_COMPILATION_CACHE_DIR from outside,
# else <checkout>/.jax_cache/cpu. The reference keeps suite time down with
# long-lived shared clusters (conftest.py:590) — this is the JAX-native
# equivalent lever.
from ray_tpu.core import compile_cache  # noqa: E402

compile_cache.configure()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import pytest  # noqa: E402

# The suite must be unable to hang: any bare get()/wait() that would block
# forever raises in minutes instead (inherited by worker subprocesses).
os.environ.setdefault("RAY_TPU_BLOCKING_WATCHDOG_S", "300")

# Hang forensics. The blocking watchdog covers get()/wait(); a deadlock on
# a raw Lock/Condition it cannot see. Arm a per-test stack-dump timer: any
# test stuck longer than PER_TEST_HANG_DUMP_S dumps EVERY thread's stack
# and aborts the run — a silent futex park becomes a diagnosable failure.
# SIGUSR1 dumps stacks on demand for a live run (kill -USR1 <pytest pid>).
import faulthandler  # noqa: E402
import signal  # noqa: E402

PER_TEST_HANG_DUMP_S = float(os.environ.get("PER_TEST_HANG_DUMP_S", "480"))
# A REAL file, not sys.stderr: under pytest's fd-level capture a default
# dump lands in the per-test capture tempfile and vanishes with the process.
HANG_DUMP_PATH = os.environ.get("HANG_DUMP_PATH", "/tmp/ray_tpu_hang_dump.txt")
_hang_dump_file = open(HANG_DUMP_PATH, "a")  # noqa: SIM115 — lives forever
try:
    faulthandler.register(signal.SIGUSR1, all_threads=True,
                          file=_hang_dump_file)
except (AttributeError, ValueError):  # non-main thread / unsupported
    pass

# Custom watchdog instead of faulthandler.dump_traceback_later: that caps
# the dump at 100 threads and the suite accumulates several hundred daemon
# threads — the main thread and the actual lock holder land in the
# truncated tail. This dumper names every thread and has no cap.
import sys  # noqa: E402
import threading as _threading  # noqa: E402
import traceback as _traceback  # noqa: E402

_watchdog_timer = None


def _dump_all_threads_and_exit(nodeid: str):
    names = {t.ident: t.name for t in _threading.enumerate()}
    f = _hang_dump_file
    f.write(f"\n!!! HANG ({PER_TEST_HANG_DUMP_S:.0f}s) in {nodeid}\n")
    for tid, frame in sys._current_frames().items():
        f.write(f"\n--- thread {names.get(tid, '?')} ({tid})\n")
        f.write("".join(_traceback.format_stack(frame)))
    f.flush()
    os._exit(70)


@pytest.fixture(autouse=True)
def _hang_dump(request):
    global _watchdog_timer
    _hang_dump_file.write(f"=== arm: {request.node.nodeid}\n")
    _hang_dump_file.flush()
    _watchdog_timer = _threading.Timer(
        PER_TEST_HANG_DUMP_S, _dump_all_threads_and_exit,
        args=(request.node.nodeid,))
    _watchdog_timer.daemon = True
    _watchdog_timer.start()
    yield
    _watchdog_timer.cancel()


@pytest.fixture(scope="module")
def ray_start_module():
    """Module-scoped cluster (reference conftest.py:590 fixture reuse):
    tests that exercise the public API without killing cluster components
    share one runtime per file. Generous LOGICAL cpus — actors from
    earlier tests in the module stay alive and each reserves one."""
    import ray_tpu
    ray_tpu.shutdown()
    ctx = ray_tpu.init(num_cpus=64, _system_config={
        "health_check_period_s": 0.2,
        "health_check_failure_threshold": 3,
    })
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    import ray_tpu
    ray_tpu.shutdown()
    ctx = ray_tpu.init(num_cpus=4, _system_config={
        "health_check_period_s": 0.2,
        "health_check_failure_threshold": 3,
    })
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.core.cluster import Cluster
    import ray_tpu
    ray_tpu.shutdown()
    cluster = Cluster()
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


@pytest.fixture
def jax_cpu_mesh():
    import jax
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "need 8 virtual cpu devices"
    yield devices
