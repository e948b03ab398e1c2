"""TPU slice topology detection.

TPU-native generalization of the reference's TPU accelerator manager
(/root/reference/python/ray/_private/accelerators/tpu.py:114 topology inference,
:199 detection). The chips are what the host has: one device node per chip
(``/dev/vfio/<n>`` on v5e and later, ``/dev/accel<n>`` before), counted
without importing jax — in head mode the node agent is the driver, and a
process that initialises the TPU backend holds the chip against the worker
that needs it. The TPU runtime environment variables only label the host
with its slice identity, so the scheduler can do ICI-aware placement and
atomic slice gang scheduling (SURVEY.md §7 phase 4); they never decide
whether chips exist or how many.

A fake provider (``RAY_TPU_FAKE_TOPOLOGY`` env, JSON) lets multi-slice
scheduling tests run on CPU hosts — the test keystone called out in
SURVEY.md §4.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass

from ray_tpu.core.cpu_env import pinned_to_cpu

# one node per attached chip: vfio groups (v5e/v5p/v6e), accel nodes (v2-v4)
_CHIP_DEVICE_GLOBS = ("/dev/vfio/[0-9]*", "/dev/accel[0-9]*")

# chips per host of a FULL slice of each type, for slice_hosts() only (ref:
# tpu.py topology tables) — this host's own count comes from its devices
_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 4, "v5e": 4, "v6e": 4,
}


@dataclass
class SliceTopology:
    slice_name: str
    pod_type: str       # e.g. "v5p-64"
    topology: str       # e.g. "2x2x4"
    worker_id: int      # this host's index within the slice
    num_hosts: int
    chips_per_host: int

    @property
    def total_chips(self) -> int:
        return self.num_hosts * self.chips_per_host


def _accelerator_chips_per_host(pod_type: str) -> int:
    gen = pod_type.split("-")[0].lower()
    return _CHIPS_PER_HOST.get(gen, 4)


def local_chip_count() -> int:
    """TPU chips this host has for a process started from this environment:
    the device nodes present, or 0 when ``JAX_PLATFORMS`` pins jax to the CPU
    (the chips are then unreachable, and advertising them would place TPU
    work on a node that cannot run it). Present is not free: a node admits
    one process at a time, and ``openable_chip_count`` says how many can be
    opened now."""
    if pinned_to_cpu():
        return 0
    return sum(len(glob.glob(pat)) for pat in _CHIP_DEVICE_GLOBS)


def openable_chip_count(globs: tuple[str, ...] = _CHIP_DEVICE_GLOBS) -> int:
    """Chip nodes that can be opened at this moment. Each node is opened and
    closed at once; one that another process holds fails with EBUSY, also
    while that process is already dead and /proc shows it as a zombie with
    no file open (a dead worker's chips take seconds to be released). A
    host with no nodes answers 0 without opening anything."""
    n = 0
    for pat in globs:
        for node in glob.glob(pat):
            try:
                os.close(os.open(node, os.O_RDWR | os.O_CLOEXEC))
            except OSError:
                continue
            n += 1
    return n


def detect_local_topology() -> SliceTopology | None:
    """Detect this host's slice membership, or None if not a TPU host."""
    fake = os.environ.get("RAY_TPU_FAKE_TOPOLOGY")
    if fake:
        d = json.loads(fake)
        return SliceTopology(
            slice_name=d.get("slice_name", "fake-slice"),
            pod_type=d.get("pod_type", "v5p-8"),
            topology=d.get("topology", "2x2x1"),
            worker_id=int(d.get("worker_id", 0)),
            num_hosts=int(d.get("num_hosts", 1)),
            chips_per_host=int(d.get("chips_per_host", 4)),
        )
    chips = local_chip_count()
    if chips == 0:
        return None
    # slice identity labels from the TPU VM runtime environment, where set
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return SliceTopology(
        slice_name=os.environ.get(
            "TPU_NAME", os.environ.get("HOSTNAME", "local-slice")),
        pod_type=os.environ.get("TPU_ACCELERATOR_TYPE", "tpu"),
        topology=os.environ.get("TPU_TOPOLOGY", ""),
        worker_id=int(os.environ.get("TPU_WORKER_ID", "0")),
        num_hosts=len(hostnames.split(",")) if hostnames else 1,
        chips_per_host=chips)


def slice_hosts(pod_type: str) -> int:
    """Number of hosts in a full slice of the given pod type, e.g. v5p-64 → 8
    (4 chips/host on v5p; the suffix counts cores on v2-v4 and chips on v5+)."""
    try:
        n = int(pod_type.split("-")[-1])
    except ValueError:
        return 1
    return max(1, n // _accelerator_chips_per_host(pod_type))
