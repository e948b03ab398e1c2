"""A worker process has one owner, the node agent, from spawn to reaped exit.

What the agent spawned is gone (reaped, not a zombie: ``os.kill(pid, 0)``
answers for a zombie) when ``stop()`` returns, whichever way the worker was
ended; and a lease that holds TPU chips is granted on chip nodes that can be
opened. A chip node admits one process, and a process keeps it until its last
thread has closed its files, which only ``waitpid`` can tell: /proc shows such
a process as a zombie with no file open.
"""

import errno
import os
import signal
import time

import pytest

import ray_tpu
from ray_tpu.core import node_agent
from ray_tpu.core.config import get_config
from ray_tpu.parallel import topology


def _gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


def _wait_gone(pids, limit_s: float = 15.0) -> list[int]:
    """The pids still there (zombies count) when the limit has passed."""
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline and not all(_gone(p) for p in pids):
        time.sleep(0.05)
    return [p for p in pids if not _gone(p)]


def _lease(agent, resources=None, **body):
    reply = agent._h_lease_worker(
        {"resources": resources or {"CPU": 1.0}, "timeout": 60.0, **body})
    assert reply.get("granted"), reply
    return reply


def test_shutdown_reaps_a_stopped_actor_worker():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    from ray_tpu.core import api
    agent = api._head[1]

    @ray_tpu.remote
    class Pid:
        def pid(self):
            return os.getpid()

    actor = Pid.remote()
    pid = ray_tpu.get(actor.pid.remote(), timeout=60)
    spawned = [w.pid for w in agent._workers.values()]
    assert pid in spawned
    os.kill(pid, signal.SIGSTOP)   # answers neither exit_worker nor SIGTERM
    t0 = time.monotonic()
    ray_tpu.shutdown()
    took = time.monotonic() - t0
    assert [p for p in spawned if not _gone(p)] == []
    assert took < node_agent._WORKER_REAP_LIMIT_S / 2, took


@pytest.mark.parametrize("how", ["evicted_for_its_runtime_env",
                                 "its_lessee_died"])
def test_a_worker_the_agent_ends_is_reaped_by_the_monitor(
        ray_start_cluster, monkeypatch, how):
    agent = ray_start_cluster.add_node(num_cpus=4)
    first = _lease(agent)
    info = agent._workers[first["worker_id"]]
    if how == "evicted_for_its_runtime_env":
        agent._h_return_lease({"lease_id": first["lease_id"]})
        monkeypatch.setattr(get_config(), "max_workers_per_node", 1)
        # the pool is at its cap and its one idle worker has another env
        second = _lease(agent, runtime_env={"env_vars": {"LIFETIME": "1"}})
        ended = info
        assert second["worker_id"] != ended.worker_id
    else:
        second = _lease(agent, lessee=info.worker_id)
        ended = agent._workers[second["worker_id"]]
        os.kill(info.pid, signal.SIGKILL)
    # never forgotten: in _workers for as long as the process exists
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline and ended.worker_id in agent._workers:
        assert not _gone(ended.pid)
        time.sleep(0.01)
    assert ended.worker_id not in agent._workers
    assert _wait_gone([ended.pid], 1.0) == []
    assert ended.proc.returncode is not None
    spawned = [w.pid for w in agent._workers.values()] + [info.pid, ended.pid]
    ray_start_cluster.remove_node(agent)
    assert [p for p in spawned if not _gone(p)] == []


def test_an_idle_worker_leaves_by_exit_worker_and_is_waited_for(
        ray_start_cluster):
    agent = ray_start_cluster.add_node(num_cpus=2)
    reply = _lease(agent)
    agent._h_return_lease({"lease_id": reply["lease_id"]})
    info = agent._workers[reply["worker_id"]]
    ray_start_cluster.remove_node(agent)
    # it left on its own (os._exit on the thread that served exit_worker)
    # and stop() waited for it: no signal ended it
    assert info.proc.returncode == 0
    assert _gone(info.pid)


def test_openable_chip_count_on_stand_in_nodes(tmp_path, monkeypatch):
    pattern = (str(tmp_path / "[0-9]*"),)
    assert topology.openable_chip_count(pattern) == 0   # no nodes, no cost
    for n in range(4):
        (tmp_path / str(n)).write_bytes(b"")
    (tmp_path / "vfio").write_bytes(b"")   # the container node is no chip
    assert topology.openable_chip_count(pattern) == 4
    real_open = os.open

    def held(path, flags, *a, **kw):
        if str(path).endswith("/2"):
            raise OSError(errno.EBUSY, "Device or resource busy", str(path))
        return real_open(path, flags, *a, **kw)

    monkeypatch.setattr(os, "open", held)
    assert topology.openable_chip_count(pattern) == 3


@pytest.mark.parametrize("held_s, bound_s", [(0.5, 60.0), (60.0, 0.7)],
                         ids=["granted_once_the_chips_open",
                              "granted_anyway_past_the_bound"])
def test_a_tpu_lease_waits_for_chips_that_can_be_opened(
        ray_start_cluster, monkeypatch, caplog, held_s, bound_s):
    agent = ray_start_cluster.add_node(num_cpus=2, tpu_slice="s",
                                       tpu_chips=2)
    probes = []

    def probe():   # one of the two nodes is held for held_s from the first look
        probes.append(time.monotonic())
        return 2 if probes[-1] - probes[0] >= held_s else 1

    monkeypatch.setattr(topology, "local_chip_count", lambda: 2)
    monkeypatch.setattr(topology, "openable_chip_count", probe)
    monkeypatch.setattr(node_agent, "_CHIP_WAIT_S", bound_s)
    t0 = time.monotonic()
    with caplog.at_level("INFO", logger=node_agent.__name__):
        reply = _lease(agent, {"CPU": 1.0, "TPU": 2.0})
        waited = time.monotonic() - min(probes)
        assert min(held_s, bound_s) <= waited < min(held_s, bound_s) + 5.0
        said = [r.getMessage() for r in caplog.records
                if "TPU lease" in r.getMessage()]
        assert len(said) == 1, said
        # the worker holds the chips itself from its first task on: a later
        # lease on it has nothing to wait for
        agent._h_return_lease({"lease_id": reply["lease_id"]})
        n = len(probes)
        again = _lease(agent, {"CPU": 1.0, "TPU": 2.0})
        assert again["worker_id"] == reply["worker_id"]
        assert len(probes) == n
    assert time.monotonic() - t0 < 30.0


def test_a_lease_without_chip_nodes_probes_nothing(ray_start_cluster,
                                                   monkeypatch):
    # a CPU host with a fake slice: TPU resources, no device nodes
    agent = ray_start_cluster.add_node(num_cpus=2, tpu_slice="s",
                                       tpu_chips=4)
    monkeypatch.setattr(
        topology, "openable_chip_count",
        lambda: pytest.fail("probed a host that has no chip nodes"))
    _lease(agent, {"CPU": 1.0, "TPU": 4.0})
