"""Worker process entry point.

TPU-native analog of the reference's default_worker.py
(/root/reference/python/ray/_private/workers/default_worker.py): spawned by the
node agent, builds a WorkerRuntime, registers back with the agent, then serves
tasks until told to exit.
"""

from __future__ import annotations

import logging
import os
import signal
import threading
import time


def _parse_addr(s: str) -> tuple[str, int]:
    host, port = s.rsplit(":", 1)
    return (host, int(port))


def main():
    logging.basicConfig(
        level=os.environ.get("RAY_TPU_LOG_LEVEL", "WARNING"),
        format=f"[worker {os.getpid()}] %(levelname)s %(name)s: %(message)s")
    # debugging hook: `kill -USR1 <pid>` dumps all thread stacks to the
    # worker's log file (reference: ray stack / py-spy dump equivalent)
    import faulthandler
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    from ray_tpu.core.ids import JobID, NodeID, WorkerID
    from ray_tpu.core.worker import WorkerRuntime
    from ray_tpu.core import api

    cp_addr = _parse_addr(os.environ["RAY_TPU_CP_ADDR"])
    agent_addr = _parse_addr(os.environ["RAY_TPU_AGENT_ADDR"])
    node_id = NodeID(bytes.fromhex(os.environ["RAY_TPU_NODE_ID"]))
    worker_id = WorkerID(bytes.fromhex(os.environ["RAY_TPU_WORKER_ID"]))

    rt = WorkerRuntime(
        mode="worker", cp_addr=cp_addr, agent_addr=agent_addr,
        job_id=JobID.from_int(0), worker_id=worker_id, node_id=node_id)
    api._set_runtime(rt)

    from ray_tpu.core.rpc import RpcClient
    agent = RpcClient(agent_addr, name="agent-client")
    agent.call_with_retry(
        "worker_ready",
        {"worker_id": worker_id, "addr": rt.addr, "pid": os.getpid()},
        timeout=30.0)
    # the process's start-up ledger: from the process's creation, as the
    # kernel has it, to here (interpreter, imports, the runtime)
    from ray_tpu.observability import profiling
    startup = profiling.startup()
    startup.stamp("worker_boot", startup.created, time.monotonic())

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        stop.wait()
    except KeyboardInterrupt:
        pass
    rt.shutdown()


if __name__ == "__main__":
    main()
