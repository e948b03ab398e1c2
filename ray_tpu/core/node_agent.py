"""Node agent — the per-node scheduler, worker pool, and object-store host.

TPU-native analog of the reference's raylet (/root/reference/src/ray/raylet/ —
NodeManager node_manager.h:120): grants worker leases
(HandleRequestWorkerLease node_manager.cc:1627; queueing mirrors
ClusterLeaseManager::QueueAndScheduleLease), spawns/monitors worker processes
(worker_pool.h PopWorker/StartWorkerProcess), hosts the shared-memory object
store in-process (store_runner.cc runs plasma inside the raylet), reserves
placement-group bundles with 2-phase prepare/commit
(placement_group_resource_manager.cc), spills leases back to other nodes
(hybrid policy), and releases a blocked worker's CPU so nested tasks can't
deadlock the pool (the reference's blocked-worker resource release).

TPU-first: if the node hosts TPU chips, the agent pins ONE worker process per
chip group and routes all TPU-resource leases to it — chips admit a single
attached process (SURVEY.md §7 hard-part 7), unlike the fungible CPU pool.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import threading
import time
import uuid
from dataclasses import dataclass, field

from ray_tpu.core.config import get_config
from ray_tpu.core.ids import ActorID, NodeID, PlacementGroupID, WorkerID
from ray_tpu.core.object_store import make_store
from ray_tpu.core.rpc import ClientPool, RpcServer
from ray_tpu.core.scheduler import add, fits, subtract
from ray_tpu.util import metrics as _metrics

logger = logging.getLogger(__name__)

# Built-in node-agent metrics (ISSUE 4; ref: stats/metric_defs.cc
# raylet-side series). Shipped to the CP by the per-process MetricsFlusher.
_SPILLBACK_COUNTER = _metrics.Counter(
    "ray_tpu_scheduler_spillbacks_total",
    "lease requests redirected to another node (hybrid spillback)")
_STORE_BYTES_STORED = _metrics.Counter(
    "ray_tpu_object_store_bytes_stored_total",
    "bytes allocated in this node's shared-memory store")
_STORE_HITS = _metrics.Counter(
    "ray_tpu_object_store_hits_total",
    "object lookups served from the local store")
_STORE_MISSES = _metrics.Counter(
    "ray_tpu_object_store_misses_total",
    "object lookups that required a remote pull or failed locally")
_STORE_SPILLED_GAUGE = _metrics.Gauge(
    "ray_tpu_object_store_spilled_objects",
    "objects spilled to disk by this node's store")
_WORKER_COUNT_GAUGE = _metrics.Gauge(
    "ray_tpu_node_agent_workers",
    "worker processes in this agent's pool, by state",
    tag_keys=("state",))
_ENV_CACHE_GAUGE = _metrics.Gauge(
    "ray_tpu_node_agent_env_cache_entries",
    "materialized runtime-env cache entries on this node")

# How a worker ends (NodeAgent._end_worker): asked to leave, killed once its
# grace has run out, reaped. The limit on the wait after SIGKILL is there
# only so that a wedged kernel cannot hang stop()'s caller for ever.
_WORKER_EXIT_GRACE_S = 2.0
_WORKER_REAP_LIMIT_S = 30.0
# the longest a TPU lease waits for chip nodes some other process still
# holds (benchmark/run.py's guard at its own start waits as long)
_CHIP_WAIT_S = 60.0


class _InProcHandle:
    """Process-like facade over an in-process WorkerRuntime, so the agent's
    monitor/kill/reap paths (poll/terminate/kill/wait/returncode) work
    unchanged for in-process workers — the fake_multi_node-style harness
    that lets scale and autoscaler tests run hundreds of workers as threads
    instead of processes (reference:
    python/ray/autoscaler/_private/fake_multi_node/node_provider.py)."""

    def __init__(self, rt):
        self._rt = rt
        self._exited = threading.Event()
        self.returncode: int | None = None

    def exit(self, code: int = 0) -> None:
        """Soft process-exit: bound to WorkerRuntime.on_exit."""
        if self._exited.is_set():
            return
        self.returncode = code
        self._exited.set()
        threading.Thread(target=self._shutdown, daemon=True).start()

    def _shutdown(self):
        try:
            self._rt.shutdown()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass

    # Popen facade ------------------------------------------------------
    def poll(self):
        return self.returncode if self._exited.is_set() else None

    def terminate(self):
        self.exit(-15)

    def kill(self):
        self.exit(-9)

    def wait(self, timeout: float | None = None):
        self._exited.wait(timeout)
        return self.returncode


@dataclass
class _WorkerInfo:
    worker_id: WorkerID
    addr: tuple[str, int] | None = None
    proc: subprocess.Popen | None = None
    pid: int = 0
    busy: bool = False
    # set once the worker is dying (_end_worker): when it is to be killed
    kill_at: float | None = None
    actor_id: ActorID | None = None
    is_tpu_worker: bool = False
    chips_awaited: bool = False  # its first TPU lease waited for the chips
    env_key: str = ""  # runtime-env hash (worker pool keyed per env)
    idle_since: float = field(default_factory=time.monotonic)
    ready = None  # threading.Event
    log_paths: tuple[str, str] | None = None
    log_offsets: list = field(default_factory=lambda: [0, 0])
    job_id: str = ""  # hex of the job the current/last lease belongs to


@dataclass
class _Lease:
    lease_id: str
    worker_id: WorkerID
    resources: dict[str, float]
    pg_id: PlacementGroupID | None = None
    bundle_index: int = -1
    lessee: WorkerID | None = None  # holder; reclaimed if it dies


class NodeAgent:
    def __init__(self, cp_addr: tuple[str, int], *, host: str = "127.0.0.1", port: int = 0,
                 resources: dict[str, float] | None = None,
                 labels: dict[str, str] | None = None,
                 object_store_memory: int | None = None,
                 node_id: NodeID | None = None,
                 inproc_workers: bool = False):
        cfg = get_config()
        # in-process workers: WorkerRuntimes as threads instead of
        # subprocesses (see _InProcHandle) — the scale/autoscaler harness
        self._inproc_workers = bool(inproc_workers)
        self.node_id = node_id or NodeID.from_random()
        self.cp_addr = tuple(cp_addr)
        self._lock = threading.RLock()
        self._pool = ClientPool("agent")
        self._workers: dict[WorkerID, _WorkerInfo] = {}
        self._leases: dict[str, _Lease] = {}
        self._lease_cv = threading.Condition(self._lock)
        if resources is None:
            resources = {"CPU": float(os.cpu_count() or 1)}
        self.resources_total = dict(resources)
        self.available = dict(resources)
        self.labels = dict(labels or {})
        self._detect_tpu_topology()
        # pg_id -> bundle_index -> remaining reserved resources
        self._pg_reserved: dict[PlacementGroupID, dict[int, dict[str, float]]] = {}
        self._pg_prepared: dict[PlacementGroupID, dict[int, dict[str, float]]] = {}
        self.store = make_store(object_store_memory or cfg.object_store_memory,
                                prefix=f"rtpu{os.getpid() % 10000}_{self.node_id.hex()[:6]}")
        self.store.on_evict = self._on_store_evict
        self._object_owners: dict = {}  # ObjectID -> owner addr, for evict notices
        self._pull_cv = threading.Condition()
        self._relay_channels: dict[str, object] = {}  # shadow path -> Channel
        self._channel_relay_stops: dict = {}  # (path, index) -> stop Event
        self._pull_inflight_bytes = 0
        self._pulls_in_progress: dict = {}  # ObjectID -> Event (single-flight)
        self._stopped = threading.Event()
        # graceful drain (ref: node_manager.proto:448 DrainRaylet): a
        # draining agent refuses new leases (redirecting where possible)
        # but lets in-flight ones finish; set by the CP's drain notify or
        # learned from the heartbeat reply's `state` field.
        self._draining = False
        self._res_version = 0  # versioned resource-view sync (RaySyncer)
        self._server = RpcServer(
            self._handle, host=host, port=port, name="nodeagent",
            blocking_methods={"lease_worker", "pull_object",
                              "wait_object_local", "channel_push",
                              "drain_objects"},
            pool_size=16)
        self.addr = self._server.addr
        self._register_with_cp()
        # per-process metrics auto-flush (ISSUE 4): delta snapshots to the
        # CP time-series store every metrics_flush_interval_s + once on
        # stop(). In-process harnesses share one flusher per process (first
        # component to start it wins; `stop_flusher` is owner-checked).
        self._metrics_flusher = None
        if cfg.metrics_enabled:
            # acknowledged call, not a one-way notify: a flush into a CP
            # that just died can land in the kernel buffer and vanish —
            # the reply makes the failure visible so the flusher's outage
            # backlog keeps the payload for re-send
            self._metrics_flusher = _metrics.start_flusher(
                lambda p: self._pool.get(self.cp_addr).call(
                    "metrics_report", p, timeout=10.0),
                source=f"node:{self.node_id.hex()}",
                node_id=self.node_id.hex())
        self._memory_monitor = None
        if cfg.memory_usage_threshold > 0:
            from ray_tpu.core.memory_monitor import MemoryMonitor
            self._memory_monitor = MemoryMonitor(
                self._oom_kill_worker, cfg.memory_usage_threshold,
                cfg.memory_monitor_interval_s)
        self._monitor_thread = threading.Thread(
            target=self._monitor_workers, name="agent-monitor", daemon=True)
        self._monitor_thread.start()
        if cfg.log_to_driver:
            threading.Thread(target=self._log_monitor_loop,
                             name="agent-logmon", daemon=True).start()

    def _detect_tpu_topology(self):
        """Populate TPU resources/labels from the environment (generalizes the
        reference's TPU accelerator manager, _private/accelerators/tpu.py:199,
        topology inference tpu.py:114)."""
        from ray_tpu.parallel.topology import detect_local_topology
        topo = detect_local_topology()
        if topo is None:
            return
        self.resources_total.setdefault("TPU", float(topo.chips_per_host))
        self.available.setdefault("TPU", float(topo.chips_per_host))
        self.labels.setdefault("slice_name", topo.slice_name)
        self.labels.setdefault("pod_type", topo.pod_type)
        self.labels.setdefault("topology", topo.topology)
        self.labels.setdefault("tpu_worker_id", str(topo.worker_id))

    def _register_with_cp(self):
        self._pool.get(self.cp_addr).call_with_retry(
            "register_node",
            {"node_id": self.node_id, "addr": self.addr,
             "resources": self.resources_total, "labels": self.labels},
            timeout=get_config().rpc_connect_timeout_s)
        # a (re-)registered node is ALIVE CP-side; a drain that was in
        # flight across a CP restart is forgotten by both ends together
        self._draining = False

    def _report_resources(self):
        """Versioned resource report (ref: RaySyncer versioned views,
        ray_syncer.h:87): every snapshot carries a monotonically increasing
        version so the CP can discard stale/reordered updates — notify-based
        reports race heartbeats, and an out-of-order apply would regress the
        CP's availability view."""
        with self._lock:
            self._res_version += 1
            body = {"node_id": self.node_id,
                    "available": dict(self.available),
                    "version": self._res_version}
        try:
            # versioned heartbeat: a lost report self-heals on the next
            # periodic report (the CP keeps the highest version it saw)
            # graftlint: fire-and-forget
            self._pool.get(self.cp_addr).notify("report_resources", body)
        except Exception:
            pass
        return body["version"]

    # ------------------------------------------------------------------
    def _handle(self, method: str, body, peer):
        fn = getattr(self, "_h_" + method, None)
        if fn is None:
            raise ValueError(f"node agent: unknown method {method}")
        return fn(body)

    def _h_ping(self, body):
        return {"ok": True}

    # ---- graceful drain (ref: node_manager.proto:448 DrainRaylet) ------
    def _h_drain(self, body):
        """CP tells us we are DRAINING: stop granting leases (waiters wake
        and redirect/refuse) but let in-flight work run to completion —
        the CP's drain finisher polls drain_status until we are idle."""
        self._draining = True
        with self._lock:
            self._lease_cv.notify_all()
        return {"ok": True}

    def _h_drain_status(self, body):
        """Drain progress for the CP finisher and `ray-tpu status`."""
        with self._lock:
            return {"draining": self._draining,
                    "inflight_leases": len(self._leases),
                    "busy_workers": sum(
                        1 for w in self._workers.values() if w.busy)}

    def _h_drain_objects(self, body):
        """Re-home primary copies: every sealed object this store holds for
        a live owner is pulled BY the target node (chunked, admission-
        controlled — the same path as any remote read), then the owner is
        told the copy moved so later gets resolve to the survivor instead
        of a gone node. Blocking method: migration streams real bytes."""
        target_addr = tuple(body["target_addr"])
        target_node = body.get("target_node_id")
        target = self._pool.get(target_addr)
        with self._lock:
            owned = dict(self._object_owners)
        moved = failed = 0
        for oid, owner in owned.items():
            if self._stopped.is_set():
                break
            if not self.store.contains(oid):
                continue
            try:
                r = target.call(
                    "pull_object",
                    {"object_id": oid, "from_addr": self.addr,
                     "owner_addr": owner}, timeout=120.0)
            except Exception:  # noqa: BLE001 - count and keep going
                r = None
            if not (r and r.get("ok")):
                failed += 1
                continue
            moved += 1
            if owner is not None and target_node is not None:
                # Acknowledged call: the owner's location table MUST learn
                # the copy moved — this node deregisters right after the
                # drain, and an owner still pointing here would direct
                # readers at a dead node. A lost one-way notify does
                # exactly that, silently.
                try:
                    self._pool.get(tuple(owner)).call(
                        "object_moved",
                        {"object_id": oid, "node_id": target_node,
                         "from_node_id": self.node_id}, timeout=5.0)
                except Exception:  # noqa: BLE001 - owner may be gone
                    pass
        return {"ok": True, "moved": moved, "failed": failed}

    # ---- cross-node mutable channels (ref: node_manager.proto:509-512
    # RegisterMutableObject/PushMutableObject) -------------------------
    def _h_channel_relay_open(self, body):
        """Writer-node side: start relaying one reader slot of a local
        channel to a shadow channel on another node's agent. A reader index
        has ONE live attachment: re-attaching (consumer restarted elsewhere)
        replaces the previous relay; a value already consumed by the old
        relay may be delivered to the old attachment."""
        key = (body["path"], int(body["index"]))
        stop = threading.Event()
        with self._lock:
            old = self._channel_relay_stops.pop(key, None)
            self._channel_relay_stops[key] = stop
        if old is not None:
            old.set()
        threading.Thread(
            target=self._channel_relay_loop,
            args=(body["path"], int(body["index"]),
                  tuple(body["target_agent"]), body["target_path"], stop),
            name="chan-relay", daemon=True).start()
        return {"ok": True}

    def _channel_relay_loop(self, path, index, target_agent, target_path,
                            relay_stop):
        from ray_tpu.core.channel import (
            ChannelClosedError,
            ChannelReader,
            ChannelTimeoutError,
        )
        reader = ChannelReader(path, index)
        client = self._pool.get(target_agent)
        while not self._stopped.is_set() and not relay_stop.is_set():
            try:
                data = reader.read(timeout=1.0, raw=True)
            except ChannelTimeoutError:
                continue
            except ChannelClosedError:
                try:
                    client.call("channel_close", {"path": target_path},
                                timeout=10.0)
                except Exception:  # noqa: BLE001 - consumer may be gone
                    pass
                return
            except OSError:
                return  # writer unlinked the segment
            try:
                # synchronous push: the shadow write blocks until the
                # consumer acks, carrying backpressure upstream (our ack
                # above releases the writer slot only once per relayed value)
                client.call("channel_push",
                            {"path": target_path, "data": data},
                            timeout=600.0)
            except Exception as e:  # noqa: BLE001 - consumer died/stalled
                # close the shadow so the consumer sees ChannelClosedError
                # instead of blocking forever on a relay that will never
                # deliver again (the in-hand value is lost — log it)
                logger.warning(
                    "channel relay %s[%d] -> %s push failed (%r); closing "
                    "the shadow and stopping the relay", path, index,
                    target_path, e)
                try:
                    client.call("channel_close", {"path": target_path},
                                timeout=10.0)
                except Exception:  # noqa: BLE001 - consumer gone entirely
                    pass
                return

    def _h_channel_push(self, body):
        from ray_tpu.core.channel import Channel
        path = body["path"]
        with self._lock:
            ch = self._relay_channels.get(path)
            if ch is None:
                ch = self._relay_channels[path] = Channel(0, 0, _attach=path)
        ch.write(body["data"], timeout=600.0)
        return {"ok": True}

    def _h_channel_close(self, body):
        from ray_tpu.core.channel import Channel
        path = body["path"]
        with self._lock:
            ch = self._relay_channels.pop(path, None)
        if ch is None:
            try:
                ch = Channel(0, 0, _attach=path)
            except OSError:
                return {"ok": False}
        ch.close()
        return {"ok": True}

    def _h_dump_node_stacks(self, body):
        """Stack snapshot of the agent AND every registered worker on this
        node (ref: dashboard reporter profiling endpoints). A worker that
        doesn't answer within the per-worker budget is reported as such —
        exactly the workers you most want flagged."""
        from concurrent.futures import ThreadPoolExecutor

        from ray_tpu.observability.profiling import dump_thread_stacks
        out = {"agent": dump_thread_stacks()}
        with self._lock:
            targets = [(w.hex()[:12], i.addr) for w, i in
                       self._workers.items() if i.addr is not None]

        def probe(item):
            wid, addr = item
            try:
                r = self._pool.get(tuple(addr)).call(
                    "dump_stacks", None, timeout=5.0, connect_timeout=2.0)
                return wid, r.get("stacks", "")
            except Exception as e:  # noqa: BLE001
                return wid, f"<unreachable: {e!r}>"

        if targets:
            # concurrent: N wedged workers must cost ~one per-worker budget,
            # not N of them serially (the caller's timeout would fire and
            # lose the whole node's dump — the diagnostic you needed most)
            with ThreadPoolExecutor(max_workers=min(16, len(targets))) as ex:
                for wid, text in ex.map(probe, targets):
                    out[f"worker-{wid}"] = text
        return out

    def _fanout_workers(self, method: str, body, timeout: float) -> dict:
        """Call ``method`` on every registered worker with an RPC address
        (same shape as _h_dump_node_stacks: concurrent, per-worker budget,
        unreachable workers reported instead of failing the node)."""
        from concurrent.futures import ThreadPoolExecutor

        with self._lock:
            targets = [(w.hex(), i.addr) for w, i in
                       self._workers.items() if i.addr is not None]

        def probe(item):
            wid, addr = item
            try:
                return wid, self._pool.get(tuple(addr)).call(
                    method, body, timeout=timeout, connect_timeout=2.0)
            except Exception as e:  # noqa: BLE001
                return wid, {"ok": False, "error": repr(e)}

        out: dict[str, dict] = {}
        if targets:
            with ThreadPoolExecutor(max_workers=min(16, len(targets))) as ex:
                for wid, res in ex.map(probe, targets):
                    out[wid] = res
        return out

    def _h_profiling_start(self, body):
        """Start an XPlane capture on every worker process of this node
        (the per-node hop of the cluster-wide `ray-tpu profile` path)."""
        return {"node_id": self.node_id.hex(),
                "workers": self._fanout_workers(
                    "profiling_start", body or {}, timeout=15.0)}

    def _h_profiling_stop(self, body):
        """Stop the active captures; per-worker results carry the trace
        logdirs the caller registers as artifacts."""
        return {"node_id": self.node_id.hex(),
                "workers": self._fanout_workers(
                    "profiling_stop", body or {}, timeout=30.0)}

    def _h_save_device_memory_profile(self, body):
        """Device-memory (pprof) dump on every worker of this node."""
        return {"node_id": self.node_id.hex(),
                "workers": self._fanout_workers(
                    "save_device_memory_profile", body or {}, timeout=30.0)}

    # ---- worker pool ---------------------------------------------------
    def _spawn_inproc_worker(self, for_tpu: bool,
                             runtime_env: dict | None) -> _WorkerInfo:
        """In-process spawn: a WorkerRuntime hosted on threads in THIS
        process, registered synchronously (no call-home round trip).
        Process-level runtime_env isolation does not apply — acceptable for
        the scale/autoscaler harness this mode exists for."""
        from ray_tpu.core.ids import JobID
        from ray_tpu.core.worker import WorkerRuntime
        from ray_tpu.runtime_env import env_hash

        worker_id = WorkerID.from_random()
        rt = WorkerRuntime(
            mode="worker", cp_addr=self.cp_addr, agent_addr=self.addr,
            job_id=JobID.from_int(0), worker_id=worker_id,
            node_id=self.node_id)
        handle = _InProcHandle(rt)
        rt.on_exit = handle.exit
        info = _WorkerInfo(worker_id=worker_id, is_tpu_worker=for_tpu,
                           env_key=env_hash(runtime_env))
        info.ready = threading.Event()
        info.proc = handle
        info.pid = os.getpid()
        info.addr = rt.addr
        with self._lock:
            self._workers[worker_id] = info
            info.ready.set()
            self._lease_cv.notify_all()
        return info

    def _spawn_worker(self, for_tpu: bool = False,
                      runtime_env: dict | None = None) -> _WorkerInfo:
        from ray_tpu.runtime_env import env_hash, materialize_runtime_env

        if self._inproc_workers:
            return self._spawn_inproc_worker(for_tpu, runtime_env)
        worker_id = WorkerID.from_random()
        env = dict(os.environ)
        cwd = os.getcwd()
        # the framework must stay importable even when a runtime_env moves
        # the worker's cwd (source-tree installs aren't on sys.path then)
        from ray_tpu.core.config import package_parent_path
        env["PYTHONPATH"] = (package_parent_path() + os.pathsep
                             + env.get("PYTHONPATH", ""))
        python_exe = sys.executable
        if runtime_env:
            # materialize BEFORE spawn (reference: runtime_env agent creates
            # the env, then the worker starts inside it)
            env_vars, env_cwd, pypath, venv_py, container = \
                materialize_runtime_env(
                    self._pool.get(self.cp_addr), runtime_env)
            env.update(env_vars)
            if env_cwd:
                cwd = env_cwd
            if pypath:
                env["PYTHONPATH"] = os.pathsep.join(
                    pypath + [env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
            if venv_py:
                # pip envs: the worker runs on the spec's virtualenv
                # interpreter, so its installed packages shadow the base
                # environment's (reference pip/uv plugin semantics)
                python_exe = venv_py
            # pin every cache entry this worker will run out of: the env
            # GC must never rmtree a live worker's cwd/py_modules/venv
            # (unpinned when the agent reaps the worker)
            from ray_tpu.runtime_env.packaging import pin_env_paths
            pin_paths = list(pypath)
            if env_cwd:
                pin_paths.append(env_cwd)
            if venv_py:
                # <env_root>/venv-<key>/bin/python -> the venv entry dir
                pin_paths.append(
                    os.path.dirname(os.path.dirname(venv_py)))
            pin_env_paths(worker_id.hex(), pin_paths)
        # see ray_tpu/__init__.py: arrow's mimalloc pool is unsafe under the
        # worker's thread profile; pin the system pool unless the user set one
        env.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")
        env["RAY_TPU_CP_ADDR"] = f"{self.cp_addr[0]}:{self.cp_addr[1]}"
        env["RAY_TPU_AGENT_ADDR"] = f"{self.addr[0]}:{self.addr[1]}"
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_WORKER_ID"] = worker_id.hex()
        if not for_tpu:
            # CPU-pool workers must never grab the TPU chips as an import side
            # effect (single-process-per-chipset constraint); jax is imported
            # lazily (CPU backend) only if a task actually uses it.
            from ray_tpu.core.cpu_env import force_cpu_env
            force_cpu_env(env)
        # every worker compiles into the one cache directory its platform
        # gets (inherited from outside when set there)
        from ray_tpu.core import compile_cache
        compile_cache.configure(env)
        info = _WorkerInfo(worker_id=worker_id, is_tpu_worker=for_tpu,
                           env_key=env_hash(runtime_env))
        info.ready = threading.Event()
        # Per-worker log files (ref: /tmp/ray/session_*/logs +
        # _private/log_monitor.py); stderr/stdout land here, readable via
        # `ray_tpu.util.state.worker_logs()`.
        log_dir = get_config().log_dir or os.path.join(
            "/tmp/ray_tpu_logs", f"agent-{os.getpid()}")
        os.makedirs(log_dir, exist_ok=True)
        out_path = os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.out")
        err_path = os.path.join(log_dir, f"worker-{worker_id.hex()[:12]}.err")
        argv = [python_exe, "-m", "ray_tpu.core.worker_main"]
        if runtime_env and container:
            # image_uri envs: the worker runs inside the container (shm +
            # host network shared — the object plane and RPC addresses keep
            # working; reference image_uri.py worker-in-container). The
            # container list ends with the image; worker identity env vars
            # are forwarded explicitly.
            env_flags: list[str] = []
            for k, v in env.items():
                if k.startswith(("RAY_TPU_", "PYTHONPATH", "ARROW_")):
                    env_flags += ["-e", f"{k}={v}"]
            argv = container[:-1] + env_flags + [
                container[-1], "python", "-m", "ray_tpu.core.worker_main"]
        with open(out_path, "ab") as fout, open(err_path, "ab") as ferr:
            proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=fout, stderr=ferr)
        info.proc, info.pid = proc, proc.pid
        info.log_paths = (out_path, err_path)
        with self._lock:
            self._workers[worker_id] = info
        return info

    def _system_metrics(self) -> dict:
        """Per-node system gauges shipped with the heartbeat and exported at
        the control plane's prometheus endpoint (TPU-native analog of the
        reference's per-node ReporterAgent -> MetricsAgent pipeline,
        dashboard/modules/reporter/reporter_agent.py + stats/metric_defs.cc)."""
        with self._lock:
            workers = list(self._workers.values())
            leases = len(self._leases)
        m = {
            "workers_total": len(workers),
            "workers_busy": sum(1 for w in workers if w.busy),
            "workers_actor": sum(1 for w in workers
                                 if w.actor_id is not None),
            "leases_active": leases,
        }
        try:
            st = self.store.stats()
            m["object_store_used_bytes"] = st.get("used_bytes", 0)
            m["object_store_num_objects"] = st.get("num_objects", 0)
            m["object_store_capacity_bytes"] = getattr(
                self.store, "capacity", 0)
        except Exception:  # noqa: BLE001 - store impl without counters
            pass
        m["object_store_num_spilled"] = getattr(self.store, "num_spilled", 0)
        # mirror into the flusher registry (the heartbeat copy feeds the CP
        # exposition's per-node gauges; these feed the time-series store)
        _WORKER_COUNT_GAUGE.set(m["workers_total"], tags={"state": "total"})
        _WORKER_COUNT_GAUGE.set(m["workers_busy"], tags={"state": "busy"})
        _WORKER_COUNT_GAUGE.set(m["workers_actor"], tags={"state": "actor"})
        _STORE_SPILLED_GAUGE.set(m["object_store_num_spilled"])
        try:
            from ray_tpu.runtime_env.packaging import env_cache_size
            _ENV_CACHE_GAUGE.set(env_cache_size())
        except Exception:  # noqa: BLE001 - gauge only
            pass
        for k, v in self.resources_total.items():
            m[f"resource_total:{k}"] = float(v)
        with self._lock:
            for k, v in self.available.items():
                m[f"resource_available:{k}"] = float(v)
        return m

    def _log_monitor_loop(self):
        """Tail per-worker log files and publish new lines to the CP
        "worker_logs" channel, where driver runtimes print them (TPU-native
        analog of the reference's log monitor, _private/log_monitor.py: files
        -> GCS pubsub -> driver stdout)."""
        interval = get_config().log_monitor_interval_s
        while not self._stopped.wait(interval):
            with self._lock:
                targets = [w for w in self._workers.values()
                           if w.log_paths and w.job_id]
            for info in targets:
                for i, path in enumerate(info.log_paths):
                    try:
                        with open(path, "rb") as f:
                            f.seek(info.log_offsets[i])
                            data = f.read(256 * 1024)
                    except OSError:
                        continue
                    if not data:
                        continue
                    # consume only whole lines; an unterminated tail stays in
                    # the file for the next tick (a straddled write must not
                    # surface as two broken lines / torn UTF-8). Pathological
                    # newline-free output still flushes once it tops 64KB.
                    nl = data.rfind(b"\n")
                    if nl < 0 and len(data) < 64 * 1024:
                        continue
                    data = data if nl < 0 else data[:nl + 1]
                    info.log_offsets[i] += len(data)
                    lines = data.decode("utf-8", "replace").splitlines()
                    for lo in range(0, len(lines), 200):
                        try:
                            # lossy log streaming by design — dropping a
                            # chunk under CP outage beats stalling the
                            # log monitor loop
                            # graftlint: fire-and-forget
                            self._pool.get(self.cp_addr).notify("publish", {
                                "channel": f"worker_logs:{info.job_id}",
                                "msg": {"node_id": self.node_id.hex()[:8],
                                        "pid": info.pid,
                                        "stream": ("out", "err")[i],
                                        "actor": (info.actor_id.hex()[:8]
                                                  if info.actor_id else None),
                                        "lines": lines[lo:lo + 200]}})
                        except Exception:
                            break

    def _h_worker_ready(self, body):
        """Worker process calls home after starting its RPC server."""
        with self._lock:
            info = self._workers.get(body["worker_id"])
            if info is None:
                info = _WorkerInfo(worker_id=body["worker_id"])
                info.ready = threading.Event()
                self._workers[body["worker_id"]] = info
            info.addr = tuple(body["addr"])
            info.pid = body.get("pid", info.pid)
            info.ready.set()
            self._lease_cv.notify_all()
        return {"ok": True, "node_id": self.node_id}

    def _pop_idle_worker(self, for_tpu: bool,
                         env_key: str = "") -> _WorkerInfo | None:
        for info in self._workers.values():
            if (info.addr is not None and not info.busy and info.actor_id is None
                    and info.is_tpu_worker == for_tpu
                    and info.env_key == env_key):
                return info
        return None

    def _h_lease_worker(self, body):
        """Blocking lease grant (ref: HandleRequestWorkerLease
        node_manager.cc:1627). Reply: granted | redirect (spillback) | timeout.

        The resource reservation is taken once and HELD while a worker spawns —
        a competing request that cannot reserve redirects to another node
        immediately instead of fighting over the pool (the reference's
        queue-then-spillback in ClusterLeaseManager)."""
        cfg = get_config()
        resources = dict(body.get("resources") or {})
        pg_id = body.get("pg_id")
        bundle_index = body.get("bundle_index", -1)
        for_actor = body.get("for_actor")
        runtime_env = body.get("runtime_env")
        from ray_tpu.runtime_env import env_hash
        env_key = env_hash(runtime_env)
        for_tpu = resources.get("TPU", 0) > 0
        deadline = time.monotonic() + body.get("timeout", cfg.lease_timeout_s)
        # When nothing can be reserved and no spillback target exists, reply
        # `busy` after a short grace instead of blocking out the full
        # timeout: the caller then opens its per-worker pipelining depth
        # (submitter MAX_INFLIGHT_PER_WORKER) rather than waiting on a lease
        # that may be a minute away.
        busy_deadline = time.monotonic() + min(
            0.5, body.get("timeout", cfg.lease_timeout_s))
        reserved = False
        spawned = False
        spawned_wid = None  # THIS lease's spawn (reap is per-lease)
        try:
            while not self._stopped.is_set():
                if self._draining:
                    # draining nodes take no new work: spill the request to
                    # a peer when possible, refuse otherwise (the caller
                    # retries through the CP, whose view excludes us)
                    if pg_id is None:
                        target = self._find_remote_node(resources)
                        if target is not None:
                            _SPILLBACK_COUNTER.inc()
                            return {"granted": False, "redirect": target}
                    return {"granted": False, "draining": True}
                need_spawn = False
                try_redirect = False
                victim = grant = None
                await_chips = False
                with self._lock:
                    # reap spawns that died BEFORE registering (e.g. killed
                    # by chaos mid-boot): without this, `spawned` stays set
                    # and the lease waits out its full timeout on a corpse.
                    # Only OUR OWN dead spawn resets our flag — resetting on
                    # any death would double-spawn for other live leases.
                    dead = [wid for wid, i in self._workers.items()
                            if i.proc is not None and i.addr is None
                            and i.proc.poll() is not None]
                    for wid in dead:
                        del self._workers[wid]
                        self._unpin_worker_envs(wid)
                    # not "in dead": a CONCURRENT lease loop may have reaped
                    # our corpse in its own iteration — absence from the
                    # pool is the durable signal (a healthy registered spawn
                    # stays in the dict). Same for THEFT: another concurrent
                    # lease may legally pop OUR spawn the moment it
                    # registers (the pool is fungible); if our spawn is
                    # gone, dead, or taken, we must become spawn-eligible
                    # again or we'd wait out the full lease timeout with
                    # `spawned` set on a worker we'll never get.
                    if spawned and spawned_wid is not None:
                        w = self._workers.get(spawned_wid)
                        if w is None or w.busy or w.actor_id is not None:
                            spawned = False
                            spawned_wid = None
                    if not reserved:
                        reserved = self._try_reserve(resources, pg_id, bundle_index)
                    if reserved:
                        worker = self._pop_idle_worker(for_tpu, env_key)
                        if worker is not None and worker.ready.is_set():
                            worker.busy = True
                            worker.job_id = body.get("job_id") or worker.job_id
                            if for_actor is not None:
                                worker.actor_id = for_actor
                            lease = _Lease(uuid.uuid4().hex, worker.worker_id,
                                           resources, pg_id, bundle_index,
                                           lessee=body.get("lessee"))
                            self._leases[lease.lease_id] = lease
                            reserved = False  # consumed by the lease
                            grant_version = self._report_resources()
                            # snapshot rides the reply so the caller can SET
                            # its view instead of subtracting (a subtract
                            # after our async report double-counts the lease
                            # and can wedge the view at 0)
                            grant = {"granted": True, "lease_id": lease.lease_id,
                                     "worker_id": worker.worker_id,
                                     "worker_addr": worker.addr,
                                     "available": dict(self.available),
                                     "version": grant_version}
                            await_chips = for_tpu and not worker.chips_awaited
                            worker.chips_awaited = True
                        elif not spawned and self._can_spawn(for_tpu):
                            spawned = need_spawn = True
                        elif not spawned:
                            # pool is at its cap but holds idle workers for
                            # OTHER runtime envs: evict one to make room, or
                            # an env-mismatched burst starves this lease
                            # until its timeout
                            victim = next(
                                (i for i in self._workers.values()
                                 if i.addr is not None and not i.busy
                                 and i.actor_id is None
                                 and i.is_tpu_worker == for_tpu
                                 and i.env_key != env_key), None)
                            if victim is not None:
                                victim.busy = True  # unleaseable while dying
                                spawned = need_spawn = True
                    elif pg_id is None:
                        try_redirect = True
                if grant is not None:
                    if await_chips:
                        # the worker opens the chips in its first task
                        self._await_chips(resources["TPU"], deadline)
                    return grant
                if victim is not None:
                    self._end_worker(victim, _WORKER_EXIT_GRACE_S)
                if need_spawn:
                    spawned_wid = self._spawn_worker(
                        for_tpu, runtime_env).worker_id
                if try_redirect:
                    target = self._find_remote_node(resources)
                    if target is not None:
                        _SPILLBACK_COUNTER.inc()
                        return {"granted": False, "redirect": target}
                    if time.monotonic() > busy_deadline:
                        return {"granted": False, "busy": True}
                with self._lock:
                    self._lease_cv.wait(timeout=0.05)
                if time.monotonic() > deadline:
                    logger.warning(
                        "lease timeout: res=%s reserved=%s spawned=%s "
                        "env_key=%r available=%s workers=%s", resources,
                        reserved, spawned, env_key, self.available,
                        [(w.hex()[:6], i.busy, i.actor_id is not None,
                          i.addr is not None, i.env_key)
                         for w, i in self._workers.items()])
                    return {"granted": False, "timeout": True}
            return {"granted": False, "timeout": True}
        finally:
            if reserved:
                with self._lock:
                    self._unreserve(resources, pg_id, bundle_index)
                    self._lease_cv.notify_all()

    def _can_spawn(self, for_tpu: bool) -> bool:
        """Concurrent leases are bounded by the CPU resource, so the pool
        never needs more workers than logical CPUs (+ headroom for
        zero-CPU leases); spawn-ahead is also bounded so a burst of lease
        requests can't fork dozens of interpreters at once and thrash the
        host (ref: worker_pool.h maximum_startup_concurrency)."""
        cfg = get_config()
        mine = [w for w in self._workers.values()
                if w.is_tpu_worker == for_tpu]
        if for_tpu:
            # one TPU worker process per chip group (hard-part 7)
            return len(mine) < 1
        cpus = int(self.resources_total.get("CPU", 4))
        # Actors each occupy a dedicated worker for life and are gated by
        # the resource scheduler, so only POOL (non-actor) workers count
        # against the cap — otherwise N zero-CPU actors would starve task
        # leases (and vice versa).
        pool = [w for w in mine if w.actor_id is None]
        limit = cfg.max_workers_per_node or (cpus + 4)
        if len(pool) >= limit:
            return False
        starting = sum(1 for w in pool if w.addr is None)
        return starting < max(2, cpus // 2)

    def _try_reserve(self, resources, pg_id, bundle_index) -> bool:
        if pg_id is not None:
            pg = self._pg_reserved.get(pg_id)
            if pg is None:
                return False
            if bundle_index >= 0:
                pool = pg.get(bundle_index)
                if pool is None or not fits(pool, resources):
                    return False
                subtract(pool, resources)
                return True
            for pool in pg.values():
                if fits(pool, resources):
                    subtract(pool, resources)
                    return True
            return False
        if not fits(self.available, resources):
            return False
        subtract(self.available, resources)
        return True

    def _unreserve(self, resources, pg_id, bundle_index):
        if pg_id is not None:
            pg = self._pg_reserved.get(pg_id)
            if pg is None:
                return
            if bundle_index >= 0 and bundle_index in pg:
                add(pg[bundle_index], resources)
            elif pg:
                add(next(iter(pg.values())), resources)
            return
        add(self.available, resources)

    def _find_remote_node(self, resources) -> tuple | None:
        try:
            nodes = self._pool.get(self.cp_addr).call("get_nodes", None, timeout=5.0)
        except Exception:
            return None
        for n in nodes:
            if n["node_id"] == self.node_id or not n["alive"] \
                    or n.get("state", "ALIVE") != "ALIVE":
                continue
            if fits(n["available"], resources):
                return tuple(n["addr"])
        return None

    def _h_return_lease(self, body):
        with self._lock:
            lease = self._leases.pop(body["lease_id"], None)
            if lease is None:
                return {"ok": False}
            self._unreserve(lease.resources, lease.pg_id, lease.bundle_index)
            worker = self._workers.get(lease.worker_id)
            if worker is not None and worker.actor_id is None:
                worker.busy = False
                worker.idle_since = time.monotonic()
            self._lease_cv.notify_all()
        self._report_resources()
        return {"ok": True}

    def _h_worker_blocked(self, body):
        """A leased worker blocked in get(); release its CPU so nested tasks
        can run (ref: the raylet's blocked-worker resource release)."""
        with self._lock:
            for lease in self._leases.values():
                if lease.worker_id == body["worker_id"]:
                    cpus = {"CPU": lease.resources.get("CPU", 0.0)}
                    if cpus["CPU"] > 0:
                        self._unreserve(cpus, lease.pg_id, lease.bundle_index)
                        lease.resources = {**lease.resources, "CPU": 0.0}
                    self._lease_cv.notify_all()
                    break
        return {"ok": True}

    # ---- placement group bundles --------------------------------------
    def _h_prepare_bundles(self, body):
        """Phase 1 (ref: node_manager.proto:452 PrepareBundleResources)."""
        pg_id = body["pg_id"]
        with self._lock:
            need: dict[str, float] = {}
            for _, b in body["bundles"]:
                for k, v in b.items():
                    need[k] = need.get(k, 0.0) + v
            if not fits(self.available, need):
                return {"ok": False}
            subtract(self.available, need)
            self._pg_prepared[pg_id] = {i: dict(b) for i, b in body["bundles"]}
        self._report_resources()
        return {"ok": True}

    def _h_commit_bundles(self, body):
        """Phase 2 (ref: node_manager.proto:457 CommitBundleResources)."""
        pg_id = body["pg_id"]
        with self._lock:
            prepared = self._pg_prepared.pop(pg_id, None)
            if prepared is None:
                return {"ok": False}
            self._pg_reserved[pg_id] = prepared
            self._lease_cv.notify_all()
        return {"ok": True}

    def _h_cancel_bundles(self, body):
        """(ref: node_manager.proto:461 CancelResourceReserve)"""
        pg_id = body["pg_id"]
        with self._lock:
            pools = self._pg_prepared.pop(pg_id, None) or self._pg_reserved.pop(pg_id, None)
            if pools:
                for pool in pools.values():
                    add(self.available, pool)
            # Live leases under this pg become plain node leases; their
            # resources return to `available` when the lease returns. No
            # adjustment here: prepare subtracted the FULL bundle from
            # `available`, and the pools we just added back held only the
            # unleased remainder — the leased share stays owed until lease
            # return (subtracting again would double-count it).
            for lease in self._leases.values():
                if lease.pg_id == pg_id:
                    lease.pg_id = None
                    lease.bundle_index = -1
            self._lease_cv.notify_all()
        self._report_resources()
        return {"ok": True}

    # ---- object store --------------------------------------------------
    def _h_store_create(self, body):
        name, offset = self.store.create(body["object_id"], body["size"],
                                         body.get("device_hint", ""))
        if body["size"] > 0:
            _STORE_BYTES_STORED.inc(body["size"])
        if body.get("owner_addr") is not None:
            self._object_owners[body["object_id"]] = tuple(body["owner_addr"])
        return {"shm_name": name, "offset": offset}

    def _h_store_seal(self, body):
        self.store.seal(body["object_id"])
        return {"ok": True}

    def _h_store_get_meta(self, body):
        meta = self.store.get_meta(body["object_id"])
        (_STORE_HITS if meta is not None else _STORE_MISSES).inc()
        return meta

    def _h_store_read_done(self, body):
        """Reader finished deserializing: release its read lease so the
        spill/delete paths may touch the extent again."""
        read_done = getattr(self.store, "read_done", None)
        if read_done is not None:
            read_done(body["object_id"])
        return {"ok": True}

    def _h_store_contains(self, body):
        return self.store.contains(body["object_id"])

    def _h_store_pin(self, body):
        self.store.pin(body["object_id"], body.get("pinned", True))
        return {"ok": True}

    def _h_store_delete(self, body):
        self._object_owners.pop(body["object_id"], None)
        self.store.delete(body["object_id"])
        return {"ok": True}

    def _h_store_stats(self, body):
        return self.store.stats()

    def _h_read_object(self, body):
        """Chunked remote read (ref: object_manager.proto:60 Pull/Push)."""
        out = self.store.read_bytes(
            body["object_id"], body.get("offset", 0), body.get("size"))
        if out is None:
            return None
        total, chunk = out
        return {"total": total, "data": chunk}

    def _admit_pull(self, nbytes: int) -> bool:
        """Admission control: bound total in-flight pull bytes so N
        concurrent large pulls can't blow host memory / flood the network
        (ref: pull_manager.h:49 PullManager quota). Blocking-methods
        handlers run on dedicated threads, so waiting here is safe.
        Returns False (nothing reserved) if the agent is shutting down."""
        limit = get_config().max_inflight_pull_bytes
        with self._pull_cv:
            while self._pull_inflight_bytes + nbytes > limit \
                    and self._pull_inflight_bytes > 0:
                self._pull_cv.wait(timeout=1.0)
                if self._stopped.is_set():
                    return False
            self._pull_inflight_bytes += nbytes
        return True

    def _release_pull(self, nbytes: int) -> None:
        with self._pull_cv:
            self._pull_inflight_bytes -= nbytes
            self._pull_cv.notify_all()

    def _h_pull_object(self, body):
        """Fetch an object from a remote node's store into the local store
        (ref: pull_manager.h:49). Chunks stream straight into the local
        store allocation — peak host memory is one chunk, not the object.
        Concurrent pulls of the same object are deduplicated: followers
        wait for the leader instead of racing the chunk writes."""
        object_id = body["object_id"]
        if self.store.contains(object_id):
            _STORE_HITS.inc()
            return {"ok": True}
        _STORE_MISSES.inc()
        # single-flight per object (ref: PullManager object-level dedup)
        with self._pull_cv:
            leader = object_id not in self._pulls_in_progress
            if leader:
                self._pulls_in_progress[object_id] = threading.Event()
            event = self._pulls_in_progress[object_id]
        if not leader:
            event.wait(timeout=300.0)
            return {"ok": self.store.contains(object_id)}
        try:
            return self._pull_as_leader(body, object_id)
        finally:
            with self._pull_cv:
                self._pulls_in_progress.pop(object_id, None)
            event.set()

    def _pull_as_leader(self, body, object_id):
        remote = self._pool.get(tuple(body["from_addr"]))
        chunk = 8 * 1024 * 1024
        first = remote.call_with_retry(
            "read_object", {"object_id": object_id, "offset": 0, "size": chunk},
            timeout=60.0)
        if first is None:
            return {"ok": False}
        total = first["total"]
        if not self._admit_pull(total):
            return {"ok": False}
        try:
            self.store.write_chunk(object_id, 0, first["data"], total)
            off = len(first["data"])
            while off < total:
                part = remote.call_with_retry(
                    "read_object",
                    {"object_id": object_id, "offset": off, "size": chunk},
                    timeout=60.0)
                if part is None:
                    self.store.delete(object_id)
                    return {"ok": False}
                self.store.write_chunk(object_id, off, part["data"], total)
                off += len(part["data"])
        finally:
            self._release_pull(total)
        if body.get("owner_addr") is not None:
            self._object_owners[object_id] = tuple(body["owner_addr"])
        return {"ok": True}

    def _on_store_evict(self, object_id):
        """Tell the owner its primary copy on this node is gone so lineage
        reconstruction can kick in (ref: object_recovery_manager.h:41)."""
        owner = self._object_owners.pop(object_id, None)
        if owner is not None:
            try:
                # advisory: an owner that misses this learns the location
                # is gone on its next failed pull and re-discovers/respawns
                # via lineage — eviction is not a drain (no deregistration)
                # graftlint: fire-and-forget
                self._pool.get(owner).notify(
                    "object_lost", {"object_id": object_id, "node_id": self.node_id})
            except Exception:
                pass

    # ---- worker monitoring ----------------------------------------------
    def _monitor_workers(self):
        cfg = get_config()
        hb_interval = cfg.agent_heartbeat_interval_s
        last_report = 0.0
        while not self._stopped.is_set():
            time.sleep(0.1)
            # periodic resource heartbeat (ref: RaySyncer resource view
            # gossip, ray_syncer.h:87): self-heals any CP-view drift from
            # report/subtract races, and re-registers after a CP restart
            # (NotifyGCSRestart analog)
            now = time.monotonic()
            if now - last_report >= hb_interval:
                last_report = now
                try:
                    with self._lock:
                        self._res_version += 1
                        hb = {"node_id": self.node_id,
                              "available": dict(self.available),
                              "version": self._res_version}
                    hb["metrics"] = self._system_metrics()
                    r = self._pool.get(self.cp_addr).call(
                        "heartbeat", hb, timeout=5.0)
                    if r is not None and not r.get("known", True):
                        logger.info("control plane lost this node "
                                    "(restart?); re-registering")
                        self._register_with_cp()
                    elif r is not None \
                            and r.get("state") in ("DRAINING", "DRAINED") \
                            and not self._draining:
                        # the CP's drain notify was lost: the heartbeat
                        # reply is the backstop delivery channel
                        self._h_drain({})
                except Exception:
                    pass
            if self._memory_monitor is not None:
                with self._lock:
                    snapshot = list(self._workers.values())
                self._memory_monitor.maybe_kill(snapshot)
            # the one place a worker leaves _workers: reaped here, and
            # only here is its death accounted for (_on_worker_dead)
            dead: list[_WorkerInfo] = []
            ending: list[_WorkerInfo] = []
            with self._lock:
                now = time.monotonic()
                for info in list(self._workers.values()):
                    if info.proc is not None and info.proc.poll() is not None:
                        dead.append(info)
                        del self._workers[info.worker_id]
                    elif info.kill_at is not None and now >= info.kill_at:
                        ending.append(info)  # dying, and its grace is over
                    elif (not info.busy and info.actor_id is None
                            and info.addr is not None
                            and now - info.idle_since > cfg.idle_worker_ttl_s):
                        info.busy = True  # unleaseable while dying
                        ending.append(info)
            for info in ending:
                self._end_worker(info, _WORKER_EXIT_GRACE_S)
            for info in dead:
                self._on_worker_dead(info)

    def _end_worker(self, info: _WorkerInfo, grace_s: float) -> None:
        """The one way this agent ends a worker. The first call marks it
        dying, so that it is never leased again, and asks it to leave:
        ``exit_worker`` where it has an address, SIGTERM where it has not
        registered one yet. A call once the grace is over (``grace_s`` 0: at
        once) kills it. Never blocks, and never forgets the worker: it stays
        in ``_workers`` until the monitor, or ``stop()``, has reaped it."""
        with self._lock:
            now = time.monotonic()
            first = info.kill_at is None
            info.busy = True
            info.kill_at = (now + grace_s if first
                            else min(info.kill_at, now + grace_s))
            overdue = now >= info.kill_at
        try:
            if overdue:
                if info.proc is not None:
                    info.proc.kill()
            elif first and info.addr is not None:
                # a hint: the kill at the grace's end does not depend on it
                # graftlint: fire-and-forget
                self._pool.get(info.addr).notify(
                    "exit_worker", {"worker_id": info.worker_id})
            elif first and info.proc is not None:
                info.proc.terminate()
        except Exception:  # noqa: BLE001 - already gone
            pass

    def _reap_worker(self, info: _WorkerInfo) -> bool:
        """Wait until a dying worker's process has been reaped, killing it
        at its grace's end. False: it outlived SIGKILL by the limit."""
        for limit in (info.kill_at - time.monotonic(), _WORKER_REAP_LIMIT_S):
            try:
                info.proc.wait(timeout=max(0.0, limit))
            except subprocess.TimeoutExpired:
                pass
            if info.proc.poll() is not None:
                return True
            self._end_worker(info, 0.0)
        logger.error("worker pid %s is still there %.0f s after SIGKILL",
                     info.pid, _WORKER_REAP_LIMIT_S)
        return False

    def _await_chips(self, chips: float, deadline: float) -> None:
        """A TPU worker is about to get its first task, in which it opens
        the chips: wait until as many chip nodes can be opened as the lease
        holds. A node may be held by a process that is already exiting (a
        worker of the run before, killed and not yet through closing its
        files). Past ``_CHIP_WAIT_S``, or the lease's own deadline, the
        lease is granted all the same and the worker reports what it
        finds."""
        from ray_tpu.parallel import topology
        present = topology.local_chip_count()
        want = min(int(chips), present)
        if want == 0:
            return  # no chip nodes here: a CPU host, or a fake topology
        t0 = time.monotonic()
        end = min(deadline, t0 + _CHIP_WAIT_S)
        while (found := topology.openable_chip_count()) < want \
                and time.monotonic() < end and not self._stopped.is_set():
            time.sleep(0.1)
        waited = time.monotonic() - t0
        logger.log(
            logging.WARNING if found < want or waited > 1.0 else logging.INFO,
            "TPU lease of %d chip(s): %d of %d node(s) can be opened after "
            "%.3f s", want, found, present, waited)

    def _oom_kill_worker(self, info: _WorkerInfo, reason: str) -> None:
        """Hard-kill a worker under memory pressure; the normal dead-worker
        path (monitor loop) reaps it and notifies owners."""
        self._end_worker(info, 0.0)

    def _unpin_worker_envs(self, worker_id) -> None:
        """Release a reaped worker's runtime-env cache pins so the LRU GC
        may evict its entries again."""
        try:
            from ray_tpu.runtime_env.packaging import unpin_env_paths
            unpin_env_paths(worker_id.hex() if hasattr(worker_id, "hex")
                            else str(worker_id))
        except Exception:  # noqa: BLE001 — cleanup must not break reaping
            pass

    def _on_worker_dead(self, info: _WorkerInfo):
        code = info.proc.returncode if info.proc else None
        logger.info("worker %s (pid %s, actor=%s) died, exit code %s",
                    info.worker_id.hex()[:8], info.pid,
                    info.actor_id.hex()[:8] if info.actor_id else None, code)
        orphaned = []
        with self._lock:
            for lid, lease in list(self._leases.items()):
                # release leases ON the dead worker and leases HELD BY it
                # (a killed actor can't return the task leases it was
                # holding; leaking them wedges the node's resource view)
                if (lease.worker_id == info.worker_id
                        or lease.lessee == info.worker_id):
                    self._unreserve(lease.resources, lease.pg_id, lease.bundle_index)
                    del self._leases[lid]
                    w = self._workers.get(lease.worker_id)
                    if w is not None and lease.worker_id != info.worker_id \
                            and w.actor_id is None:
                        # the worker may still be mid-execution of the dead
                        # lessee's orphaned task — marking it idle would
                        # re-lease a busy CPU; end it instead (the monitor
                        # reaps it and a fresh worker spawns clean)
                        orphaned.append(w)
            self._lease_cv.notify_all()
        self._unpin_worker_envs(info.worker_id)
        for w in orphaned:
            self._end_worker(w, _WORKER_EXIT_GRACE_S)
        self._report_resources()
        # ALWAYS tell the CP (not just for actors): a dead worker's metric
        # series must be retracted from the time-series store / exposition
        # even when it held no actor (ISSUE 4 metrics GC). Acknowledged
        # call, not one-way notify: metric retraction, kv-tier index
        # retraction, and actor-death fanout all hang off this message —
        # a notify dropped into a half-closed socket loses them silently.
        try:
            self._pool.get(self.cp_addr).call(
                "worker_died",
                {"worker_id": info.worker_id, "actor_id": info.actor_id,
                 "node_id": self.node_id,
                 "reason": f"worker process exited with code {code}"},
                timeout=5.0)
        except Exception:  # noqa: BLE001 — CP down; its own worker-death
            pass           # sweep (heartbeat miss) retracts eventually
        self._report_resources()

    # ---- lifecycle -------------------------------------------------------
    def _h_shutdown(self, body):
        threading.Thread(target=self.stop, daemon=True).start()
        return {"ok": True}

    def stop(self):
        """Stop the agent. Every worker is asked to leave, killed where it
        has not left by the grace's end, and waited for: when this returns,
        no process this agent spawned exists any more, as a zombie or
        otherwise, and what a worker held (a TPU chip) has been given back.
        The wait cannot be replaced by a look at /proc: a dead TPU worker
        shows there as a zombie with no file open for as long as its chips
        take to be released, and only then can it be reaped (PERF.md)."""
        self._stopped.set()
        lost: set[WorkerID] = set()
        # again, for the spawn a lease handler was in the middle of
        while True:
            with self._lock:
                live = [i for i in self._workers.values()
                        if i.proc is not None and i.proc.poll() is None
                        and i.worker_id not in lost]
            if not live:
                break
            for info in live:
                self._end_worker(info, _WORKER_EXIT_GRACE_S)
            lost.update(info.worker_id for info in live
                        if not self._reap_worker(info))
        # final metrics flush while the CP client pool is still open (clean
        # shutdown must not drop the last interval's deltas)
        if self._metrics_flusher is not None:
            _metrics.stop_flusher(self._metrics_flusher)
        else:
            _metrics.flush_now()
        self._server.stop()
        # the monitor thread reads store stats for heartbeats; it must be
        # gone before the native arena handle is destroyed (use-after-free
        # segfault otherwise)
        self._monitor_thread.join(timeout=5.0)
        self.store.shutdown()
        self._pool.close_all()
