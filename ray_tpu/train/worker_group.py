"""Worker group: one actor per rank, gang-placed, polled by the controller.

TPU-native analog of the reference's Train v2 worker group
(/root/reference/python/ray/train/v2/_internal/execution/worker_group/
worker_group.py — _start:190, PG creation :275, RayTrainWorker spawn :388-396;
worker.py:122; thread_runner.py; poll.py). TPU twist: the gang is placed via
an atomic slice placement group (SPREAD over hosts) and each worker is the
single process allowed to attach its host's chips (SURVEY.md §7 hard part 7).
"""

from __future__ import annotations

import dataclasses
import threading
import traceback
from typing import Any, Callable, Optional

import ray_tpu
from ray_tpu.core.placement_group import (
    PlacementGroupSchedulingStrategy,
    placement_group,
    remove_placement_group,
)
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.context import TrainContext, TrainingReport, _set_context


@dataclasses.dataclass
class WorkerStatus:
    alive: bool
    finished: bool
    error: Optional[str]
    reports: list  # list[TrainingReport]
    result: Any = None


@ray_tpu.remote
class RayTrainWorker:
    """One rank. Runs the user train fn on a thread; polled for reports.

    Reference: RayTrainWorker (worker.py:122) + ThreadRunner
    (thread_runner.py).
    """

    def __init__(self):
        self._ctx: Optional[TrainContext] = None
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[str] = None
        self._error_exc = None
        self._finished = False
        self._result = None

    def init_context(self, *, world_rank: int, world_size: int,
                     local_rank: int, local_world_size: int, node_rank: int,
                     experiment_name: str = "", trial_name: str = "",
                     trial_id: str = "", trial_dir: str = "",
                     hparams: Optional[dict] = None,
                     dataset_shards: Optional[dict] = None,
                     resume_checkpoint=None, sync_report: bool = False) -> dict:
        self._ctx = TrainContext(
            world_rank=world_rank, world_size=world_size,
            local_rank=local_rank, local_world_size=local_world_size,
            node_rank=node_rank, experiment_name=experiment_name,
            trial_name=trial_name, trial_id=trial_id, trial_dir=trial_dir,
            dataset_shards=dataset_shards, hparams=hparams)
        self._ctx._sync_report = sync_report
        if resume_checkpoint is not None:
            self._ctx._latest_checkpoint = resume_checkpoint
        _set_context(self._ctx)
        import socket
        return {"hostname": socket.gethostname(),
                "node_id": ray_tpu.get_runtime_context().node_id}

    def setup_backend(self, backend_fn: Optional[Callable]) -> None:
        """Run backend bootstrap (e.g. jax.distributed.initialize) in the
        worker process, before the train fn starts."""
        if backend_fn is not None:
            backend_fn(self._ctx)

    def run_train_fn(self, train_fn: Callable, config: Optional[dict]) -> bool:
        assert self._ctx is not None, "init_context first"
        self._finished = False
        self._error = None

        def _run():
            _set_context(self._ctx)
            try:
                import inspect
                sig = inspect.signature(train_fn)
                if len(sig.parameters) >= 1:
                    self._result = train_fn(config or {})
                else:
                    self._result = train_fn()
            except SystemExit:
                pass
            except BaseException as e:  # noqa: BLE001 - report to controller
                self._error = traceback.format_exc()
                self._error_exc = e
            finally:
                self._finished = True

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name="train_fn")
        self._thread.start()
        return True

    def poll(self) -> WorkerStatus:
        reports = self._ctx._drain_reports() if self._ctx else []
        return WorkerStatus(alive=True, finished=self._finished,
                            error=self._error, reports=reports,
                            result=self._result)

    def stop(self) -> None:
        if self._ctx is not None:
            self._ctx._stop_event.set()

    def execute(self, fn: Callable, *args, **kwargs):
        """Run an arbitrary fn in the worker process (used by tests and
        backend utilities; reference WorkerGroup.execute)."""
        return fn(*args, **kwargs)

    def shutdown(self) -> bool:
        return True


@dataclasses.dataclass
class WorkerInfo:
    actor: Any
    world_rank: int
    node_id: str = ""
    hostname: str = ""


class WorkerGroup:
    """Creates the PG + rank actors, fans out calls, polls status."""

    def __init__(self, scaling: ScalingConfig, experiment_name: str = "",
                 trial_dir: str = ""):
        self._scaling = scaling
        self._experiment_name = experiment_name
        self._trial_dir = trial_dir
        self._pg = None
        self.workers: list[WorkerInfo] = []

    def start(self, *, hparams: Optional[dict] = None,
              dataset_shards_per_rank: Optional[list[dict]] = None,
              resume_checkpoint=None, backend_fn: Optional[Callable] = None):
        n = self._scaling.num_workers
        per = self._scaling._resources_per_worker
        self._pg = placement_group([dict(per) for _ in range(n)],
                                   strategy=self._scaling.placement_strategy)
        self._pg.ready(timeout=120.0)

        actors = []
        for rank in range(n):
            a = RayTrainWorker.options(
                **{k: v for k, v in _actor_resource_opts(per).items()},
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self._pg, placement_group_bundle_index=rank),
            ).remote()
            actors.append(a)

        # init contexts (local rank/node rank computed after hostnames known:
        # first pass assumes one worker per node for SPREAD, else all-local).
        infos = []
        init_refs = []
        for rank, a in enumerate(actors):
            shards = (dataset_shards_per_rank[rank]
                      if dataset_shards_per_rank else None)
            init_refs.append(a.init_context.remote(
                world_rank=rank, world_size=n,
                local_rank=0 if self._scaling.placement_strategy == "SPREAD" else rank,
                local_world_size=1 if self._scaling.placement_strategy == "SPREAD" else n,
                node_rank=rank if self._scaling.placement_strategy == "SPREAD" else 0,
                experiment_name=self._experiment_name,
                trial_dir=self._trial_dir,
                hparams=hparams, dataset_shards=shards,
                resume_checkpoint=resume_checkpoint))
        metas = ray_tpu.get(init_refs)
        for rank, (a, meta) in enumerate(zip(actors, metas)):
            infos.append(WorkerInfo(actor=a, world_rank=rank,
                                    node_id=meta["node_id"],
                                    hostname=meta["hostname"]))
        self.workers = infos
        if backend_fn is not None:
            ray_tpu.get([w.actor.setup_backend.remote(backend_fn)
                         for w in self.workers])

    def run_train_fn(self, train_fn: Callable, config: Optional[dict]):
        ray_tpu.get([w.actor.run_train_fn.remote(train_fn, config)
                     for w in self.workers])

    def poll(self, timeout: float = 30.0) -> list[Optional[WorkerStatus]]:
        """Poll every worker; a dead worker yields None (reference poll.py
        marks errors per-worker)."""
        refs = [w.actor.poll.remote() for w in self.workers]
        out = []
        for ref in refs:
            try:
                out.append(ray_tpu.get(ref, timeout=timeout))
            except Exception:  # noqa: BLE001 - worker death IS the signal
                out.append(None)
        return out

    def execute(self, fn: Callable, *args, **kwargs) -> list:
        return ray_tpu.get([w.actor.execute.remote(fn, *args, **kwargs)
                            for w in self.workers])

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w.actor)
            except Exception:  # noqa: BLE001
                pass
        self.workers = []
        if self._pg is not None:
            try:
                remove_placement_group(self._pg)
            except Exception:  # noqa: BLE001
                pass
            self._pg = None

    def __len__(self):
        return len(self.workers)


def _actor_resource_opts(per: dict) -> dict:
    # the rank actor draws EXACTLY its bundle: left unset, num_cpus defaults
    # to 1 and a {"TPU": n} bundle (ScalingConfig(use_tpu=True)) can never
    # grant the lease — the gang would wait forever
    opts = {"num_cpus": per.get("CPU", 0)}
    if "TPU" in per:
        opts["num_tpus"] = per["TPU"]
    rest = {k: v for k, v in per.items() if k not in ("CPU", "TPU")}
    if rest:
        opts["resources"] = rest
    return opts
