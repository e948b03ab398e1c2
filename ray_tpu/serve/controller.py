"""Serve controller: target-state reconciliation for deployments.

TPU-native analog of the reference's ServeController
(/root/reference/python/ray/serve/_private/controller.py:95 —
run_control_loop:387; deployment_state.py replica lifecycle;
autoscaling_state.py; deployment_scheduler.py). A detached actor owns the
target state {app -> deployments -> config}, reconciles replica actors
toward it, health-checks them, applies queue-length autoscaling, and serves
versioned routing tables to routers/proxies (the long-poll analog).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from typing import Any, Optional

import ray_tpu
from ray_tpu.exceptions import TaskError
from ray_tpu.observability import events as _fr
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig
from ray_tpu.serve.replica import ServeReplica

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "_serve_controller"


class _DeploymentState:
    def __init__(self, app: str, name: str, serialized_cls, init_args,
                 init_kwargs, config: DeploymentConfig, route_prefix):
        self.app = app
        self.name = name
        self.serialized_cls = serialized_cls
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.config = config
        self.route_prefix = route_prefix
        self.replicas: list = []
        # created but not yet past their first health check: NOT routable
        # (the reference's STARTING state) — requests must never queue
        # behind actor creation
        self.starting: list = []
        # replicas on a DRAINING node: still routable (their node keeps
        # running in-flight + new work until the drain deadline) but no
        # longer counted toward target, so reconcile pre-starts
        # replacements. Retired — table flip FIRST, then graceful stop —
        # only once enough replacements are ready.
        self.draining: list = []
        # ready (first health check passed) but still pre-populating their
        # prefix cache from the KV tier (ISSUE 17 cache-warm scale-up):
        # NOT routable. They join `replicas` — with the version bump in
        # the same synchronous block — only once the warm_start RPC
        # resolves, so the router's first sight of a scale-up replica is
        # a warm holder, never a cold one cratering the fleet hit rate.
        self.warming: list = []
        # in-flight warm_start tasks keyed by replica actor-id hex
        self._warm_tasks: dict = {}
        # cumulative warm-start economy across this deployment's scale-ups
        self.warm_stats: dict = {"replicas_warmed": 0, "pages": 0,
                                 "ms": 0.0}
        # signal-driven scale decision log (ISSUE 17): bounded ring of
        # {ts, from, to, reason, signals} plus per-reason counters —
        # exported through detailed_status for the dashboard and the
        # open-loop harness
        self.scale_decisions: list = []
        self.scale_counters: dict[str, int] = {}
        self._signals: dict = {}
        self._signals_ts = 0.0
        # True while the heat guard is continuously refusing a downscale
        # (so the refusal is logged once per episode, not per 0.2s tick)
        self._guard_episode = False
        self.version = 0
        self.target = config.target_replicas()
        # consecutive failed health checks per replica (actor id hex) — a
        # replica is dropped only at health_check_failure_threshold
        self.health_fails: dict[str, int] = {}
        # Prefix-affinity summaries (ISSUE 10): per-replica resident
        # page-chain digests, collected piggyback on the reconcile tick and
        # shipped to routers through the routing-table long-poll (the
        # request path stays RPC-free). Keyed by replica actor-id hex —
        # bounded by (replicas × prefix_summary_max_pages). A replaced
        # replica's entry is pruned the tick it leaves `replicas`, so it
        # starts cold in every router.
        self.summary_gen = 0
        self.summaries: dict[str, list] = {}
        self.summary_versions: dict[str, int] = {}
        self.summary_meta: dict = {}
        # replicas that answered "prefix cache off / not an engine": never
        # probed again (their entry is dropped if the actor is replaced)
        self.summary_unsupported: set[str] = set()
        self._last_scale_ts = 0.0
        self._scale_pending_since: Optional[float] = None
        self._pending_target: Optional[int] = None

    def full_name(self) -> str:
        return f"{self.app}#{self.name}"


@ray_tpu.remote
class ServeController:
    def __init__(self):
        self._deployments: dict[str, _DeploymentState] = {}
        self._routes: dict[str, tuple[str, str]] = {}  # prefix -> (app, deployment)
        self._stopped = False
        # __init__ runs off the actor event loop; the control loop is started
        # lazily from the first async method invocation.
        self._loop_task = None
        # node-death pubsub: the handler runs on the hosting worker's pubsub
        # dispatch thread; the control loop drains this on its own cadence
        self._dead_nodes: list = []
        self._draining_nodes: list = []
        self._dead_nodes_lock = threading.Lock()
        self._node_sub_done = False
        # affinity-summary collection cadence (ISSUE 10): piggybacks on
        # the 0.2s reconcile tick but only probes replicas this often
        self._summary_ts = 0.0
        self._summary_interval_s = 1.0

    def _ensure_started(self):
        if self._loop_task is None:
            self._loop_task = asyncio.ensure_future(self._control_loop())
            self._change_event = asyncio.Event()
            self._subscribe_node_events()

    def _subscribe_node_events(self):
        """Wire CP `node` pubsub death events into the reconcile loop so
        replicas on a dead node are replaced PROACTIVELY instead of waiting
        out health-check timeouts (ref: GcsActorManager::OnNodeDead)."""
        if self._node_sub_done:
            return
        self._node_sub_done = True
        try:
            from ray_tpu.core import api as _api
            rt = _api._try_get_runtime()
            if rt is not None:
                rt.register_pubsub_handler("node", self._on_node_event)
        except Exception:  # noqa: BLE001 — degraded: health checks still work
            logger.exception("serve controller: node pubsub wiring failed")

    def _on_node_event(self, msg):
        if not isinstance(msg, dict):
            return
        event = msg.get("event")
        if event not in ("dead", "draining"):
            return
        node_id = msg.get("node_id")
        hexed = node_id.hex() if hasattr(node_id, "hex") else str(node_id)
        with self._dead_nodes_lock:
            if event == "dead":
                self._dead_nodes.append(hexed)
            else:
                self._draining_nodes.append(hexed)

    def _notify_change(self):
        ev = getattr(self, "_change_event", None)
        if ev is not None:
            ev.set()
            self._change_event = asyncio.Event()

    # ---- deploy API ----------------------------------------------------
    async def deploy_application(self, app_name: str,
                                 deployments: list[dict]) -> bool:
        """deployments: [{name, serialized_cls, init_args, init_kwargs,
        config(DeploymentConfig), route_prefix, is_ingress}]"""
        self._ensure_started()
        new_names = set()
        for d in deployments:
            key = f"{app_name}#{d['name']}"
            new_names.add(key)
            existing = self._deployments.get(key)
            state = _DeploymentState(
                app_name, d["name"], d["serialized_cls"],
                d.get("init_args"), d.get("init_kwargs"),
                d["config"], d.get("route_prefix"))
            if existing is not None:
                state.replicas = existing.replicas
                state.starting = existing.starting
                state.draining = existing.draining
                state.warming = existing.warming
                state._warm_tasks = existing._warm_tasks
                state.warm_stats = existing.warm_stats
                state.scale_decisions = existing.scale_decisions
                state.scale_counters = existing.scale_counters
                # config change with same code → reconfigure in place
                if d["config"].user_config is not None:
                    for r in state.replicas:
                        try:
                            await asyncio.wait_for(_as_future(
                                r.reconfigure.remote(
                                    d["config"].user_config)), 10.0)
                        except Exception:  # noqa: BLE001
                            pass
                # version computed AT PUBLISH time, after the awaits above:
                # the control loop may bump existing.version while a
                # reconfigure is in flight, and republishing at an older
                # (or equal) version would leave long-pollers pinned on
                # the stale table forever (ISSUE 17 atomicity fix)
                state.version = existing.version + 1
            self._deployments[key] = state
            if d.get("is_ingress") and d.get("route_prefix") is not None:
                self._routes[d["route_prefix"]] = (app_name, d["name"])
        self._notify_change()
        # remove deployments of this app not in the new spec
        for key in [k for k in self._deployments
                    if k.startswith(app_name + "#") and k not in new_names]:
            await self._drain_deployment(self._deployments.pop(key))
        # wait until all deployments have their target replicas up — at
        # least a minute, and as long as the slowest deployment says its
        # replicas may go without answering a health check (an LLM engine
        # compiles its programs for minutes inside its constructor)
        deadline = time.monotonic() + max(
            [60.0] + [d["config"].health_check_timeout_s
                      for d in deployments])
        while time.monotonic() < deadline:
            if all(len(s.replicas) >= s.target
                   for s in self._deployments.values()
                   if s.app == app_name):
                return True
            await asyncio.sleep(0.05)
        return False

    async def delete_application(self, app_name: str) -> bool:
        self._ensure_started()
        for key in [k for k in self._deployments
                    if self._deployments[k].app == app_name]:
            await self._drain_deployment(self._deployments.pop(key))
        self._routes = {p: t for p, t in self._routes.items()
                        if t[0] != app_name}
        return True

    async def _drain_deployment(self, state: _DeploymentState):
        for t in state._warm_tasks.values():
            t.cancel()
        state._warm_tasks = {}
        for r in state.starting + state.warming:
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass
        state.starting = []
        state.warming = []
        for r in state.replicas + state.draining:
            try:
                await asyncio.wait_for(
                    _as_future(r.prepare_for_shutdown.remote(
                        state.config.graceful_shutdown_timeout_s)),
                    state.config.graceful_shutdown_timeout_s + 5.0)
            except Exception:  # noqa: BLE001
                pass
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass
        state.replicas = []
        state.draining = []

    # ---- introspection -------------------------------------------------
    def _summary_entry(self, state: _DeploymentState,
                       known_gen: Optional[int]) -> Optional[dict]:
        """The affinity-summary element of a routing-table entry. None when
        the router already holds this generation (delta shipping: an
        unchanged fleet costs zero summary bytes per poll). A deployment
        with nothing collected (non-LLM) still ships its empty gen-0
        entry until the router acknowledges the gen — withholding it
        would pin the router at gen -1, make every poll look changed,
        and degenerate the long-poll into a hot spin."""
        if known_gen is not None and known_gen == state.summary_gen:
            return None
        return {"gen": state.summary_gen,
                "meta": dict(state.summary_meta),
                "replicas": {k: list(v) for k, v in state.summaries.items()}}

    async def get_routing_table(self, app_name: str,
                                known_gens: Optional[dict] = None) -> dict:
        self._ensure_started()
        known_gens = known_gens or {}
        out = {}
        for state in self._deployments.values():
            if state.app == app_name:
                # draining replicas stay routable until replacements are
                # ready — the table never shrinks below target mid-drain.
                # (Their affinity summaries are ALREADY gone: the collector
                # prunes anything not in `replicas`, so draining replicas
                # take load-balanced spillover only, never affinity pulls.)
                out[state.name] = (list(state.replicas) + list(state.draining),
                                   state.version,
                                   self._summary_entry(
                                       state, known_gens.get(state.name)))
        return out

    async def poll_routing_table(self, app_name: str,
                                 known_versions: dict,
                                 timeout_s: float = 30.0) -> dict | None:
        """LONG-POLL (reference long_poll.py LongPollHost:228): returns the
        app's routing table as soon as any deployment's version OR affinity
        summary generation differs from `known_versions`
        ({name: version} or {name: [version, summary_gen]} — both accepted),
        or None at timeout. Routers hang on this instead of re-polling on a
        timer."""
        self._ensure_started()
        deadline = asyncio.get_event_loop().time() + timeout_s
        known: dict = {}
        known_gens: dict = {}
        for d, v in dict(known_versions or {}).items():
            if isinstance(v, (list, tuple)) and v:
                known[d] = v[0]
                # legacy single-int callers never subscribe to summaries
                known_gens[d] = v[1] if len(v) > 1 else None
            else:
                known[d] = v
        while True:
            states = [s for s in self._deployments.values()
                      if s.app == app_name]
            current = {s.name: s.version for s in states}
            # Changed = a deployment the router hasn't seen (or at an older
            # version), or a deployment the router saw a REAL version of that
            # is now gone. A router-side placeholder (version -1 for a
            # deployment that doesn't exist yet) must NOT count, or the
            # long-poll degenerates into a hot spin.
            changed = any(known.get(d) != ver for d, ver in current.items()) \
                or any(ver >= 0 and d not in current
                       for d, ver in known.items()) \
                or any(d in known_gens and known_gens[d] is not None
                       and known_gens[d] != s.summary_gen for d, s in
                       ((s.name, s) for s in states))
            if changed:
                return await self.get_routing_table(app_name, known_gens)
            ev = self._change_event
            remaining = deadline - asyncio.get_event_loop().time()
            if remaining <= 0:
                return None
            try:
                await asyncio.wait_for(ev.wait(), timeout=min(remaining, 5.0))
            except asyncio.TimeoutError:
                pass

    async def get_http_routes(self) -> dict:
        self._ensure_started()
        return dict(self._routes)

    async def get_request_timeout(self, app_name: str,
                                  deployment: str) -> Optional[float]:
        """Deployment's default end-to-end request timeout (None = fall back
        to the `serve_request_timeout_s` flag; proxy caches this)."""
        self._ensure_started()
        state = self._deployments.get(f"{app_name}#{deployment}")
        if state is None:
            return None
        return getattr(state.config, "request_timeout_s", None)

    async def get_slo_policy(self, app_name: str,
                             deployment: str) -> Optional[dict]:
        """Deployment's SLO policy for the proxy's critical-path
        attribution (None = unknown deployment; all-None values = no
        objectives configured, baseline sampling only)."""
        self._ensure_started()
        state = self._deployments.get(f"{app_name}#{deployment}")
        if state is None:
            return None
        return {
            "slo_ttft_p99_ms": getattr(state.config, "slo_ttft_p99_ms",
                                       None),
            "slo_e2e_p99_ms": getattr(state.config, "slo_e2e_p99_ms", None),
            "slo_sample_rate": getattr(state.config, "slo_sample_rate",
                                       0.01),
        }

    async def ingress_has_http_dispatch(self, app_name: str,
                                        deployment: str) -> bool:
        """Does the ingress class define handle_http(path, method, payload)?
        (Proxy sub-path dispatch for multi-route apps, e.g. the OpenAI
        ingress — ray_tpu.serve.llm.openai_api.)"""
        self._ensure_started()
        state = self._deployments.get(f"{app_name}#{deployment}")
        if state is None:
            return False
        import cloudpickle
        try:
            cls = cloudpickle.loads(state.serialized_cls)
        except Exception:  # noqa: BLE001
            return False
        return callable(getattr(cls, "handle_http", None))

    async def status(self) -> dict:
        self._ensure_started()
        return {
            state.full_name(): {
                "replicas": len(state.replicas),
                "draining": len(state.draining),
                "warming": len(state.warming),
                "target": state.target,
                "version": state.version,
                "app": state.app,
                "role": state.config.role,
            }
            for state in self._deployments.values()
        }

    async def detailed_status(self) -> dict:
        """status() plus live per-replica queue lengths (dashboard serve
        view; reference: dashboard/modules/serve/ deployment details)."""
        self._ensure_started()

        async def probe(replica):
            try:
                return int(await asyncio.wait_for(
                    replica.get_queue_len.remote(), timeout=2.0))
            except Exception:  # noqa: BLE001 — replica busy/dead
                return None

        # engine stats ride next to the live queue lens: deployments whose
        # callable defines engine_stats() (LLM servers) report steps /
        # prefills / tokens_out / shed counts / prefix-cache hit-miss-evict
        # counters per replica — plus the ISSUE-6 introspection surface
        # (per-phase p50/p95, ITL, compile events, device memory) that the
        # dashboard /profiling panel renders; anything else probes to None
        from ray_tpu.observability.profiling import PHASES, STARTUP_TOTALS
        _ENGINE_KEYS = ("steps", "prefills", "tokens_out", "requests",
                        "shed_expired",
                        "active_slots", "waiting", "free_pages",
                        "failover_resumed", "failover_restored_tokens",
                        "prefix_hits", "prefix_misses", "prefix_hit_tokens",
                        "prefix_cached_pages", "prefix_shared_pages",
                        "prefix_evictions",
                        "spilled_pages", "restored_pages",
                        "restore_partial", "restoring",
                        "warm_start_pages", "warm_start_ms",
                        "disagg_prefills", "handoff_bytes_wire",
                        "handoff_overlap_ms",
                        "tier_hit_tokens", "tier_bytes_shm",
                        "tier_bytes_disk",
                        "tier_bytes_shm_raw", "tier_bytes_disk_raw",
                        "tier_codec_ratio",
                        "tier_encode_ms_p50", "tier_decode_ms_p50",
                        "tier_prefetch_hints", "tier_prefetch_pages",
                        "tier_prefetch_hit_pages",
                        "prefix_summary_version", "prefix_summary_pages",
                        "decode_block_effective", "pending_pipeline_depth",
                        "dispatch_tier_admit_total",
                        "dispatch_tier_pressure_total",
                        "dispatch_tier_idle_total", "idle_lead_k",
                        "lead_climbs_total", "lead_descents_total",
                        "spec_rounds", "spec_drafted_tokens",
                        "spec_accepted_tokens",
                        "attention_backend", "attn_backend_pallas",
                        "attn_kernel_compiles", "attn_decode_dispatches",
                        "attn_verify_dispatches", "attn_chunk_dispatches",
                        "chunk_heads_skipped", "greedy_dispatches",
                        "device_platform", "device_kind", "device_count",
                        "attn_interpret",
                        "tp_degree", "mesh_shape", "kv_shard_pool_bytes",
                        "kv_shard_page_occupancy",
                        "itl_s", "compile_events", "mid_traffic_compiles",
                        "compile_s", "weights_bytes", "kv_pool_bytes",
                        *STARTUP_TOTALS,
                        "kv_page_occupancy", "device_bytes_in_use",
                        "device_peak_bytes",
                        "host_stall_s_total", "host_stall_n",
                        "gc_pause_s_total", "gc_pause_n", "gc_pause_max_ms",
                        "gc_young_s_total", "gc_young_n",
                        "dry_dispatches_total", "dry_s_total",
                        "clock_s") + tuple(
                            f"phase_{p}_{q}" for p in PHASES
                            for q in ("p50_ms", "p95_ms", "s_total", "n"))

        async def probe_engine(replica):
            try:
                stats = await asyncio.wait_for(
                    replica.handle_request.remote("engine_stats", (), {}),
                    timeout=2.0)
            except Exception:  # noqa: BLE001 — not an engine / busy / dead
                return None
            if not isinstance(stats, dict):
                return None
            return {k: stats[k] for k in _ENGINE_KEYS if k in stats}

        out = {}
        for state in self._deployments.values():
            # concurrent probes: a deployment of N hung replicas must cost
            # one 2s timeout, not N of them (the dashboard polls this)
            qlens = list(await asyncio.gather(
                *(probe(r) for r in state.replicas)))
            engines = list(await asyncio.gather(
                *(probe_engine(r) for r in state.replicas)))
            out[state.full_name()] = {
                "app": state.app,
                "role": state.config.role,
                "replicas": len(state.replicas),
                "starting": len(state.starting),
                "warming": len(state.warming),
                "draining": len(state.draining),
                "target": state.target,
                "version": state.version,
                "queue_lens": qlens,
                "engine": (engines if any(e is not None for e in engines)
                           else None),
                "latency_ms": self._latency_percentiles(state.name),
                # elastic fleet (ISSUE 17): the scale-decision flight
                # recorder + cache-warm scale-up economy the dashboard
                # serve panel and the open-loop harness render
                "scale_decisions": list(state.scale_decisions[-10:]),
                "scale_counters": dict(state.scale_counters),
                "warm": dict(state.warm_stats),
                "signals": dict(state._signals),
            }
        return out

    @staticmethod
    def _latency_percentiles(deployment: str) -> dict | None:
        """p50/p95/p99 (ms) from the CP time-series store: the merged
        cross-replica cumulative histogram of on-replica processing latency
        (ISSUE 4 percentile views). None until the replicas' flushers have
        reported."""
        try:
            from ray_tpu.core import api as _api
            from ray_tpu.util.metrics import percentiles_from_buckets
            rt = _api._try_get_runtime()
            if rt is None:
                return None
            res = rt.cp_client.call(
                "metrics_query",
                {"name": "ray_tpu_serve_replica_processing_seconds",
                 "tags": {"deployment": deployment}}, timeout=5.0)
            merged = (res or {}).get("merged")
            if not merged or not merged.get("count"):
                return None
            qs = percentiles_from_buckets(
                res.get("boundaries") or [], merged["buckets"])
            return {f"p{round(q * 100)}": (None if v is None else v * 1000.0)
                    for q, v in qs.items()}
        except Exception:  # noqa: BLE001 — metrics are best-effort
            return None

    async def shutdown(self) -> bool:
        self._stopped = True
        for state in self._deployments.values():
            for t in state._warm_tasks.values():
                t.cancel()
            for r in (state.replicas + state.starting + state.warming
                      + state.draining):
                try:
                    ray_tpu.kill(r)
                except Exception:  # noqa: BLE001
                    pass
        self._deployments = {}
        return True

    # ---- reconciliation loop -------------------------------------------
    async def _control_loop(self):
        while not self._stopped:
            try:
                await self._reconcile_once()
            except Exception:  # noqa: BLE001
                logger.exception("serve control loop error")
            await asyncio.sleep(0.2)

    @staticmethod
    def _replica_key(replica) -> str:
        aid = getattr(replica, "_actor_id", None)
        return aid.hex() if hasattr(aid, "hex") else str(id(replica))

    async def _drop_replicas_on_dead_nodes(self):
        """Drain node-death events and immediately drop (and kill) replicas
        placed on those nodes — the reconcile pass below restarts
        replacements this same tick."""
        with self._dead_nodes_lock:
            dead, self._dead_nodes = list(self._dead_nodes), []
        if not dead:
            return
        dead_set = set(dead)

        def _list_actors_blocking():
            from ray_tpu.util import state as state_api
            return state_api.list_actors(limit=100000)

        try:
            actors = await asyncio.get_event_loop().run_in_executor(
                None, _list_actors_blocking)
        except Exception:  # noqa: BLE001 — CP briefly away; health checks
            logger.exception("list_actors failed while handling node death")
            return
        on_dead_nodes = {a["actor_id"] for a in actors
                         if a.get("node_id") in dead_set}
        for state in self._deployments.values():
            keep = [r for r in state.replicas
                    if self._replica_key(r) not in on_dead_nodes]
            if len(keep) != len(state.replicas):
                lost = len(state.replicas) - len(keep)
                logger.warning(
                    "%s: %d replica(s) on dead node(s) %s — replacing",
                    state.full_name(), lost,
                    [n[:8] for n in dead_set])
                for r in state.replicas:
                    if self._replica_key(r) in on_dead_nodes:
                        state.health_fails.pop(self._replica_key(r), None)
                        try:
                            ray_tpu.kill(r)  # idempotent; frees CP state
                        except Exception:  # noqa: BLE001
                            pass
                state.replicas = keep
                state.version += 1
                self._notify_change()
            # a draining node that died (deadline hit, or crashed mid-drain)
            # takes its still-routable replicas with it
            left = [r for r in state.draining
                    if self._replica_key(r) not in on_dead_nodes]
            if len(left) != len(state.draining):
                for r in state.draining:
                    if self._replica_key(r) in on_dead_nodes:
                        state.health_fails.pop(self._replica_key(r), None)
                        try:
                            ray_tpu.kill(r)
                        except Exception:  # noqa: BLE001
                            pass
                state.draining = left
                state.version += 1
                self._notify_change()
            # a STARTING replica on a dead node will never become ready;
            # a WARMING one will never finish its warm_start — both are
            # pre-table, so no version bump, just re-place via scale-up
            still = [r for r in state.starting
                     if self._replica_key(r) not in on_dead_nodes]
            if len(still) != len(state.starting):
                for r in state.starting:
                    if self._replica_key(r) in on_dead_nodes:
                        try:
                            ray_tpu.kill(r)
                        except Exception:  # noqa: BLE001
                            pass
                state.starting = still
            warm_left = [r for r in state.warming
                         if self._replica_key(r) not in on_dead_nodes]
            if len(warm_left) != len(state.warming):
                for r in state.warming:
                    if self._replica_key(r) in on_dead_nodes:
                        t = state._warm_tasks.pop(self._replica_key(r), None)
                        if t is not None:
                            t.cancel()
                        try:
                            ray_tpu.kill(r)
                        except Exception:  # noqa: BLE001
                            pass
                state.warming = warm_left

    async def _move_replicas_on_draining_nodes(self):
        """Drain node-DRAINING events: replicas on those nodes move
        replicas → draining. They stay in the routing table (the node keeps
        serving until its drain deadline) but stop counting toward target,
        so the scale-up pass pre-starts replacements elsewhere this same
        tick — the table is only flipped away from them once the
        replacements are ready (ref: DrainRaylet + deployment_state
        graceful replacement)."""
        with self._dead_nodes_lock:
            draining, self._draining_nodes = list(self._draining_nodes), []
        if not draining:
            return
        draining_set = set(draining)

        def _list_actors_blocking():
            from ray_tpu.util import state as state_api
            return state_api.list_actors(limit=100000)

        try:
            actors = await asyncio.get_event_loop().run_in_executor(
                None, _list_actors_blocking)
        except Exception:  # noqa: BLE001 — CP briefly away; retry next event
            logger.exception("list_actors failed while handling node drain")
            with self._dead_nodes_lock:
                self._draining_nodes.extend(draining)
            return
        on_draining = {a["actor_id"] for a in actors
                       if a.get("node_id") in draining_set}
        for state in self._deployments.values():
            moving = [r for r in state.replicas
                      if self._replica_key(r) in on_draining]
            if moving:
                logger.warning(
                    "%s: %d replica(s) on draining node(s) %s — pre-starting "
                    "replacements before retiring them",
                    state.full_name(), len(moving),
                    [n[:8] for n in draining_set])
                state.replicas = [r for r in state.replicas
                                  if self._replica_key(r) not in on_draining]
                state.draining.extend(moving)
                # no version bump: the routing table still contains them
                # Drain pre-move spill (ISSUE 14): tell the moving
                # replicas to push in-flight KV chains into the tier NOW
                # — when the node dies, streams mid-generation there get
                # re-dispatched as continuations and the replacements
                # restore this work instead of recomputing it.
                # Fire-and-forget: drain must not block on a spill.
                for r in moving:
                    try:
                        r.prepare_to_move.remote()  # graftlint: fire-and-forget
                    except Exception:  # noqa: BLE001
                        pass
            # STARTING/WARMING replicas on a draining node would come up
            # on a node about to disappear — kill now, scale-up re-places
            # them (both are pre-table: no version traffic)
            doomed = [r for r in state.starting + state.warming
                      if self._replica_key(r) in on_draining]
            if doomed:
                state.starting = [r for r in state.starting
                                  if self._replica_key(r) not in on_draining]
                state.warming = [r for r in state.warming
                                 if self._replica_key(r) not in on_draining]
                for r in doomed:
                    t = state._warm_tasks.pop(self._replica_key(r), None)
                    if t is not None:
                        t.cancel()
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001
                        pass

    async def _collect_summaries(self):
        """Refresh per-replica prefix summaries (ISSUE 10). Rate-limited;
        per-replica `since` versions make an idle fleet answer with tiny
        "unchanged" markers. A changed deployment bumps its summary_gen and
        wakes long-pollers WITHOUT a routing-table version bump (routers
        must not reshuffle probe caches for a summary delta)."""
        now = time.monotonic()
        if now - self._summary_ts < self._summary_interval_s:
            return
        self._summary_ts = now

        async def probe_summary(state, replica):
            key = self._replica_key(replica)
            if key in state.summary_unsupported:
                return False
            since = state.summary_versions.get(key)
            try:
                res = await asyncio.wait_for(_as_future(
                    replica.handle_request.remote(
                        "prefix_summary", (since,), {}), timeout=2.0), 3.0)
            except asyncio.TimeoutError:
                return False  # busy replica: retry next round
            except Exception as e:  # noqa: BLE001
                # only a proven-missing prefix_summary method (plain
                # deployment: getattr raises AttributeError, a wrong
                # signature TypeError) is permanent; any other failure
                # is a replica fault — transient blips must not exile a
                # healthy replica from affinity until replacement
                cause = e.cause if isinstance(e, TaskError) else e
                if isinstance(cause, (AttributeError, TypeError)):
                    state.summary_unsupported.add(key)
                return False
            if not isinstance(res, dict) or not res.get("supported"):
                state.summary_unsupported.add(key)
                return False
            if res.get("meta"):
                state.summary_meta = dict(res["meta"])
            if res.get("unchanged"):
                return False
            state.summary_versions[key] = int(res.get("version", 0))
            digests = list(res.get("digests") or [])
            if state.summaries.get(key) == digests:
                return False
            state.summaries[key] = digests
            return True

        for state in list(self._deployments.values()):
            changed = False
            # prune entries for replicas that left the routable-and-counted
            # set (dead, draining, replaced): they must exit every router's
            # affinity candidate set on the NEXT poll, and a replacement
            # replica starts cold
            live = {self._replica_key(r) for r in state.replicas}
            for k in [k for k in state.summaries if k not in live]:
                del state.summaries[k]
                state.summary_versions.pop(k, None)
                changed = True
            state.summary_unsupported &= live
            for k in [k for k in state.summary_versions if k not in live]:
                del state.summary_versions[k]
            if state.replicas:
                flags = await asyncio.gather(
                    *(probe_summary(state, r) for r in state.replicas))
                changed = changed or any(flags)
            if changed:
                state.summary_gen += 1
                self._notify_change()

    async def _warm_one(self, state: _DeploymentState, replica) -> dict:
        """Cache-warm one READY but unpublished replica (ISSUE 17): a
        single bounded warm_start RPC through the generic dispatch. The
        replica restores the fleet's hottest KV-tier chains into its
        prefix cache; unsupported deployments (plain callables, tier
        off) resolve immediately. A hung warm is promoted cold by the
        timeout rather than parked forever."""
        try:
            res = await asyncio.wait_for(_as_future(
                replica.handle_request.remote("warm_start", (), {}),
                timeout=30.0), 35.0)
        except Exception as e:  # noqa: BLE001 — promote cold
            cause = e.cause if isinstance(e, TaskError) else e
            if not isinstance(cause, (AttributeError, TypeError)):
                logger.warning("%s: warm_start failed — promoting cold: %r",
                               state.full_name(), e)
            return {"supported": False, "pages": 0}
        if isinstance(res, dict) and res.get("supported"):
            logger.info(
                "%s: warm start landed %s pages / %s chains in %s ms",
                state.full_name(), res.get("pages", 0),
                res.get("chains", 0), res.get("ms", 0.0))
            return res
        return {"supported": False, "pages": 0}

    async def _collect_scale_signals(self, state: _DeploymentState) -> dict:
        """Serve-plane signals for decide_signals (ISSUE 17), refreshed
        at most every 2 s and cached between refreshes. Everything
        degrades to absence (pure queue-length policy) when the exemplar
        store or affinity summaries aren't there."""
        now = time.monotonic()
        if now - state._signals_ts < 2.0:
            return state._signals
        state._signals_ts = now
        sig: dict = {}
        # affinity heat from the ISSUE-10 summaries already in hand:
        # per-replica resident-page skew (what the router's load ×
        # locality score is fighting) and the share of replicas holding
        # anything (what a downscale would evict)
        counts = [len(state.summaries.get(self._replica_key(r)) or [])
                  for r in state.replicas]
        if counts:
            mean = sum(counts) / len(counts)
            sig["prefill_skew"] = (round(max(counts) / mean, 3)
                                   if mean > 0 else 0.0)
            sig["affinity_hit_share"] = round(
                sum(1 for c in counts if c > 0) / len(counts), 3)

        # PR 12 attribution: violation count + dominant p99-TTFT stage
        # for this deployment's exemplar window (CP call → executor)
        def _report():
            from ray_tpu.util import state as state_api
            return state_api.slo_report(deployment=state.name)

        try:
            rep = await asyncio.get_event_loop().run_in_executor(
                None, _report)
        except Exception:  # noqa: BLE001 — attribution absent
            rep = None
        if isinstance(rep, dict) and rep.get("count"):
            sig["slo_violations"] = int(rep.get("violations") or 0)
            dom = rep.get("dominant_stage") or {}
            if isinstance(dom, dict) and dom:
                sig["dominant_stage"] = max(dom.items(),
                                            key=lambda kv: kv[1])[0]
        state._signals = sig
        return sig

    def _record_scale(self, state: _DeploymentState, prev: int, new: int,
                      reason: str, signals: Optional[dict] = None):
        """Append to the deployment's bounded scale-decision log (the
        dashboard/harness flight recorder) and bump the reason counter."""
        state.scale_counters[reason] = \
            state.scale_counters.get(reason, 0) + 1
        state.scale_decisions.append({
            "ts": time.time(), "from": int(prev), "to": int(new),
            "reason": reason, "signals": dict(signals or {})})
        del state.scale_decisions[:-50]
        # full history rides the journal — the CP's severity-tiered
        # store outlives the last-50 local window above (ISSUE 19)
        _fr.emit("replica_scale", "INFO",
                 deployment=state.full_name(), reason=reason,
                 attrs={"from": int(prev), "to": int(new),
                        "signals": dict(signals or {})})

    async def _pick_downscale_victim(self, state: _DeploymentState):
        """Coldest, least-loaded replica: fewest exported prefix-summary
        digests first (retiring a hot holder evicts the fleet's working
        set), then shortest live queue. An unreachable probe scores as
        idle — the health sweep reclaims a genuinely dead replica either
        way."""
        scored = []
        for i, r in enumerate(state.replicas):
            heat = len(state.summaries.get(self._replica_key(r)) or [])
            try:
                q = int(await asyncio.wait_for(
                    _as_future(r.get_queue_len.remote()), 2.0))
            except Exception:  # noqa: BLE001
                q = 0
            scored.append((heat, q, i, r))
        scored.sort(key=lambda t: (t[0], t[1], -t[2]))
        return scored[0][3]

    async def set_target_replicas(self, app_name: str,
                                  deployment: Optional[str] = None,
                                  target: Optional[int] = None,
                                  delta: Optional[int] = None,
                                  reason: str = "manual") -> dict:
        """Imperative scale knob (bench schedules, `replica_scale` chaos
        events, operators). Sets the reconcile target directly: scale-up
        goes through STARTING → WARMING → one atomic publish; scale-down
        drains the coldest replica with zero dropped requests. Clamped
        to the autoscaling [min, max] when one is configured, and to
        >= 1 always. Returns {full_name: target} for the touched
        deployments."""
        self._ensure_started()
        out = {}
        for state in list(self._deployments.values()):
            if state.app != app_name:
                continue
            if deployment is not None and state.name != deployment:
                continue
            new = state.target if target is None else int(target)
            if target is None and delta is not None:
                new = state.target + int(delta)
            asc = state.config.autoscaling_config
            if asc is not None:
                new = max(asc.min_replicas, min(asc.max_replicas, new))
            new = max(1, new)
            if new != state.target:
                self._record_scale(state, state.target, new, reason,
                                   state._signals)
                logger.info("set_target_replicas %s: %d -> %d (%s)",
                            state.full_name(), state.target, new, reason)
                state.target = new
                state._pending_target = None
            out[state.full_name()] = state.target
        return out

    async def _reconcile_once(self):
        await self._drop_replicas_on_dead_nodes()
        await self._move_replicas_on_draining_nodes()
        for state in list(self._deployments.values()):
            # readiness: a freshly created replica becomes routable only
            # after its first successful health check (the reference's
            # STARTING → RUNNING transition) — publishing it earlier would
            # queue live requests behind actor creation
            if state.starting:
                ready_flags = await asyncio.gather(
                    *(_probe_ready(r) for r in state.starting))
                became = [r for r, ok in zip(state.starting, ready_flags)
                          if ok]
                if became:
                    state.starting = [
                        r for r, ok in zip(state.starting, ready_flags)
                        if not ok]
                    # cache-warm scale-up (ISSUE 17): a ready replica is
                    # NOT published yet — it first pre-populates its
                    # prefix cache from the KV tier (WARMING). Promotion
                    # below is the only way into the routing table.
                    state.warming.extend(became)
                    for r in became:
                        state._warm_tasks[self._replica_key(r)] = \
                            asyncio.ensure_future(self._warm_one(state, r))

            # promote warmed replicas. The list mutation and the version
            # bump happen in ONE synchronous block (no await between), so
            # a long-poller can never observe a table that contains the
            # new replica under the old version — or the bumped version
            # without the replica (ISSUE 17 atomicity fix). Warming is
            # best-effort: a failed/unsupported/timed-out warm promotes
            # the replica cold rather than parking it forever.
            if state.warming:
                done = [r for r in state.warming
                        if state._warm_tasks.get(
                            self._replica_key(r), None) is None
                        or state._warm_tasks[self._replica_key(r)].done()]
                if done:
                    for r in done:
                        t = state._warm_tasks.pop(self._replica_key(r), None)
                        res = None
                        if t is not None and t.done() and not t.cancelled():
                            try:
                                res = t.result()
                            except Exception:  # noqa: BLE001
                                res = None
                        if isinstance(res, dict) and res.get("supported"):
                            state.warm_stats["replicas_warmed"] += 1
                            state.warm_stats["pages"] += int(
                                res.get("pages") or 0)
                            state.warm_stats["ms"] = round(
                                state.warm_stats["ms"]
                                + float(res.get("ms") or 0.0), 3)
                            _fr.emit(
                                "warm_start", "INFO",
                                deployment=state.full_name(),
                                replica=self._replica_key(r),
                                attrs={
                                    "pages": int(res.get("pages") or 0),
                                    "chains": int(res.get("chains") or 0),
                                    "ms": float(res.get("ms") or 0.0)})
                    done_set = {self._replica_key(r) for r in done}
                    state.warming = [
                        r for r in state.warming
                        if self._replica_key(r) not in done_set]
                    state.replicas.extend(done)
                    state.version += 1
                    self._notify_change()
                    _fr.emit("table_publish", "INFO",
                             deployment=state.full_name(),
                             reason="warmed replicas promoted",
                             attrs={"version": state.version,
                                    "replicas": len(state.replicas)})

            # health: drop replicas only after `health_check_failure_threshold`
            # CONSECUTIVE failures (one transient miss must not cost a
            # replica), and kill() the dropped actor so its worker process
            # doesn't leak
            threshold = max(1, state.config.health_check_failure_threshold)
            alive = []
            for r in state.replicas:
                key = self._replica_key(r)
                try:
                    await asyncio.wait_for(_as_future(
                        r.check_health.remote(),
                        timeout=state.config.health_check_timeout_s),
                        state.config.health_check_timeout_s + 1.0)
                    state.health_fails.pop(key, None)
                    alive.append(r)
                except Exception:  # noqa: BLE001
                    fails = state.health_fails.get(key, 0) + 1
                    state.health_fails[key] = fails
                    logger.warning(
                        "replica of %s failed health check (%d/%d)",
                        state.full_name(), fails, threshold)
                    if fails < threshold:
                        alive.append(r)
                        continue
                    state.health_fails.pop(key, None)
                    _fr.emit("replica_death", "ERROR",
                             deployment=state.full_name(), replica=key,
                             reason=f"{fails} consecutive failed "
                                    "health checks")
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
            if len(alive) != len(state.replicas):
                state.replicas = alive
                state.version += 1
                self._notify_change()
                _fr.emit("table_publish", "INFO",
                         deployment=state.full_name(),
                         reason="dead replicas removed",
                         attrs={"version": state.version,
                                "replicas": len(state.replicas)})

            # draining replicas are still routable, so they get the same
            # health policy — one that dies mid-drain must leave the table
            if state.draining:
                keep_draining = []
                for r in state.draining:
                    key = self._replica_key(r)
                    try:
                        await asyncio.wait_for(_as_future(
                            r.check_health.remote(),
                            timeout=state.config.health_check_timeout_s),
                            state.config.health_check_timeout_s + 1.0)
                        state.health_fails.pop(key, None)
                        keep_draining.append(r)
                    except Exception:  # noqa: BLE001
                        fails = state.health_fails.get(key, 0) + 1
                        state.health_fails[key] = fails
                        if fails < threshold:
                            keep_draining.append(r)
                            continue
                        state.health_fails.pop(key, None)
                        try:
                            ray_tpu.kill(r)
                        except Exception:  # noqa: BLE001
                            pass
                if len(keep_draining) != len(state.draining):
                    state.draining = keep_draining
                    state.version += 1
                    self._notify_change()

            # retire draining replicas once enough replacements are READY:
            # flip the routing table first (version bump → routers/proxies
            # long-poll the new set), THEN stop the old replicas gracefully
            # so their in-flight requests complete — a drain drops zero
            # requests (ISSUE acceptance)
            if state.draining and len(state.replicas) >= state.target:
                retired, state.draining = list(state.draining), []
                state.version += 1
                self._notify_change()
                logger.info("%s: retiring %d drained replica(s) — "
                            "replacements are serving", state.full_name(),
                            len(retired))
                for r in retired:
                    state.health_fails.pop(self._replica_key(r), None)
                    try:
                        await asyncio.wait_for(_as_future(
                            r.prepare_for_shutdown.remote(
                                state.config.graceful_shutdown_timeout_s)),
                            state.config.graceful_shutdown_timeout_s + 5.0)
                    except Exception:  # noqa: BLE001
                        pass
                    try:
                        ray_tpu.kill(r)
                    except Exception:  # noqa: BLE001
                        pass

            # autoscaling: queue-length policy folded with serve-plane
            # signals (ISSUE 17) — PR 12 SLO attribution (violations +
            # dominant p99-TTFT stage) and PR 10/14 affinity heat (hit
            # share, per-replica summary-page skew). Signals degrade to
            # {} when the exemplar store or summaries are absent, which
            # reduces decide_signals to the original queue policy.
            asc = state.config.autoscaling_config
            if asc is not None and state.replicas:
                total = 0
                for r in state.replicas:
                    try:
                        total += await asyncio.wait_for(
                            _as_future(r.get_queue_len.remote()), 2.0)
                    except Exception:  # noqa: BLE001
                        pass
                signals = await self._collect_scale_signals(state)
                desired, reason = asc.decide_signals(
                    len(state.replicas), total, signals)
                now = time.monotonic()
                if desired != state.target:
                    delay = (asc.upscale_delay_s if desired > state.target
                             else asc.downscale_delay_s)
                    if state._pending_target != desired:
                        state._pending_target = desired
                        state._scale_pending_since = now
                    elif now - state._scale_pending_since >= delay:
                        logger.info("autoscaling %s: %d -> %d (%s)",
                                    state.full_name(), state.target,
                                    desired, reason)
                        self._record_scale(state, state.target, desired,
                                           reason, signals)
                        state.target = desired
                        state._pending_target = None
                else:
                    state._pending_target = None
                    # a heat-guard refusal is a scale decision too: log
                    # it once per continuous guard episode, not per tick
                    if reason == "heat_guard":
                        if not state._guard_episode:
                            state._guard_episode = True
                            self._record_scale(state, state.target,
                                               state.target, reason,
                                               signals)
                    else:
                        state._guard_episode = False

            # scale toward target; new replicas go through STARTING (and
            # then WARMING) and are published to routers only once warm
            counted = (len(state.replicas) + len(state.starting)
                       + len(state.warming))
            while counted < state.target:
                replica = ServeReplica.options(
                    max_concurrency=max(100, state.config.max_ongoing_requests),
                    **state.config.ray_actor_options).remote(
                    state.name, state.serialized_cls, state.init_args,
                    state.init_kwargs, state.config.user_config,
                    state.config.max_ongoing_requests)
                state.starting.append(replica)
                counted += 1
            while counted > state.target:
                counted -= 1
                # prefer killing replicas that never took traffic
                if state.starting:
                    victim = state.starting.pop()
                elif state.warming:
                    victim = state.warming.pop()
                    t = state._warm_tasks.pop(
                        self._replica_key(victim), None)
                    if t is not None:
                        t.cancel()
                else:
                    # graceful downscale (ISSUE 17): pick the coldest,
                    # least-loaded replica and move it to DRAINING — the
                    # retirement block above flips the routing table
                    # first next tick, then prepare_for_shutdown lets
                    # its in-flight streams finish (spilling KV for any
                    # that must resume elsewhere) before the kill. No
                    # request is dropped, no resumed stream diverges.
                    victim = await self._pick_downscale_victim(state)
                    state.replicas.remove(victim)
                    state.draining.append(victim)
                    logger.info(
                        "%s: downscale — draining replica %s",
                        state.full_name(),
                        self._replica_key(victim)[:8])
                    continue  # still routable; retired gracefully later
                try:
                    ray_tpu.kill(victim)
                except Exception:  # noqa: BLE001
                    pass

        # prefix-affinity summaries ride the reconcile loop (rate-limited
        # inside): collection must see the post-churn replica sets so a
        # replica dropped above leaves every router's candidate set now
        await self._collect_summaries()


async def _as_future(ref, timeout: Optional[float] = None):
    """Adapt a ray_tpu ObjectRef get to asyncio without blocking the loop.
    Pass `timeout` so the executor thread unblocks itself even when the
    awaiting coroutine gives up first (asyncio.wait_for cannot interrupt
    a thread already parked in ray_tpu.get)."""
    loop = asyncio.get_event_loop()
    return await loop.run_in_executor(
        None, lambda: ray_tpu.get(ref, timeout=timeout))


async def _probe_ready(replica) -> bool:
    """One bounded readiness probe (first health check) of a STARTING
    replica. The short timeout keeps the reconcile tick fast; a replica
    still constructing simply stays in STARTING until a later tick."""
    try:
        await asyncio.wait_for(
            _as_future(replica.check_health.remote(), timeout=1.0), 2.0)
        return True
    except Exception:  # noqa: BLE001 — not up yet (or already dead)
        return False


def get_or_create_controller():
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME, timeout=0.2)
    except Exception:  # noqa: BLE001 - create it
        return ServeController.options(
            name=CONTROLLER_NAME, lifetime="detached",
            max_concurrency=1000).remote()
