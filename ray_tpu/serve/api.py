"""Serve public API: @deployment, run, shutdown, status, handles.

TPU-native analog of the reference's serve API
(/root/reference/python/ray/serve/api.py — @serve.deployment:333,
serve.run:685; _private/client.py deploy_applications). Applications are
graphs of deployments built with `.bind()` (the reference's DAG builder);
`serve.run` ships them to the controller which reconciles replica actors.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

import cloudpickle

import ray_tpu
from ray_tpu.serve.config import AutoscalingConfig, DeploymentConfig
from ray_tpu.serve.controller import get_or_create_controller
from ray_tpu.serve.handle import DeploymentHandle, _reset_routers

_lock = threading.Lock()
_proxy = None  # (HTTPProxy, port) — primary ingress
# Multi-proxy ingress (ISSUE 17): additional HTTPProxy instances behind
# the same fleet (start_http_proxies). They share ONE router map — one
# controller long-poll per app for the whole ingress tier — and each
# serves its own /-/stats. All are stopped by shutdown().
_extra_proxies: list = []
_shared_routers: dict = {}


class Application:
    """A bound deployment graph node (reference: Application from
    Deployment.bind)."""

    def __init__(self, deployment: "Deployment", init_args, init_kwargs):
        self.deployment = deployment
        self.init_args = init_args
        self.init_kwargs = init_kwargs

    def _collect(self, out: list, seen: set) -> None:
        """Topo-collect all deployments reachable through bound args."""
        for arg in list(self.init_args) + list(self.init_kwargs.values()):
            if isinstance(arg, Application) and id(arg) not in seen:
                seen.add(id(arg))
                arg._collect(out, seen)
        if self not in out:
            out.append(self)


class Deployment:
    def __init__(self, func_or_class, name: str, config: DeploymentConfig,
                 route_prefix: Optional[str] = "/"):
        self.func_or_class = func_or_class
        self.name = name
        self.config = config
        self.route_prefix = route_prefix

    def options(self, *, name: Optional[str] = None,
                num_replicas: Optional[Any] = None,
                max_ongoing_requests: Optional[int] = None,
                user_config: Any = None,
                autoscaling_config: Optional[dict | AutoscalingConfig] = None,
                route_prefix: Optional[str] = "__unset__",
                ray_actor_options: Optional[dict] = None,
                health_check_period_s: Optional[float] = None,
                health_check_failure_threshold: Optional[int] = None,
                request_timeout_s: Optional[float] = None,
                slo_ttft_p99_ms: Optional[float] = None,
                slo_e2e_p99_ms: Optional[float] = None,
                slo_sample_rate: Optional[float] = None,
                graceful_shutdown_timeout_s: Optional[float] = None) -> "Deployment":
        import copy
        cfg = copy.deepcopy(self.config)
        if num_replicas is not None:
            if num_replicas == "auto":
                cfg.autoscaling_config = cfg.autoscaling_config or AutoscalingConfig()
            else:
                cfg.num_replicas = int(num_replicas)
        if max_ongoing_requests is not None:
            cfg.max_ongoing_requests = max_ongoing_requests
        if user_config is not None:
            cfg.user_config = user_config
        if autoscaling_config is not None:
            if isinstance(autoscaling_config, dict):
                autoscaling_config = AutoscalingConfig(**autoscaling_config)
            cfg.autoscaling_config = autoscaling_config
        if ray_actor_options is not None:
            cfg.ray_actor_options = ray_actor_options
        if health_check_period_s is not None:
            cfg.health_check_period_s = health_check_period_s
        if health_check_failure_threshold is not None:
            cfg.health_check_failure_threshold = health_check_failure_threshold
        if request_timeout_s is not None:
            cfg.request_timeout_s = request_timeout_s
        if slo_ttft_p99_ms is not None:
            cfg.slo_ttft_p99_ms = slo_ttft_p99_ms
        if slo_e2e_p99_ms is not None:
            cfg.slo_e2e_p99_ms = slo_e2e_p99_ms
        if slo_sample_rate is not None:
            cfg.slo_sample_rate = slo_sample_rate
        if graceful_shutdown_timeout_s is not None:
            cfg.graceful_shutdown_timeout_s = graceful_shutdown_timeout_s
        return Deployment(
            self.func_or_class, name or self.name, cfg,
            self.route_prefix if route_prefix == "__unset__" else route_prefix)

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def __call__(self, *a, **k):
        raise RuntimeError(
            "deployments are not directly callable; use .bind() + serve.run "
            "then handle.remote()")


def deployment(_func_or_class=None, *, name: Optional[str] = None,
               num_replicas: Any = None, max_ongoing_requests: int = 100,
               user_config: Any = None,
               autoscaling_config: Optional[dict | AutoscalingConfig] = None,
               ray_actor_options: Optional[dict] = None,
               health_check_period_s: float = 2.0,
               health_check_timeout_s: float = 30.0,
               health_check_failure_threshold: int = 3,
               request_timeout_s: Optional[float] = None,
               slo_ttft_p99_ms: Optional[float] = None,
               slo_e2e_p99_ms: Optional[float] = None,
               slo_sample_rate: float = 0.01,
               graceful_shutdown_timeout_s: float = 20.0):
    """@serve.deployment decorator (reference api.py:333)."""

    def decorate(obj):
        cfg = DeploymentConfig(
            max_ongoing_requests=max_ongoing_requests,
            user_config=user_config,
            health_check_period_s=health_check_period_s,
            health_check_timeout_s=health_check_timeout_s,
            health_check_failure_threshold=health_check_failure_threshold,
            request_timeout_s=request_timeout_s,
            slo_ttft_p99_ms=slo_ttft_p99_ms,
            slo_e2e_p99_ms=slo_e2e_p99_ms,
            slo_sample_rate=slo_sample_rate,
            graceful_shutdown_timeout_s=graceful_shutdown_timeout_s,
            ray_actor_options=ray_actor_options or {})
        if num_replicas == "auto":
            cfg.autoscaling_config = AutoscalingConfig()
        elif num_replicas is not None:
            cfg.num_replicas = int(num_replicas)
        if autoscaling_config is not None:
            cfg.autoscaling_config = (
                AutoscalingConfig(**autoscaling_config)
                if isinstance(autoscaling_config, dict) else autoscaling_config)
        return Deployment(obj, name or obj.__name__, cfg)

    if _func_or_class is not None:
        return decorate(_func_or_class)
    return decorate


def run(app: Application, *, name: str = "default",
        route_prefix: Optional[str] = "/", blocking: bool = False,
        _local_testing_mode: bool = False) -> DeploymentHandle:
    """Deploy an application; returns a handle to the ingress deployment
    (reference serve.run api.py:685). With ``_local_testing_mode`` the
    whole application runs IN-PROCESS — no cluster, no controller, no
    replica actors (reference _private/local_testing_mode.py) — for unit
    tests and notebooks."""
    if _local_testing_mode:
        from ray_tpu.serve.local_testing import run_local
        return run_local(app, name)
    if not ray_tpu.is_initialized():
        ray_tpu.init()
    controller = get_or_create_controller()

    ordered: list[Application] = []
    app._collect(ordered, set())
    ingress = ordered[-1]

    specs = []
    for node in ordered:
        dep = node.deployment
        init_args, handle_args = [], []
        # bound sub-applications become handles at construction time
        def conv(v):
            if isinstance(v, Application):
                return DeploymentHandle(v.deployment.name, name)
            return v
        args = tuple(conv(a) for a in node.init_args)
        kwargs = {k: conv(v) for k, v in node.init_kwargs.items()}
        specs.append({
            "name": dep.name,
            "serialized_cls": cloudpickle.dumps(dep.func_or_class),
            "init_args": args, "init_kwargs": kwargs,
            "config": dep.config,
            "route_prefix": route_prefix if node is ingress else None,
            "is_ingress": node is ingress,
        })
    # the controller waits for the slowest deployment's replicas (see
    # deploy_application); allow that plus its own bookkeeping
    ok = ray_tpu.get(
        controller.deploy_application.remote(name, specs),
        timeout=60.0 + max([60.0] + [
            sp["config"].health_check_timeout_s for sp in specs]))
    if not ok:
        raise RuntimeError(f"application {name!r} failed to deploy")
    _reset_routers()
    return DeploymentHandle(ingress.deployment.name, name)


def get_app_handle(name: str = "default") -> DeploymentHandle:
    controller = get_or_create_controller()
    routes = ray_tpu.get(controller.get_http_routes.remote(), timeout=10.0)
    for prefix, (app, dep) in routes.items():
        if app == name:
            return DeploymentHandle(dep, app)
    st = ray_tpu.get(controller.status.remote(), timeout=10.0)
    for full, info in st.items():
        if info["app"] == name:
            return DeploymentHandle(full.split("#", 1)[1], name)
    raise ValueError(f"no application named {name!r}")


def get_deployment_handle(deployment_name: str,
                          app_name: str = "default") -> DeploymentHandle:
    return DeploymentHandle(deployment_name, app_name)


def status() -> dict:
    controller = get_or_create_controller()
    return ray_tpu.get(controller.status.remote(), timeout=10.0)


def detailed_status() -> dict:
    """Per-deployment status incl. replica details and `latency_ms`
    p50/p95/p99 from the merged replica-processing histogram."""
    controller = get_or_create_controller()
    return ray_tpu.get(controller.detailed_status.remote(), timeout=30.0)


def delete(name: str = "default") -> None:
    controller = get_or_create_controller()
    ray_tpu.get(controller.delete_application.remote(name), timeout=60.0)
    _reset_routers()


def shutdown() -> None:
    global _proxy
    with _lock:
        if _proxy is not None:
            _proxy[0].stop()
            _proxy = None
        for p in _extra_proxies:
            try:
                p.stop()
            except Exception:  # noqa: BLE001 — already down
                pass
        _extra_proxies.clear()
        _shared_routers.clear()
    try:
        controller = ray_tpu.get_actor("_serve_controller", timeout=0.2)
        ray_tpu.get(controller.shutdown.remote(), timeout=30.0)
        ray_tpu.kill(controller)
    except Exception:  # noqa: BLE001 - not running
        pass
    from ray_tpu.serve.grpc_ingress import _reset_grpc_proxy
    _reset_grpc_proxy()
    _reset_routers()


def start_http_proxy(host: str = "127.0.0.1", port: int = 8000,
                     router_config=None):
    """Start the node's HTTP ingress (reference: one HTTPProxy actor per
    node, proxy.py:706; here one aiohttp server in the driver process).
    router_config overrides the proxy's RouterConfig; ignored if a proxy
    is already running."""
    global _proxy
    from ray_tpu.serve.proxy import HTTPProxy
    with _lock:
        if _proxy is None:
            p = HTTPProxy(get_or_create_controller(), host, port,
                          router_config=router_config)
            p.start()
            _proxy = (p, port)
        return _proxy[0]


def start_http_proxies(count: int, host: str = "127.0.0.1",
                       port: int = 8000, router_config=None) -> list:
    """Multi-proxy ingress (ISSUE 17): `count` HTTPProxy instances behind
    the SAME fleet. The first takes `port` (or joins an already-running
    primary), the rest take `port+1, port+2, ...` — pass ``port=0`` for
    OS-assigned ports on all of them. Every proxy shares one router map:
    one controller long-poll per app for the whole ingress tier, one
    shared retry budget and circuit breaker per app, while each proxy
    answers its own `/-/stats` (tagged with its name/port). Put any
    TCP-level balancer — or a client-side port list — in front; the
    proxies are stateless beyond their shared routing cache. Returns the
    proxy list (index 0 = primary). Idempotent: already-running proxies
    are reused, only the missing tail is started."""
    global _proxy
    from ray_tpu.serve.proxy import HTTPProxy
    out = []
    with _lock:
        controller = get_or_create_controller()
        if _proxy is None:
            p = HTTPProxy(controller, host, port,
                          router_config=router_config, name="proxy-0",
                          shared_routers=_shared_routers)
            p.start()
            _proxy = (p, p.port)
        out.append(_proxy[0])
        out.extend(_extra_proxies)
        while len(out) < max(1, int(count)):
            i = len(out)
            p = HTTPProxy(controller, host,
                          0 if port == 0 else port + i,
                          router_config=router_config, name=f"proxy-{i}",
                          shared_routers=_shared_routers)
            p.start()
            _extra_proxies.append(p)
            out.append(p)
    return out
