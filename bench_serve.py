"""Serving benchmark: p50 TTFT + req/s, continuous batching over HTTP.

North-star metric harness (BASELINE.json: "Ray Serve p50 TTFT + req/s,
Llama-3-8B continuous batching"; reference harness:
release/serve_tests/workloads/ + release/llm_tests/serve/). Drives the FULL
stack: HTTP proxy → router → replica actor → continuous-batching engine on
the chip.

The driver process must not initialize the TPU backend (one process per
chip): the engine replica runs in a TPU worker when a TPU resource exists,
else in-driver on CPU (test mode).

Prints ONE JSON line:
  {"metric": "serve_p50_ttft_ms", "value": ..., "unit": "ms",
   "extra": {"req_per_s": ..., "p90_ttft_ms": ..., "tokens_per_s": ...}}
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import statistics
import time
import urllib.request


def _post(url: str, payload: dict, timeout: float = 600.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _post_stream(url: str, payload: dict, timeout: float = 600.0) -> dict:
    """SSE request; returns CLIENT-observed timings: ttft_s is the wall
    time to the first data: byte on this socket (the north-star metric —
    engine-side ttft excludes proxy/router/transport), plus the final
    chunk's usage/engine accounting."""
    req = urllib.request.Request(
        url, data=json.dumps({**payload, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.monotonic()
    ttft = None
    last = {}
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            if ttft is None:
                ttft = time.monotonic() - t0
            body = line[5:].strip()
            if body == "[DONE]":
                break
            try:
                chunk = json.loads(body)
            except ValueError:
                continue
            if chunk.get("usage") is not None:
                last = chunk
    return {"client_ttft_s": ttft, "client_latency_s": time.monotonic() - t0,
            "usage": last.get("usage") or {},
            "engine": last.get("ray_tpu") or {}}


def _post_stream_resume(url: str, payload: dict, rid: str,
                        timeout: float = 600.0) -> dict:
    """SSE request that understands mid-stream failover: accumulates the
    concatenated choice text across proxy-spliced legs, counts
    `event: resumed` control frames (whose data payload is NOT a chunk),
    and returns client-observed wall timings."""
    req = urllib.request.Request(
        url, data=json.dumps({**payload, "stream": True}).encode(),
        headers={"Content-Type": "application/json", "X-Request-Id": rid})
    t0 = time.monotonic()
    ttft = None
    resumes = 0
    pending_event = None
    texts = []
    resumed_at = []
    last = {}
    with urllib.request.urlopen(req, timeout=timeout) as r:
        for raw in r:
            line = raw.decode("utf-8", "replace").strip()
            if line.startswith("event:"):
                pending_event = line[6:].strip()
                if pending_event == "resumed":
                    resumes += 1
                continue
            if not line.startswith("data:"):
                continue
            if pending_event == "resumed":
                pending_event = None     # control frame, not a text chunk
                try:
                    # journal length at the fault: how many tokens the
                    # proxy had already written to this client when the
                    # replica died (0 => plain fresh re-dispatch)
                    resumed_at.append(json.loads(
                        line[5:].strip()).get("resume_tokens", 0))
                except ValueError:
                    pass
                continue
            pending_event = None
            body = line[5:].strip()
            if body == "[DONE]":
                break
            if ttft is None:
                ttft = time.monotonic() - t0
            try:
                chunk = json.loads(body)
            except ValueError:
                continue
            for c in chunk.get("choices") or []:
                texts.append(c.get("text") or "")
            if chunk.get("usage") is not None:
                last = chunk
    return {"text": "".join(texts), "resumes": resumes,
            "resumed_at": resumed_at,
            "client_ttft_s": ttft,
            "client_latency_s": time.monotonic() - t0,
            "usage": last.get("usage") or {},
            "engine": last.get("ray_tpu") or {}}


def _open_loop_dispatch(fn, rng, rate, *, count=None, duration_s=None,
                        max_workers=64, at=None, timeout=300.0):
    """Poisson-arrival OPEN-LOOP generator (ISSUE 17): submits ``fn(i)``
    at seeded exponential inter-arrival gaps and never gates an arrival
    on a completion — a slow fleet faces a growing backlog instead of a
    politely backing-off client, which is what makes p99 honest. Stops
    after `count` arrivals and/or `duration_s` seconds (whichever first;
    pass either). ``at=(delay_s, callback)`` fires callback once,
    mid-window, from the dispatcher thread — the scale-up/scale-down
    schedule hook. Joins every dispatched request before returning;
    returns the number dispatched. Determinism: the arrival SEQUENCE
    (gaps, order) is fully seeded by `rng`; only wall-clock placement
    varies with machine speed."""
    fired = False
    t0 = time.monotonic()
    i = 0
    with concurrent.futures.ThreadPoolExecutor(max_workers) as pool:
        futs = []
        while count is None or i < count:
            gap = rng.expovariate(rate)
            elapsed = time.monotonic() - t0
            if at is not None and not fired and elapsed >= at[0]:
                at[1]()
                fired = True
            if duration_s is not None and elapsed + gap > duration_s:
                break
            time.sleep(gap)
            futs.append(pool.submit(fn, i))
            i += 1
        if at is not None and not fired:
            rem = at[0] - (time.monotonic() - t0)
            if rem > 0:
                time.sleep(rem)
            at[1]()
        for f in futs:
            f.result(timeout=timeout)
    return i


def _chaos_scenario(name, events, duration_s, min_rate, *, seed,
                    request_timeout_s, grace_s):
    """One chaos scenario: fresh 3-node cluster (controller pinned to
    node0), a 2-replica echo app, sustained proxy traffic while a seeded
    FaultSchedule fires, then hard SLO asserts. Returns the result row
    merged into SERVE_BENCH.json's extra.chaos_suite."""
    import threading
    import urllib.error

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core.cluster import Cluster
    from ray_tpu.core.config import get_config
    from ray_tpu.util.chaos import FaultSchedule

    try:
        serve.shutdown()
        ray_tpu.shutdown()
    except Exception:  # noqa: BLE001 — nothing was up
        pass
    # the in-process CP reads the live Config singleton: tighten node-death
    # detection BEFORE the cluster starts
    cfg = get_config()
    cfg.health_check_period_s = 0.2
    cfg.health_check_failure_threshold = 3

    cluster = Cluster()
    cluster.add_node(num_cpus=1)  # node0: controller home, never a victim
    ray_tpu.init(address=cluster.address, _system_config={
        "health_check_period_s": 0.2,
        "health_check_failure_threshold": 3,
    })
    try:
        # pin the controller to node0 by creating it while node0 is the
        # only node, THEN add the replica-bearing nodes
        from ray_tpu.serve.controller import get_or_create_controller
        ctl = get_or_create_controller()
        ray_tpu.get(ctl.status.remote(), timeout=60)
        cluster.add_node(num_cpus=3)
        cluster.add_node(num_cpus=3)

        @serve.deployment(num_replicas=2, health_check_period_s=0.2,
                          health_check_failure_threshold=3,
                          request_timeout_s=request_timeout_s)
        def chaos_echo(payload):
            time.sleep(0.02)
            return {"ok": True}

        serve.run(chaos_echo.bind(), name=f"chaos-{name}",
                  route_prefix="/chaos")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}"

        # warm up until the app actually serves; the measured window must
        # not charge cold-start failures against the fault's SLO
        warm_deadline = time.monotonic() + 60.0
        while True:
            try:
                if urllib.request.urlopen(
                        urllib.request.Request(f"{base}/chaos", data=b"{}"),
                        timeout=request_timeout_s).status == 200:
                    break
            except Exception:  # noqa: BLE001 — still starting
                if time.monotonic() > warm_deadline:
                    raise
                time.sleep(0.2)

        results = []  # (ok, elapsed_s, detail)
        results_lock = threading.Lock()
        stop_traffic = threading.Event()
        t_start = time.monotonic()

        def one_request():
            t0 = time.monotonic()
            try:
                resp = urllib.request.urlopen(
                    urllib.request.Request(f"{base}/chaos", data=b"{}"),
                    timeout=request_timeout_s + grace_s)
                ok = resp.status == 200 and \
                    json.loads(resp.read())["ok"] is True
                detail = f"http {resp.status}"
            except urllib.error.HTTPError as e:
                ok, detail = False, f"http {e.code}: {e.read()[:200]!r}"
            except Exception as e:  # noqa: BLE001 — failure is data here
                ok, detail = False, repr(e)[:200]
            with results_lock:
                results.append((ok, time.monotonic() - t0,
                                f"@{t0 - t_start:.1f}s {detail}"))

        def traffic():
            while not stop_traffic.is_set():
                one_request()
                time.sleep(0.02)

        sched = FaultSchedule(cluster, events, seed=seed)
        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
            futs = [pool.submit(traffic) for _ in range(4)]
            sched.start()
            time.sleep(duration_s)
            stop_traffic.set()
            for f in futs:
                f.result(timeout=request_timeout_s + grace_s + 10)
        report = sched.stop()

        total = len(results)
        succ = sum(1 for ok, _, _ in results if ok)
        rate = (succ / total) if total else 0.0
        slow = [round(t, 2) for ok, t, _ in results
                if ok and t > request_timeout_s + grace_s]
        failures = [d for ok, _, d in results if not ok]
        row = {
            "scenario": name,
            "events": report,
            "requests": total,
            "succeeded": succ,
            "success_rate": round(rate, 4),
            "min_success_rate": min_rate,
            "slow_over_deadline": len(slow),
        }
        if len(report) < len(events) or not all(e["ok"] for e in report):
            print(json.dumps({"chaos_scenario": row}))
            raise SystemExit(
                f"chaos suite [{name}]: fault injection itself failed "
                f"({report!r}) — nothing was exercised, refusing to "
                f"report an SLO for it")
        if total < 100:
            print(json.dumps({"chaos_scenario": row}))
            raise SystemExit(
                f"chaos suite [{name}]: only {total} requests generated — "
                f"not enough traffic to make the SLO meaningful")
        if rate < min_rate:
            try:
                dbg = urllib.request.urlopen(
                    f"{base}/-/stats", timeout=10).read().decode()
            except Exception as e:  # noqa: BLE001
                dbg = repr(e)
            print(json.dumps({"chaos_scenario": row}))
            raise SystemExit(
                f"chaos suite [{name}]: success rate {rate:.4f} "
                f"({succ}/{total}) below the {min_rate} SLO; failures: "
                f"{failures[:10]}; server stats: {dbg}")
        if slow:
            print(json.dumps({"chaos_scenario": row}))
            raise SystemExit(
                f"chaos suite [{name}]: successful responses exceeded "
                f"deadline+grace: {slow}")

        # fault→symptom causal adjacency (ISSUE 19): every injected
        # fault must be on the journal as a chaos_fault ground-truth
        # event, followed within the adjacency window by the symptom
        # events that fault should cause. Polled: worker-side emitters
        # (controller, engines) batch-flush on events_flush_interval_s.
        symptom_kinds = {
            "worker_kill": ("replica_death", "replica_ejected",
                            "failover_resume"),
            "replica_kill": ("replica_death", "replica_ejected",
                             "failover_resume"),
            "node_kill": ("node_dead", "replica_death",
                          "replica_ejected", "failover_resume"),
            "node_drain": ("node_drain", "node_dead"),
            "cp_restart": ("cp_restart",),
            "replica_scale": ("replica_scale",),
        }
        adjacency_window_s = 10.0
        from ray_tpu.util import state as _state
        journal: list = []
        pairs: list = []
        missing = ["journal not polled yet"]
        poll_deadline = time.monotonic() + 15.0
        while missing and time.monotonic() < poll_deadline:
            try:
                journal = _state.list_events(limit=500)
            except Exception:  # noqa: BLE001 — CP mid-restart
                journal = []
            faults = [e for e in journal if e.get("kind") == "chaos_fault"]
            missing, pairs = [], []
            for _, fkind, _kw in events:
                fev = next(
                    (e for e in faults
                     if (e.get("attrs") or {}).get("kind") == fkind), None)
                if fev is None:
                    missing.append(f"{fkind}: no chaos_fault event")
                    continue
                want = symptom_kinds.get(fkind)
                if want is None:
                    continue
                fts = float(fev.get("ts") or 0.0)
                syms = [e for e in journal
                        if e.get("kind") in want
                        and fts <= float(e.get("ts") or 0.0)
                        <= fts + adjacency_window_s]
                if not syms:
                    missing.append(
                        f"{fkind}: none of {want} within "
                        f"{adjacency_window_s}s of the fault event")
                    continue
                pairs.append({
                    "fault": fkind, "fault_ts": fts,
                    "symptoms": sorted({s["kind"] for s in syms}),
                    "first_symptom_lag_s": round(
                        min(float(s.get("ts") or 0.0) - fts
                            for s in syms), 3)})
            if missing:
                time.sleep(0.5)
        row["fault_symptom_pairs"] = pairs
        # the postmortem surface must tell the same story in one call
        postmortem = _state.events_postmortem(
            window_s=duration_s + 60.0)
        row["postmortem_items"] = len(postmortem.get("items") or [])
        if missing:
            print(json.dumps({"chaos_scenario": row}))
            raise SystemExit(
                f"chaos suite [{name}]: fault→symptom causal adjacency "
                f"FAILED: {missing}; journal held {len(journal)} "
                f"event(s): {[e.get('kind') for e in journal][:40]}")
        try:
            stats = json.loads(urllib.request.urlopen(
                f"{base}/-/stats", timeout=10).read())
            row["degraded_at_end"] = bool(stats.get("degraded"))
        except Exception:  # noqa: BLE001 — informational only
            row["degraded_at_end"] = None
        return row
    finally:
        for teardown in (serve.shutdown, ray_tpu.shutdown, cluster.shutdown):
            try:
                teardown()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def _run_chaos_suite(args):
    """--chaos-suite: the deterministic multi-fault serve suite. Four
    seeded FaultSchedule scenarios — worker kill, node kill, graceful node
    drain, CP restart — each driving sustained HTTP traffic through a
    fresh multi-node cluster with hard per-scenario SLO asserts:

      worker_kill / node_kill   >= 99% success (retries + ejection absorb)
      node_drain                100% success — drain drops ZERO in-flight
      cp_restart                100% success — the data plane never
                                touches the CP on the hot path

    plus, for every scenario, no successful response past deadline+grace.
    The result merges into --out under extra.chaos_suite."""
    import os

    request_timeout_s = 15.0
    grace_s = 3.0
    scenarios = [
        ("worker_kill",
         [(2.0, "worker_kill", {"spare_actors": False})], 12.0, 0.99),
        ("node_kill", [(2.0, "node_kill", {})], 16.0, 0.99),
        ("node_drain", [(2.0, "node_drain", {"wait": True})], 16.0, 1.0),
        ("cp_restart", [(2.0, "cp_restart", {"down_s": 1.5})], 10.0, 1.0),
    ]

    rows = []
    for name, events, duration_s, min_rate in scenarios:
        print(f"# chaos scenario: {name}", flush=True)
        rows.append(_chaos_scenario(
            name, events, duration_s, min_rate, seed=args.chaos_seed,
            request_timeout_s=request_timeout_s, grace_s=grace_s))

    chaos_suite = {
        "seed": args.chaos_seed,
        "request_timeout_s": request_timeout_s,
        "grace_s": grace_s,
        "scenarios": rows,
    }
    # merge into --out WITHOUT clobbering earlier headline rows
    merged = {"metric": "serve_chaos_suite", "value": len(rows),
              "unit": "scenarios", "extra": {"chaos_suite": chaos_suite}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["chaos_suite"] = chaos_suite
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)
    print(json.dumps({"chaos_suite": chaos_suite}))


def _run_fleet(args):
    """--fleet: sustained-load fleet harness for prefix-affinity routing
    (ISSUE 10). A multi-tenant shared-prefix workload (every tenant's
    requests carry that tenant's long system prefix + a unique suffix)
    over >=4 cpu-tiny replicas, A/B'd affinity-on vs pow-2-only:

      - fleet prefix-cache hit rate (summed engine counters over offered
        prompt tokens) must clear --fleet-min-hit-rate with affinity on
        and beat the pow-2 arm by a real margin (pow-2 sprays each tenant
        across every replica, so each tenant's prefix is recomputed
        per-replica instead of once);
      - p50 TTFT must improve (hard) and is flagged outside/within noise;
      - greedy completions must be token-identical across arms (HARD:
        affinity is a placement hint, never a semantics knob);
      - chaos: killing the preferred holder of a hot prefix mid-load must
        keep >=99% success (retries + ejection absorb, replacement starts
        cold and re-converges).

    Merges into --out under extra.fleet."""
    import dataclasses as _dc
    import os
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve import affinity
    from ray_tpu.serve.config import RouterConfig
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    from ray_tpu.serve.router import Router

    n_replicas = max(4, args.fleet_replicas)
    tenants = args.fleet_tenants
    requests = args.fleet_requests
    concurrency = args.fleet_concurrency

    # byte tokenizer: 1 token per char. 480-char tenant prefix = 15 full
    # 32-token pages shared per tenant; the unique suffix never fills a
    # page, so steady-state hit rate ~ prefix/(prefix+suffix) ~ 0.95
    prefixes = [
        (f"[tenant {t:02d} system] You answer tersely and cite sources. "
         * 12)[:480]
        for t in range(tenants)]

    def mk_prompt(t: int, i: int) -> str:
        return prefixes[t % tenants] + f" Q{i:05d}: summarize item {i}."

    llm_cfg = LLMConfig(
        model_id="llama-tiny", model_config=llama.llama_tiny(vocab_size=2048),
        num_replicas=n_replicas, max_batch_size=8, page_size=32,
        num_pages=256, max_prompt_len=576, max_seq_len=640, max_tokens=8,
        # the tier makes router prefetch hints live (meta kv_tier=true);
        # a small retention cap keeps chains spilling so hints have work
        kv_tier_enabled=True, prefix_cache_max_pages=64,
        # deliberately unmeetable TTFT SLO + sample-everything: every
        # measured request becomes a violation exemplar, so the fleet
        # report can hard-assert a complete ordered critical path
        # (ingress -> route -> queue -> prefill -> decode) came through
        slo_ttft_p99_ms=0.1, slo_sample_rate=1.0)

    bench_cpus = max(8, (os.cpu_count() or 1))

    def fleet_engines(ctl, app_name: str) -> list:
        st = ray_tpu.get(ctl.detailed_status.remote(), timeout=60)
        for full, d in st.items():
            if d.get("app") == app_name and d.get("engine"):
                return [e or {} for e in d["engine"]]
        return []

    def fleet_sum(engines: list, key: str) -> int:
        return sum(e.get(key) or 0 for e in engines)

    def fleet_arm(affinity_on: bool) -> dict:
        tag = "on" if affinity_on else "off"
        app_name = f"llm-fleet-{tag}"
        router_cfg = (RouterConfig() if affinity_on else
                      RouterConfig(affinity_enabled=False,
                                   prefetch_hints_enabled=False))
        ray_tpu.init(num_cpus=bench_cpus)
        ctl = get_or_create_controller()
        serve.run(build_openai_app(llm_cfg, route_prefix="/v1"),
                  name=app_name, route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0, router_config=router_cfg)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"

        # warm: compile the long bucket before anything is measured
        _post_stream(base, {"prompt": mk_prompt(0, 90000), "max_tokens": 4})

        # greedy fingerprint on dedicated probe tenants, BEFORE traffic
        # muddies cache history: the first call is a cold full prefill
        # (identical weights => identical across arms), the immediate
        # second call is a cache hit (affinity pins it to the holder).
        # hit==cold through the full HTTP->router->digest-reuse stack is
        # a HARD within-arm assert; the cold outputs are the cross-arm
        # fingerprint. (Probing tenants from the traffic mix instead
        # would compare KV with different chunk-split float histories
        # across arms — placement-dependent ULP noise, not a bug.)
        completions = []
        for t in range(tenants):
            pp = (f"[probe tenant {t:02d}] Answer briefly and cite. "
                  * 16)[:480] + " Q: summarize the policy."
            fps = []
            for _ in range(2):
                o = _post(base, {"prompt": pp, "max_tokens": 12,
                                 "temperature": 0.0})
                fps.append((o["choices"][0]["text"],
                            o["usage"]["completion_tokens"]))
            if fps[0] != fps[1]:
                raise SystemExit(
                    f"fleet [{tag}]: greedy output changed between cold "
                    f"prefill and cache-hit serve for the same prompt: "
                    f"{fps!r} — the digest-reuse/restore path is corrupting "
                    f"KV, not benchmarking it")
            completions.append(fps[0])

        # seed: give every traffic tenant one request so each prefix is
        # resident SOMEWHERE before the window
        for t in range(tenants):
            _post_stream(base, {"prompt": mk_prompt(t, 91000 + t),
                                "max_tokens": 4})
        # let the controller's summary tick + long-poll ship every seeded
        # tenant prefix before the window opens (affinity arm), so the
        # measurement sees steady-state placement rather than the
        # convergence transient; the pow-2 arm just gets a fixed settle
        if affinity_on:
            probe_router = Router(ctl, app_name)
            try:
                want = set()
                deadline = time.monotonic() + 30.0
                while True:
                    meta = probe_router.affinity_meta("llm")
                    if meta and not want:
                        for t in range(tenants):
                            d = affinity.compute_prefix_digests(
                                mk_prompt(t, 91000 + t), meta, 64)
                            if d:
                                want.add(d[0])
                    with probe_router._lock:
                        rs = probe_router._sets.get("llm")
                        seen = (set().union(*rs._summaries.values())
                                if rs and rs._summaries else set())
                    if want and want <= seen:
                        break
                    if time.monotonic() > deadline:
                        print(f"# fleet [{tag}]: summaries converged for "
                              f"{len(want & seen)}/{len(want)} tenants "
                              f"before the window", flush=True)
                        break
                    time.sleep(0.2)
            finally:
                probe_router.stop()
        else:
            time.sleep(3.0)

        e0 = fleet_engines(ctl, app_name)
        ttfts, prompt_toks, failures = [], [0], []
        lock = threading.Lock()

        def one(i: int):
            try:
                # short generations keep TTFT prefill-bound (the thing
                # affinity actually moves) instead of decode-queue-bound
                out = _post_stream(base, {"prompt": mk_prompt(i, i),
                                          "max_tokens":
                                          min(8, args.max_tokens)})
                with lock:
                    if out["client_ttft_s"] is not None:
                        ttfts.append(out["client_ttft_s"])
                    prompt_toks[0] += out["usage"].get("prompt_tokens", 0)
            except Exception as e:  # noqa: BLE001 — failure is data here
                with lock:
                    failures.append(repr(e)[:200])

        # Poisson-arrival open loop (ISSUE 17): both arms replay the SAME
        # seeded arrival sequence, so the A/B stays fair while arrivals
        # stop waiting politely for completions (a closed loop's p99
        # hides queueing behind client back-off; the open loop's is the
        # one users feel)
        import random as _random
        t0 = time.monotonic()
        _open_loop_dispatch(one, _random.Random(args.open_loop_seed),
                            args.open_loop_rate, count=requests,
                            max_workers=max(concurrency, 64))
        wall = time.monotonic() - t0
        e1 = fleet_engines(ctl, app_name)

        hit_toks = (fleet_sum(e1, "prefix_hit_tokens")
                    - fleet_sum(e0, "prefix_hit_tokens"))
        hit_rate = hit_toks / prompt_toks[0] if prompt_toks[0] else 0.0
        p50 = statistics.median(ttfts) * 1e3 if ttfts else float("nan")
        p99 = (statistics.quantiles(ttfts, n=100)[-1] * 1e3
               if len(ttfts) >= 20 else p50)

        row = {
            "label": f"fleet_affinity_{tag}",
            "replicas": n_replicas, "tenants": tenants,
            "requests": requests, "concurrency": concurrency,
            "failures": len(failures),
            "req_per_s": round(requests / wall, 3),
            "p50_ttft_ms": round(p50, 2),
            "p99_ttft_ms": round(p99, 2),
            "fleet_hit_rate": round(hit_rate, 4),
            "prefix_hit_tokens": hit_toks,
            "prompt_tokens_total": prompt_toks[0],
            # concentration fingerprint: affinity pins tenants, pow-2
            # sprays them — visible as per-replica prefill spread
            "per_replica_prefills": [
                (b.get("prefills") or 0) - (a.get("prefills") or 0)
                for a, b in zip(e0, e1)],
            "tier_prefetch_hints": fleet_sum(e1, "tier_prefetch_hints"),
            "completions": completions,
        }
        if failures:
            print(json.dumps({"fleet_arm": row}))
            raise SystemExit(f"fleet [{tag}]: {len(failures)} measured "
                             f"requests failed: {failures[:5]}")

        if affinity_on:
            # pull the tail-latency breakdown BEFORE chaos muddies the
            # window with kill-induced retries
            row["slo_attribution"] = _fleet_slo_attribution()
            row["chaos"] = _fleet_chaos(ctl, app_name, base, mk_prompt,
                                        affinity, Router, args)
        serve.shutdown()
        ray_tpu.shutdown()
        return row

    def _fleet_slo_attribution() -> dict:
        """Per-stage tail breakdown + one full violation exemplar from
        the CP store. The unmeetable TTFT SLO above made every measured
        request a violation, so an empty store or an incomplete critical
        path is a HARD failure — stamping that silently drops stages
        would make the attribution table a lie."""
        from ray_tpu.observability import attribution
        from ray_tpu.util import state

        deadline = time.monotonic() + 20.0
        exemplars = []
        while time.monotonic() < deadline:
            exemplars = state.list_slo_exemplars(limit=10, kind="violation")
            if exemplars:
                break
            time.sleep(0.5)
        if not exemplars:
            raise SystemExit(
                "fleet slo: no violation exemplars reached the CP store "
                "under an unmeetable TTFT SLO — timeline stamping or the "
                "exemplar shipper is inert")
        rec = state.get_slo_exemplar(exemplars[0]["request_id"])
        if rec is None:
            raise SystemExit("fleet slo: exemplar listed but its full "
                             "record is missing from the store")
        names = [s.get("stage") for s in rec.get("stages") or []]
        for want in ("ingress", "route", "queue", "prefill", "decode"):
            if want not in names:
                raise SystemExit(
                    f"fleet slo: exemplar {rec.get('request_id')} is "
                    f"missing stage '{want}' (has {names}) — the critical "
                    f"path is incomplete")
        ranks = [attribution._STAGE_INDEX[n] for n in names
                 if n in attribution._STAGE_INDEX]
        if ranks != sorted(ranks):
            raise SystemExit(f"fleet slo: exemplar stages out of "
                             f"canonical order: {names}")
        report = state.slo_report()
        return {
            "records": report.get("count"),
            "violations": report.get("violations"),
            "stage_ms": report.get("stage_ms"),
            "dominant_stage": report.get("dominant_stage"),
            "replica_skew": report.get("replica_skew"),
            "exemplar_request_id": rec.get("request_id"),
            "exemplar_stages": names,
            "exemplar_ttft_ms": rec.get("ttft_ms"),
        }

    def _fleet_chaos(ctl, app_name, base, mk_prompt, affinity, Router,
                     args):
        """Kill the preferred holder of tenant 0's prefix under sustained
        load; retries + ejection must hold >=99% success while the
        replacement comes up cold."""
        router = Router(ctl, app_name)
        try:
            deadline = time.monotonic() + 30.0
            digs = None
            while True:
                meta = router.affinity_meta("llm")
                if meta and digs is None:
                    digs = affinity.compute_prefix_digests(
                        mk_prompt(0, 42), meta, 64)
                with router._lock:
                    rs = router._sets.get("llm")
                    ready = bool(
                        rs and digs
                        and any(digs[0] in s for s in rs._summaries.values()))
                if ready:
                    break
                if time.monotonic() > deadline:
                    raise SystemExit(
                        "fleet chaos: affinity summaries never converged — "
                        "nothing to kill, refusing to report an SLO")
                time.sleep(0.2)
            victim, matched = rs.choose_info("", digs)
            if matched < 1:
                raise SystemExit("fleet chaos: router matched no holder "
                                 "for a seeded prefix")
        finally:
            router.stop()

        results = []
        lock = threading.Lock()

        def one(i: int):
            try:
                out = _post_stream(
                    base, {"prompt": mk_prompt(i, 80000 + i),
                           "max_tokens": 4}, timeout=60.0)
                ok = out["client_ttft_s"] is not None
                detail = "ok"
            except Exception as e:  # noqa: BLE001 — failure is data here
                ok, detail = False, repr(e)[:200]
            with lock:
                results.append((ok, detail))

        n = args.fleet_chaos_requests
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futs = [pool.submit(one, i) for i in range(n // 4)]
            import ray_tpu as _rt
            _rt.kill(victim)          # the preferred holder dies mid-load
            futs += [pool.submit(one, i) for i in range(n // 4, n)]
            for f in futs:
                f.result(timeout=120)
        succ = sum(1 for ok, _ in results if ok)
        rate = succ / len(results)
        chaos = {
            "requests": len(results), "succeeded": succ,
            "success_rate": round(rate, 4), "min_success_rate": 0.99,
            "killed_matched_pages": matched,
        }
        if rate < 0.99:
            fails = [d for ok, d in results if not ok]
            print(json.dumps({"fleet_chaos": chaos}))
            raise SystemExit(
                f"fleet chaos: success rate {rate:.4f} after killing the "
                f"preferred holder (SLO 0.99); failures: {fails[:5]}")
        return chaos

    off_row = fleet_arm(False)
    on_row = fleet_arm(True)

    comp_off = off_row.pop("completions")
    comp_on = on_row.pop("completions")
    identical = comp_off == comp_on
    improved_ms = round(off_row["p50_ttft_ms"] - on_row["p50_ttft_ms"], 2)
    tol_ms = round(max(0.15 * off_row["p50_ttft_ms"], 3.0), 2)
    fleet = {
        "label": "fleet_affinity_ab",
        "model": llm_cfg.model_id, "env": "cpu-tiny",
        "replicas": n_replicas, "tenants": tenants,
        "greedy_identical": identical,
        "affinity_on": on_row, "affinity_off": off_row,
        "fleet_hit_rate_on": on_row["fleet_hit_rate"],
        "fleet_hit_rate_off": off_row["fleet_hit_rate"],
        "min_hit_rate": args.fleet_min_hit_rate,
        "p50_ttft_improvement_ms": improved_ms,
        "noise_tolerance_ms": tol_ms,
        "improved_outside_noise": improved_ms > tol_ms,
        "chaos": on_row.pop("chaos", None),
        # per-stage p99 attribution + per-replica skew + the asserted
        # violation exemplar (ISSUE 12): where the fleet's tail went
        "slo_attribution": on_row.pop("slo_attribution", None),
    }
    print(json.dumps({"fleet": fleet}))
    if not identical:
        diffs = [(i, a, b) for i, (a, b) in
                 enumerate(zip(comp_off, comp_on)) if a != b]
        raise SystemExit(
            f"fleet A/B: affinity routing changed greedy output — "
            f"placement must never alter tokens, not benchmarking it; "
            f"diverging probes (tenant, pow2, affinity): {diffs[:4]!r}")
    if fleet["fleet_hit_rate_on"] < args.fleet_min_hit_rate:
        raise SystemExit(
            f"fleet A/B: affinity-on fleet hit rate "
            f"{fleet['fleet_hit_rate_on']} below the "
            f"{args.fleet_min_hit_rate} SLO")
    if (fleet["fleet_hit_rate_on"] - fleet["fleet_hit_rate_off"]) < 0.05:
        raise SystemExit(
            f"fleet A/B: affinity-on hit rate "
            f"{fleet['fleet_hit_rate_on']} is not materially above pow-2 "
            f"({fleet['fleet_hit_rate_off']}) — cache-aware placement is "
            f"inert")
    if improved_ms <= tol_ms:
        raise SystemExit(
            f"fleet A/B: affinity p50 TTFT gain {improved_ms}ms is not "
            f"outside noise ({tol_ms}ms tolerance; "
            f"{on_row['p50_ttft_ms']}ms on vs {off_row['p50_ttft_ms']}ms "
            f"pow-2)")

    # merge into --out WITHOUT clobbering earlier headline rows
    merged = {"metric": "serve_fleet_affinity", "value":
              fleet["fleet_hit_rate_on"], "unit": "hit_rate",
              "extra": {"fleet": fleet}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["fleet"] = fleet
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)


def _run_fleet_disagg(args):
    """--fleet disagg arm (ISSUE 16): fleet prefill/decode disaggregation
    on the streamed KV plane, A/B'd against a colocated pool:

      - long FRESH prompts (over ``disagg_prompt_threshold``, no resident
        prefix) must route to the prefill pool (proxy ``disagg_prefills``
        advances; decode engines report ``handoff_bytes_wire > 0`` and
        ``handoff_overlap_ms > 0`` — the restore streamed WHILE other
        requests decoded, which is the whole point);
      - short prompts must stay colocated (the threshold is a routing
        decision, not a default);
      - greedy completions on the lossless wire must be token-identical
        to the colocated arm (HARD: placement must never alter tokens);
      - p50 TTFT for long prompts under a sustained short-prompt decode
        background is measured in both arms and reported with a
        within-noise verdict; the HARD gate is a catastrophic-regression
        bound (disagg p50 <= 2.5x colocated + 50ms). On cpu-tiny a
        strict no-worse gate is not assertable: prefill compute is
        nearly free there, so the handoff's fixed costs (prefill-leg
        RPC, codec encode, CP registration, streamed restore) dominate
        TTFT — the regime disaggregation exists for is chip-bound
        prefill, where the prompt pass dwarfs those fixed costs. The
        bound still catches a serialized/broken handoff path;
      - the int8-wire arm REPORTS its measured greedy divergence against
        the lossless reference plus the per-deployment policy decision
        (``int8_wire_allowed``) — int8 never silently defaults on.

    Merges into --out under extra.disagg."""
    import dataclasses as _dc
    import os
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import (LLMConfig, build_disagg_fleet_app,
                                   build_openai_app)
    from ray_tpu.serve.llm.disagg import (int8_wire_allowed,
                                          int8_wire_divergence)

    bench_cpus = max(8, (os.cpu_count() or 1))
    requests = max(16, min(args.fleet_requests // 4, 48))
    concurrency = 4          # measured long-prompt streams
    background_threads = 4   # sustained short-prompt decode load
    probes = 6

    # byte tokenizer: 1 token/char. Long prompts are ~176 tokens (11 full
    # 16-token pages) against a 64-token threshold; every prompt carries a
    # unique id prefix so nothing is resident anywhere (a resident prefix
    # discounts the estimate and keeps the request colocated — correct
    # behavior, but it would starve this harness of handoffs to measure).
    filler = "the quick brown fox jumps over the lazy dog. "

    def long_prompt(i: int) -> str:
        return (f"req{i:05d} " + filler * 9)[:368]

    def probe_prompt(t: int) -> str:
        return (f"probe{t:02d} " + filler * 9)[:368]

    def short_prompt(i: int) -> str:
        return f"s{i:04d} hello"

    base_cfg = LLMConfig(
        model_id="llama-tiny", model_config=llama.llama_tiny(vocab_size=512),
        num_replicas=2, max_batch_size=4, page_size=16,
        num_pages=192, max_prompt_len=384, max_seq_len=416, max_tokens=8,
        prefix_cache_enabled=True, kv_tier_enabled=True)

    def _proxy_stats(url: str) -> dict:
        with urllib.request.urlopen(url, timeout=30) as r:
            return json.loads(r.read())

    def role_engines(ctl, app_name: str) -> dict:
        st = ray_tpu.get(ctl.detailed_status.remote(), timeout=60)
        out = {}
        for _full, d in st.items():
            if d.get("app") == app_name and d.get("engine"):
                out.setdefault(d.get("role") or "decode", []).extend(
                    e or {} for e in d["engine"])
        return out

    def esum(engines: list, key: str) -> float:
        return sum(e.get(key) or 0 for e in engines)

    def arm(tag: str, build, disagg_expected: bool) -> dict:
        app_name = f"llm-disagg-{tag}"
        ray_tpu.init(num_cpus=bench_cpus)
        ctl = get_or_create_controller()
        serve.run(build(), name=app_name, route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        stats_url = f"http://127.0.0.1:{proxy.port}/-/stats"

        # warm: EVERY replica of every role must compile its buckets
        # (prefill pass / restore + tail-prefill) before anything is
        # measured — the router spreads load, so one warm request only
        # compiles one replica and the window would eat XLA compiles.
        # For the disagg arms this loop doubles as the wait for the
        # decode replicas' prefix_summary meta (threshold + prefill
        # deployment) to reach the router: until it does, long prompts
        # stay colocated and the prefill pool shows no prefills.
        def warmed() -> bool:
            roles = role_engines(ctl, app_name)
            dec = roles.get("decode", [])
            ok = bool(dec) and all(
                (e.get("prefills") or 0) + (e.get("disagg_prefills") or 0)
                >= 1 for e in dec)
            if disagg_expected:
                pre = roles.get("prefill", [])
                ok = ok and bool(pre) and all(
                    (e.get("prefills") or 0) >= 1 for e in pre)
                ok = ok and (_proxy_stats(stats_url)
                             .get("disagg_prefills", 0) >= 1)
            return ok

        deadline = time.monotonic() + 240.0
        warm_i = 91000
        _post(base, {"prompt": long_prompt(90000), "max_tokens": 4,
                     "temperature": 0.0})
        while not warmed():
            if time.monotonic() > deadline:
                raise SystemExit(
                    f"disagg [{tag}]: replicas never all warmed within "
                    f"240s" + (" — the router's disagg plan may be inert"
                               if disagg_expected else ""))
            _post(base, {"prompt": long_prompt(warm_i), "max_tokens": 4,
                         "temperature": 0.0})
            warm_i += 1
            time.sleep(0.1)

        if disagg_expected:
            # short prompts must stay colocated
            before = _proxy_stats(stats_url).get("disagg_prefills", 0)
            for i in range(4):
                _post(base, {"prompt": short_prompt(i), "max_tokens": 4,
                             "temperature": 0.0})
            if _proxy_stats(stats_url).get("disagg_prefills", 0) != before:
                raise SystemExit(
                    f"disagg [{tag}]: a short prompt (below "
                    f"disagg_prompt_threshold) was dispatched to the "
                    f"prefill pool — the threshold is not gating")

        # greedy fingerprints (cross-arm identity / divergence probes)
        pre_probe = _proxy_stats(stats_url).get("disagg_prefills", 0)
        completions = []
        for t in range(probes):
            o = _post(base, {"prompt": probe_prompt(t), "max_tokens": 8,
                             "temperature": 0.0})
            completions.append(o["choices"][0]["text"])
        if disagg_expected:
            took = (_proxy_stats(stats_url).get("disagg_prefills", 0)
                    - pre_probe)
            if took < probes:
                raise SystemExit(
                    f"disagg [{tag}]: only {took}/{probes} greedy probes "
                    f"went through the prefill pool — the fingerprint "
                    f"would compare colocated output against itself")

        # measured window: fresh long prompts racing a sustained
        # short-prompt decode background (resident prefixes, so the
        # background is pure decode slot pressure in BOTH arms — in the
        # colocated arm each measured prefill chunks through it, in the
        # disagg arm the decode replicas only restore + tail-prefill)
        ttfts, failures = [], []
        lock = threading.Lock()
        stop_bg = threading.Event()

        def background():
            i = 0
            while not stop_bg.is_set():
                try:
                    _post(base, {"prompt": short_prompt(i % 8),
                                 "max_tokens": 32, "temperature": 0.0},
                          timeout=60)
                except Exception:  # noqa: BLE001 — load, not data
                    if stop_bg.is_set():
                        return
                i += 1

        bg = [threading.Thread(target=background, daemon=True)
              for _ in range(background_threads)]
        for t in bg:
            t.start()

        def one(i: int):
            try:
                out = _post_stream(base, {"prompt": long_prompt(i),
                                          "max_tokens": 8})
                with lock:
                    if out["client_ttft_s"] is not None:
                        ttfts.append(out["client_ttft_s"])
            except Exception as e:  # noqa: BLE001 — failure is data here
                with lock:
                    failures.append(repr(e)[:200])

        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
            list(pool.map(one, range(requests)))
        wall = time.monotonic() - t0
        stop_bg.set()
        for t in bg:
            t.join(timeout=60)

        ps = _proxy_stats(stats_url)
        roles = role_engines(ctl, app_name)
        decode_eng = roles.get("decode", [])
        prefill_eng = roles.get("prefill", [])
        p50 = statistics.median(ttfts) * 1e3 if ttfts else float("nan")
        row = {
            "label": f"fleet_disagg_{tag}",
            "requests": requests, "concurrency": concurrency,
            "failures": len(failures),
            "req_per_s": round(requests / wall, 3),
            "p50_ttft_ms": round(p50, 2),
            "proxy_disagg_prefills": ps.get("disagg_prefills", 0),
            "proxy_disagg_fallbacks": ps.get("disagg_fallbacks", 0),
            "proxy_disagg_partial_restores":
                ps.get("disagg_partial_restores", 0),
            "decode_disagg_prefills": int(esum(decode_eng,
                                               "disagg_prefills")),
            "decode_handoff_bytes_wire": int(esum(decode_eng,
                                                  "handoff_bytes_wire")),
            "decode_handoff_overlap_ms": round(
                esum(decode_eng, "handoff_overlap_ms"), 2),
            "prefill_prefills": int(esum(prefill_eng, "prefills")),
            "prefill_handoff_bytes_wire": int(esum(prefill_eng,
                                                   "handoff_bytes_wire")),
            "completions": completions,
        }
        if failures:
            print(json.dumps({"disagg_arm": row}))
            raise SystemExit(f"disagg [{tag}]: {len(failures)} measured "
                             f"requests failed: {failures[:5]}")
        if disagg_expected:
            if row["decode_disagg_prefills"] < 1 or \
                    row["decode_handoff_bytes_wire"] <= 0:
                raise SystemExit(
                    f"disagg [{tag}]: decode engines report no streamed "
                    f"handoffs ({row['decode_disagg_prefills']} prefills, "
                    f"{row['decode_handoff_bytes_wire']} wire bytes) — "
                    f"the restore path is not the one being measured")
            if row["decode_handoff_overlap_ms"] <= 0:
                raise SystemExit(
                    f"disagg [{tag}]: handoff_overlap_ms is 0 under "
                    f"{concurrency}-way load — restores are blocking the "
                    f"decode loop instead of streaming under it")
        serve.shutdown()
        ray_tpu.shutdown()
        return row

    coloc_cfg = base_cfg  # no disagg knobs: the router never plans handoffs
    fleet_cfg = _dc.replace(base_cfg, disagg_prompt_threshold=64)
    int8_cfg = _dc.replace(fleet_cfg, kv_tier_codec="int8")

    coloc = arm("colocated",
                lambda: build_openai_app(coloc_cfg, route_prefix="/v1"),
                False)
    lossless = arm("lossless",
                   lambda: build_disagg_fleet_app(
                       fleet_cfg, route_prefix="/v1",
                       num_prefill=4, num_decode=2),
                   True)
    int8 = arm("int8",
               lambda: build_disagg_fleet_app(
                   int8_cfg, route_prefix="/v1",
                   num_prefill=4, num_decode=2),
               True)

    comp_ref = coloc.pop("completions")
    comp_lossless = lossless.pop("completions")
    comp_int8 = int8.pop("completions")
    identical = comp_ref == comp_lossless
    # byte tokenizer: 1 token/char, so per-position text divergence IS
    # token divergence; the policy gate takes the worst probe
    divs = [int8_wire_divergence(list(a), list(b))
            for a, b in zip(comp_ref, comp_int8)]
    div_max = round(max(divs), 4) if divs else 0.0
    tol_ms = round(max(0.15 * coloc["p50_ttft_ms"], 3.0), 2)
    regression_ms = round(lossless["p50_ttft_ms"] - coloc["p50_ttft_ms"], 2)
    bound_ms = round(2.5 * coloc["p50_ttft_ms"] + 50.0, 2)
    disagg = {
        "label": "fleet_disagg_ab",
        "model": base_cfg.model_id, "env": "cpu-tiny",
        "prefill_replicas": 4, "decode_replicas": 2,
        "disagg_prompt_threshold": fleet_cfg.disagg_prompt_threshold,
        "colocated": coloc, "disagg_lossless": lossless,
        "disagg_int8": int8,
        "greedy_identical_lossless": identical,
        "p50_ttft_regression_ms": regression_ms,
        "noise_tolerance_ms": tol_ms,
        "ttft_within_noise_of_colocated": regression_ms <= tol_ms,
        "ttft_hard_bound_ms": bound_ms,
        "int8": {
            "measured_divergence_max": div_max,
            "measured_divergence_per_probe": [round(d, 4) for d in divs],
            "max_divergence_policy": int8_cfg.disagg_int8_max_divergence,
            "int8_wire_allowed": int8_wire_allowed(int8_cfg, div_max),
        },
    }
    print(json.dumps({"disagg": disagg}))
    if not identical:
        diffs = [(i, a, b) for i, (a, b) in
                 enumerate(zip(comp_ref, comp_lossless)) if a != b]
        raise SystemExit(
            f"disagg A/B: the lossless streamed handoff changed greedy "
            f"output — the wire codec is bit-exact and KV pages are "
            f"sampling-independent, so this is KV corruption; diverging "
            f"probes (idx, colocated, disagg): {diffs[:4]!r}")
    if lossless["p50_ttft_ms"] > bound_ms:
        raise SystemExit(
            f"disagg A/B: long-prompt p50 TTFT {lossless['p50_ttft_ms']}ms "
            f"blew the catastrophic-regression bound ({bound_ms}ms = "
            f"2.5x colocated {coloc['p50_ttft_ms']}ms + 50ms) — the "
            f"handoff path is serialized or broken, not just paying its "
            f"fixed cpu-tiny overhead")

    merged = {"metric": "serve_fleet_disagg", "value":
              lossless["p50_ttft_ms"], "unit": "ms",
              "extra": {"disagg": disagg}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["disagg"] = disagg
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)


def _run_tp_ab(args):
    """--tp-ab: tensor-parallel serving A/B (ISSUE 20).

    In-process TP=1 vs TP=2 engine pair on the deeper cpu-tiny model
    (heads/ffn/vocab all divide 2), full serving stack on — prefix
    cache, speculative decoding, kv-tier spill/restore (lossless). Each
    arm also brings up a COLD same-degree replica B that restores arm
    A's spilled shared prefix through the tier, so the TP=2 leg drives
    the per-shard blob wire end to end.

    HARD asserts: greedy completions identical across TP=1 A, TP=2 A,
    and TP=2 B-after-sharded-restore (the lossless-path bit-identity
    acceptance criterion); TP=2 must actually spill mode="shards"
    payloads and B must restore pages. Reports decode throughput and
    restore wall time per arm; merges into --out under extra.tp.

    Off-TPU the arm forces 2 virtual host CPU devices (the same
    XLA_FLAGS mechanism tests/conftest.py uses) so the sharded programs
    are genuinely partitioned.
    """
    import dataclasses as _dc
    import os

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2").strip()

    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig, LLMEngine

    if len(jax.devices()) < 2:
        raise SystemExit(
            f"--tp-ab needs 2 devices, have {len(jax.devices())} "
            f"(off-TPU it forces 2 virtual host devices — is XLA_FLAGS "
            f"overridden?)")

    tp_cfg = LLMConfig(
        model_id="llama-tiny-d256",
        model_config=llama.llama_tiny(
            vocab_size=2048, dim=256, n_layers=4, n_heads=8,
            n_kv_heads=4, ffn_dim=1024),
        max_batch_size=4, page_size=32, num_pages=128,
        max_prompt_len=704, max_seq_len=768, max_tokens=16,
        warmup_compile=True, prefix_cache_max_pages=2,
        kv_tier_enabled=True, spec_decode_enabled=True)
    shared = "shared context " * 40             # 600 tokens ~ 18 pages
    prompts = [shared + f"Q{i}: " for i in range(4)]

    def run_prompts(eng):
        comps, restores = [], []
        t0 = time.monotonic()
        toks = 0
        for p in prompts:
            out = eng.generate(p, max_tokens=16, temperature=0.0)
            if out["error"]:
                raise SystemExit(f"tp A/B request failed: {out['error']}")
            comps.append((out["text"], len(out["tokens"])))
            toks += len(out["tokens"])
            restores += [s["attrs"] for s in out.get("stages") or ()
                         if s["stage"] == "restore"]
        return comps, toks / (time.monotonic() - t0), restores

    def arm(tp: int) -> dict:
        cfg = _dc.replace(tp_cfg, tp_degree=tp)
        a = LLMEngine(cfg, rng_seed=0)
        a.start()
        b = None
        try:
            a_comps, a_tps, _ = run_prompts(a)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and \
                    a.engine_stats()["spilled_pages"] < 1:
                time.sleep(0.05)
            a_st = a.engine_stats()
            if a_st["spilled_pages"] < 1:
                raise SystemExit(f"tp A/B [tp={tp}]: replica A spilled "
                                 f"nothing — not benchmarking it")
            if tp > 1:
                # the acceptance criterion's wire shape: per-shard
                # payloads under the unchanged chain digests
                for rec in a._kv_tier._blobs.values():
                    for ek, _ev in rec["data"]["pages"]:
                        if ek.get("mode") != "shards" or \
                                len(ek["shards"]) != tp:
                            raise SystemExit(
                                f"tp A/B [tp={tp}]: spilled payload is "
                                f"not split per shard: {ek.get('mode')}")
            b = LLMEngine(cfg, rng_seed=0)
            b.start()
            b_comps, _b_tps, b_restores = run_prompts(b)
            b_st = b.engine_stats()
        finally:
            a.shutdown()
            if b is not None:
                b.shutdown()
        if b_st["restored_pages"] < 1:
            raise SystemExit(f"tp A/B [tp={tp}]: cold replica B restored "
                             f"nothing — the sharded tier path is inert")
        n_r = max(1, len(b_restores))
        return {
            "tp_degree": tp,
            "mesh_shape": a_st["mesh_shape"],
            "a_completions": a_comps, "b_completions": b_comps,
            "gen_tokens_per_s_a": round(a_tps, 1),
            "spilled_pages_a": a_st["spilled_pages"],
            "restored_pages_b": b_st["restored_pages"],
            "restore_partial_b": b_st["restore_partial"],
            "spec_rounds_a": a_st["spec_rounds"],
            "kv_shard_pool_bytes": a_st["kv_shard_pool_bytes"],
            "restore_ms_mean_b": round(sum(
                r["restore_ms"] for r in b_restores) / n_r, 2),
        }

    one = arm(1)
    two = arm(2)
    identical = (one["a_completions"] == two["a_completions"]
                 == two["b_completions"] == one["b_completions"])
    tp_res = {
        "label": "tp_shard_ab",
        "model": tp_cfg.model_id,
        # the platform the two engines ran on, as jax reports it
        "env": ("cpu-tiny" if jax.devices()[0].platform == "cpu"
                else jax.devices()[0].platform),
        "requests": len(prompts),
        "shared_prefix_tokens": len(shared),
        "greedy_identical": identical,
        "decode_speedup": round(
            two["gen_tokens_per_s_a"] / one["gen_tokens_per_s_a"], 2)
        if one["gen_tokens_per_s_a"] else None,
        "arms": {},
    }
    for row in (one, two):
        row.pop("a_completions")
        row.pop("b_completions")
        tp_res["arms"][f"tp{row['tp_degree']}"] = row
    print(json.dumps({"tp": tp_res}))
    if not identical:
        raise SystemExit(
            "tp A/B: sharding the engine changed greedy output on the "
            "lossless path — per-head attention and the row-parallel "
            "psums must be token-exact; not benchmarking a broken mesh")

    merged = {"metric": "serve_tp_ab", "value":
              two["gen_tokens_per_s_a"], "unit": "tokens_per_s",
              "extra": {"tp": tp_res}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["tp"] = tp_res
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)


def _run_failover(args):
    """--failover-ab: mid-stream generation failover harness (ISSUE 14).

    Sustained greedy streaming over 3 cpu-tiny replicas with the cluster
    KV tier on; once the window is genuinely mid-flight, a chaos
    `replica_kill` fault picks the BUSIEST replica (live queue-length
    probe), runs its SIGTERM-grace eager spill, then hard-kills it. The
    proxy must splice every interrupted stream onto a survivor through
    the engine continuation path (tier restore of the victim's spilled
    chains, else suffix-only recompute).

    Hard asserts:
      - >= --failover-min-complete of streams complete;
      - every RESUMED stream is byte-identical to its uninterrupted
        reference run (zero diverged/duplicated/missing tokens; both
        passes run on their own fresh fleet so the reference comparison
        is cold-vs-cold, which is bit-stable — un-resumed flips are
        concurrent prefill-packing ULP noise, reported not gated);
      - at least one stream actually resumed (a kill that lands on an
        idle replica exercises nothing — refuse to report for it);
      - max added latency on resumed streams is bounded by fault
        detection + one restore + suffix prefill, NOT a full re-decode;
      - a violation exemplar for a resumed stream carries an ordered
        `failover` stage with its restore accounting.

    Merges into --out under extra.failover."""
    import os
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.observability import attribution
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    from ray_tpu.util import state
    from ray_tpu.util.chaos import FaultSchedule

    n_streams = args.failover_streams
    concurrency = args.failover_concurrency
    gen_tokens = args.failover_tokens
    n_replicas = 3

    llm_cfg = LLMConfig(
        model_id="llama-tiny", model_config=llama.llama_tiny(vocab_size=2048),
        num_replicas=n_replicas, max_batch_size=8, page_size=32,
        num_pages=256, max_prompt_len=576, max_seq_len=640,
        max_tokens=gen_tokens,
        # tier on: the survivor restores the victim's eager-spilled
        # chains instead of recomputing the whole prefix
        kv_tier_enabled=True, prefix_cache_max_pages=64,
        # deliberately unmeetable TTFT SLO + sample-everything: every
        # stream ships a violation exemplar, so resumed-stream timelines
        # (with their `failover` stage) are observable from the CP store
        slo_ttft_p99_ms=0.1, slo_sample_rate=1.0)

    ray_tpu.init(num_cpus=max(8, (os.cpu_count() or 1)))

    def deploy(app: str):
        # 3 engine replicas cold-import JAX concurrently; on a
        # small/loaded host a worker can miss its creation window —
        # retry the deploy, it is not the thing under test
        for attempt in range(3):
            try:
                serve.run(build_openai_app(llm_cfg, route_prefix="/v1"),
                          name=app, route_prefix="/v1")
                return serve.start_http_proxy(port=0)
            except RuntimeError:
                if attempt == 2:
                    raise
                serve.shutdown()
                time.sleep(2.0)

    def prompt_of(i: int) -> str:
        # unique head per stream: no cross-stream prefix sharing, so the
        # resumed leg's cache state is the victim's spilled chains or
        # nothing — exactly the continuation-admit paths under test.
        # SHORT prompt (~3 pages), long decode: streams spend almost all
        # of their life mid-decode with a non-empty emitted-token
        # journal, so the kill interrupts real generation (a fault in
        # queue/prefill resumes with an empty journal = a plain fresh
        # re-dispatch that never exercises the continuation path)
        return (f"[stream {i:03d}] shard {i} reports: "
                + "status nominal, queue drains, " * 2)

    def esum(rows: list, key: str) -> int:
        return sum(e.get(key) or 0 for e in rows)

    # Reference pass: uninterrupted greedy streams on a DEDICATED fresh
    # fleet — the identity fingerprint AND the latency baseline. The
    # chaos pass below runs on its own fresh fleet (same config + seed
    # => identical weights) so both passes admit every prompt cold:
    # comparing a cold run against a prefix-cache-hit rerun of the same
    # prompt is placement/chunk-split ULP noise on the cpu-tiny random
    # weights, not a failover property (same hazard the fleet harness
    # documents for cross-arm completions).
    proxy = deploy("llm-failover-ref")
    base = f"http://127.0.0.1:{proxy.port}/v1/completions"
    # warm: compile the prefill bucket + decode program and the SSE path
    _post_stream_resume(base, {"prompt": "[warmup] compile the graph.",
                               "max_tokens": 4, "temperature": 0.0},
                        "fowarm0000")
    ref = {}
    lock = threading.Lock()

    def one_ref(i: int):
        out = _post_stream_resume(
            base, {"prompt": prompt_of(i), "max_tokens": gen_tokens,
                   "temperature": 0.0}, f"foref{i:05d}", timeout=120.0)
        with lock:
            ref[i] = out

    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one_ref, range(n_streams)))
    spurious = [i for i, r in ref.items() if r["resumes"]]
    if spurious:
        raise SystemExit(
            f"failover A/B: reference streams resumed with no fault "
            f"injected: {spurious[:5]} — the resume path fires spuriously")
    serve.shutdown()
    time.sleep(1.0)

    # chaos pass: same prompts on a fresh fleet, kill the busiest
    # replica once the window is mid-flight
    app_name = "llm-failover"
    proxy = deploy(app_name)
    base = f"http://127.0.0.1:{proxy.port}/v1/completions"
    ctl = get_or_create_controller()

    def engines() -> list:
        st = ray_tpu.get(ctl.detailed_status.remote(), timeout=60)
        for _full, d in st.items():
            if d.get("app") == app_name and d.get("engine"):
                return [e or {} for e in d["engine"]]
        return []

    _post_stream_resume(base, {"prompt": "[warmup] compile the graph.",
                               "max_tokens": 4, "temperature": 0.0},
                        "fowarm0001")
    e0 = engines()
    rows = {}
    done = [0]

    def one(i: int):
        try:
            out = _post_stream_resume(
                base, {"prompt": prompt_of(i), "max_tokens": gen_tokens,
                       "temperature": 0.0}, f"fochaos{i:04d}", timeout=120.0)
            row = {"ok": True, **out}
        except Exception as e:  # noqa: BLE001 — failure is data here
            row = {"ok": False, "detail": repr(e)[:200], "resumes": 0}
        with lock:
            rows[i] = row
            done[0] += 1

    sched = FaultSchedule(None, [
        (0.0, "replica_kill", {"app": app_name, "deployment": "llm",
                               "busiest": True, "prepare": True})], seed=7)
    with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
        futs = [pool.submit(one, i) for i in range(n_streams)]
        # fire once the window is genuinely mid-flight: a few streams
        # finished (the fleet is past compile), plenty remain to
        # interrupt. The busiest-probe + SIGTERM-grace spill inside the
        # fault add their own delay before the kill lands.
        fire_deadline = time.monotonic() + 300.0
        while time.monotonic() < fire_deadline:
            with lock:
                if done[0] >= max(1, n_streams // 8):
                    break
            time.sleep(0.02)
        sched.start()
        for f in futs:
            f.result(timeout=300)
    kill_report = sched.stop()
    if len(kill_report) < 1 or not kill_report[0]["ok"] or \
            "killed replica" not in kill_report[0]["detail"]:
        raise SystemExit(
            f"failover A/B: the replica_kill fault itself failed "
            f"({kill_report!r}) — nothing was exercised, refusing to "
            f"report an SLO for it")

    completed = sorted(i for i, r in rows.items() if r["ok"])
    rate = len(completed) / n_streams
    resumed = [i for i in completed if rows[i]["resumes"] > 0]
    diverged = [i for i in completed if rows[i]["text"] != ref[i]["text"]]
    # the identity SLO is on RESUMED streams: a splice that drops,
    # duplicates or corrupts a token shows up here. Un-resumed streams
    # never touch the failover machinery — a flip there is concurrent
    # prefill-packing ULP noise on the cpu-tiny random weights (restored
    # prefixes change neighbours' chunk packing; same hazard the fleet
    # harness documents for cross-arm completions), reported not gated.
    div_resumed = [i for i in diverged if rows[i]["resumes"] > 0]
    div_unresumed = [i for i in diverged if not rows[i]["resumes"]]
    e1 = engines()
    stream_resumes = proxy.stats.get("stream_resumes", 0)
    engine_resumed = esum(e1, "failover_resumed") - esum(
        e0, "failover_resumed")
    restored_tokens = esum(e1, "failover_restored_tokens") - esum(
        e0, "failover_restored_tokens")

    ref_p50_ms = statistics.median(
        r["client_latency_s"] for r in ref.values()) * 1e3
    added_ms = sorted(
        (rows[i]["client_latency_s"] - ref[i]["client_latency_s"]) * 1e3
        for i in resumed)
    max_added_ms = added_ms[-1] if added_ms else 0.0
    # one fault detection + redispatch + restore + suffix prefill + the
    # transient queueing of a 2-survivor fleet absorbing the victim's
    # load: the constant covers detection (dead-handle probe windows)
    # plus the replacement replica's cold start contending for CPU on a
    # small host, the per-stream terms scale with the reference run. The
    # splice PATH is proven by the engine counters (failover_resumed /
    # failover_restored_tokens below); this bound refuses a stream that
    # additionally pays repeated full re-decodes on top of all that.
    bound_ms = 8000.0 + 2.0 * ref_p50_ms

    # the resumed stream's timeline must carry the spliced critical path:
    # an ordered `failover` stage between route and queue, with the
    # restore accounting the proxy stamped from resume_meta
    rec = None
    poll_deadline = time.monotonic() + 30.0
    while rec is None and time.monotonic() < poll_deadline:
        for i in resumed:
            cand = state.get_slo_exemplar(f"fochaos{i:04d}")
            names = [s.get("stage") for s in (cand or {}).get("stages")
                     or []]
            if cand is not None and "failover" in names:
                rec = cand
                break
        if rec is None:
            time.sleep(0.5)

    serve.shutdown()
    ray_tpu.shutdown()

    failover = {
        "label": "failover_midstream",
        "model": llm_cfg.model_id, "env": "cpu-tiny",
        "replicas": n_replicas, "streams": n_streams,
        "concurrency": concurrency, "max_tokens": gen_tokens,
        "kill": kill_report[0]["detail"],
        "completed": len(completed),
        "completion_rate": round(rate, 4),
        "min_completion_rate": args.failover_min_complete,
        "resumed_streams": len(resumed),
        # per-resume journal length at the fault: >0 entries prove the
        # kill interrupted live decode, not just queued/prefilling work
        "resumed_at_tokens": sorted(
            t for i in resumed for t in rows[i].get("resumed_at") or []),
        "diverged_resumed_streams": len(div_resumed),
        "diverged_unresumed_streams": len(div_unresumed),
        "proxy_stream_resumes": stream_resumes,
        "engine_failover_resumed": engine_resumed,
        "engine_failover_restored_tokens": restored_tokens,
        "per_replica_requests": [e.get("requests") for e in e1],
        "ref_p50_latency_ms": round(ref_p50_ms, 2),
        "max_added_latency_ms": round(max_added_ms, 2),
        "added_latency_bound_ms": round(bound_ms, 2),
        "exemplar_request_id": (rec or {}).get("request_id"),
        "exemplar_stages": [s.get("stage")
                            for s in (rec or {}).get("stages") or []],
    }
    print(json.dumps({"failover": failover}))

    if rate < args.failover_min_complete:
        fails = [rows[i].get("detail") for i in rows if not rows[i]["ok"]]
        raise SystemExit(
            f"failover A/B: stream completion rate {rate:.4f} below the "
            f"{args.failover_min_complete} SLO after killing the busiest "
            f"replica; failures: {fails[:5]}")
    if div_resumed:
        pairs = [(i, rows[i]["resumes"], ref[i]["text"][:80],
                  rows[i]["text"][:80]) for i in div_resumed[:3]]
        raise SystemExit(
            f"failover A/B: {len(div_resumed)} RESUMED streams diverged "
            f"from their uninterrupted greedy reference — resumption is "
            f"corrupting tokens, not benchmarking it; samples: {pairs!r}")
    if not resumed or stream_resumes < 1 or engine_resumed < 1:
        raise SystemExit(
            f"failover A/B: the kill interrupted nothing (client resumes "
            f"{len(resumed)}, proxy stream_resumes {stream_resumes}, "
            f"engine failover_resumed {engine_resumed}) — the window was "
            f"not mid-flight, refusing to report an SLO")
    if max_added_ms > bound_ms:
        raise SystemExit(
            f"failover A/B: worst resumed-stream added latency "
            f"{max_added_ms:.0f}ms exceeds the one-restore+suffix-prefill "
            f"bound {bound_ms:.0f}ms — resumption is paying a full "
            f"re-decode, not a splice")
    if rec is None:
        raise SystemExit(
            "failover A/B: no violation exemplar for a resumed stream "
            "carries a `failover` stage — the handoff is dropping the "
            "timeline, the attribution table would lie about these tails")
    names = failover["exemplar_stages"]
    ranks = [attribution._STAGE_INDEX[n] for n in names
             if n in attribution._STAGE_INDEX]
    if ranks != sorted(ranks):
        raise SystemExit(f"failover A/B: resumed exemplar stages out of "
                         f"canonical order: {names}")

    # merge into --out WITHOUT clobbering earlier headline rows
    merged = {"metric": "serve_failover_completion",
              "value": failover["completion_rate"], "unit": "rate",
              "extra": {"failover": failover}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["failover"] = failover
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)


def _run_open_loop(args):
    """--open-loop: Poisson-arrival open-loop ELASTIC harness (ISSUE 17).

    A multi-tenant shared-prefix workload under seeded open-loop arrivals
    (arrivals never gate on completions) drives a scale-up-then-scale-down
    schedule mid-window, A/B'd warm-start-on vs warm-start-off:

      phase 1  steady:   base replicas at steady-state hit rate;
      phase 2  scale-up: +1 replica — in the warm arm it pre-populates
               its prefix cache from the CP kv_tier index through the
               compressed ChainStream BEFORE entering the routing table,
               in the cold arm it enters empty;
      phase 3  downscale: back to base mid-stream — controller drains the
               coldest replica kill-free while arrivals keep coming.

    HARD asserts (full run): warm post-scale-up fleet hit rate >= 0.8 x
    its own steady-state AND materially above the cold arm (which
    demonstrably craters); the downscale phase completes 100% of streams
    with zero resumed-stream token divergence; the client p99 TTFT SLO is
    judged by PR 12 dominant-stage attribution (a violated SLO names the
    stage that ate the tail, so the failure is actionable). --smoke keeps
    the seeded schedule but drops the SLO/ratio asserts and the cold arm
    (satellite 6: fast deterministic CI leg). Concurrency is bounded by
    --open-loop-rate x service time, not a worker pool — raise the rate
    on real fleets for thousands of concurrent streams.

    Merges into --out under extra.elastic."""
    import os
    import random
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve.controller import get_or_create_controller
    from ray_tpu.serve.llm import LLMConfig, build_openai_app
    from ray_tpu.util import state as state_api

    smoke = args.smoke
    tenants = 6 if smoke else 8
    rate = args.open_loop_rate if not smoke else min(args.open_loop_rate,
                                                     8.0)
    win = 3.0 if smoke else args.open_loop_window
    base_replicas, up_replicas = 2, 3
    bench_cpus = max(8, (os.cpu_count() or 1))

    prefixes = [
        (f"[tenant {t:02d} system] You answer tersely and cite sources. "
         * 12)[:480]
        for t in range(tenants)]

    def mk_prompt(t: int, i: int) -> str:
        return prefixes[t % tenants] + f" Q{i:05d}: summarize item {i}."

    def fleet_engines(ctl, app_name: str) -> list:
        st = ray_tpu.get(ctl.detailed_status.remote(), timeout=60)
        for full, d in st.items():
            if d.get("app") == app_name and d.get("engine"):
                return [e or {} for e in d["engine"]]
        return []

    def fleet_sum(engines: list, key: str) -> int:
        return sum(e.get(key) or 0 for e in engines)

    def wait_fleet(ctl, full_name, *, replicas, timeout=180.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = ray_tpu.get(ctl.status.remote(), timeout=30)[full_name]
            if (st["replicas"] == replicas and st["warming"] == 0
                    and st["draining"] == 0):
                return st
            time.sleep(0.2)
        raise SystemExit(f"elastic: fleet never settled at {replicas} "
                         f"replicas within {timeout}s ({st})")

    def arm(warm: bool) -> dict:
        tag = "warm" if warm else "cold"
        app_name = f"llm-elastic-{tag}"
        full_name = f"{app_name}#llm"
        llm_cfg = LLMConfig(
            model_id="llama-tiny",
            model_config=llama.llama_tiny(vocab_size=2048),
            num_replicas=base_replicas, max_batch_size=8, page_size=32,
            num_pages=256, max_prompt_len=576, max_seq_len=640,
            max_tokens=8,
            # OVERSUBSCRIBED retention cap: each base replica's affine
            # tenant share (~tenants/2 x 17 pages) exceeds 40 pages, so
            # steady state churns — evicted chains spill into the cluster
            # tier (the index warm_start reads), and relieving exactly
            # that cache pressure is why the fleet scales up at all
            kv_tier_enabled=True, prefix_cache_max_pages=40,
            warm_start_enabled=warm,
            slo_ttft_p99_ms=args.open_loop_slo_ms, slo_sample_rate=1.0)

        ray_tpu.init(num_cpus=bench_cpus)
        ctl = get_or_create_controller()
        serve.run(build_openai_app(llm_cfg, route_prefix="/v1"),
                  name=app_name, route_prefix="/v1")
        # multi-proxy ingress (satellite 1): two proxies share one
        # routing long-poll; the open loop round-robins across them so a
        # single proxy event loop is not the arrival ceiling
        proxies = serve.start_http_proxies(2, port=0)
        bases = [f"http://127.0.0.1:{p.port}/v1/completions"
                 for p in proxies]

        # compile the long bucket, then seed every tenant prefix so each
        # is resident somewhere AND overflowing into the tier (2 tenants'
        # 15-page prefixes already exceed the 64-page retention cap)
        _post_stream(bases[0], {"prompt": mk_prompt(0, 90000),
                                "max_tokens": 4, "temperature": 0.0})
        for t in range(tenants):
            _post_stream(bases[t % len(bases)],
                         {"prompt": mk_prompt(t, 91000 + t),
                          "max_tokens": 4, "temperature": 0.0})
        time.sleep(2.0)   # summary tick + tier index settle

        records = []
        lock = threading.Lock()
        phase_name = ["steady"]

        # Zipf-ish tenant draw, pre-drawn from its own rng so worker
        # threads' completion order can't perturb it: the hot few
        # tenants (who dominate traffic) fit inside the warm-start page
        # budget, the cold tail churns the cache and feeds the tier —
        # the skew every real multi-tenant fleet has
        tenant_rng = random.Random(args.open_loop_seed + 1)
        weights = [1.0 / (t + 1.5) for t in range(tenants)]
        tenant_seq = tenant_rng.choices(range(tenants), weights=weights,
                                        k=100000)

        def one(i: int):
            ph = phase_name[0]
            t = tenant_seq[i % len(tenant_seq)]
            prompt = mk_prompt(t, i)
            try:
                out = _post_stream_resume(
                    bases[i % len(bases)],
                    {"prompt": prompt, "max_tokens": 4,
                     "temperature": 0.0}, rid=f"el{ph[:2]}{i:06d}",
                    timeout=120.0)
                rec = {"phase": ph, "ok": True, "prompt": prompt,
                       "text": out["text"], "resumes": out["resumes"],
                       "ttft_s": out["client_ttft_s"],
                       "prompt_tokens":
                           out["usage"].get("prompt_tokens", 0)}
            except Exception as e:  # noqa: BLE001 — failure is data here
                rec = {"phase": ph, "ok": False, "prompt": prompt,
                       "error": repr(e)[:200], "resumes": 0}
            with lock:
                records.append(rec)

        _PHASE_OFF = {"steady": 0, "transient": 20000,
                      "post_up": 40000, "down": 60000}

        def window(name, dur, *, at=None):
            import zlib
            phase_name[0] = name
            # per-phase rng: both arms replay the IDENTICAL arrival
            # sequence for each phase regardless of earlier phase drift
            rng_p = random.Random(args.open_loop_seed * 100003
                                  + zlib.crc32(name.encode()))
            off = _PHASE_OFF[name]
            e0 = fleet_engines(ctl, app_name)
            n = _open_loop_dispatch(lambda i: one(off + i), rng_p, rate,
                                    duration_s=dur,
                                    max_workers=128, at=at)
            e1 = fleet_engines(ctl, app_name)
            with lock:
                recs = [r for r in records if r["phase"] == name]
            toks = sum(r.get("prompt_tokens") or 0 for r in recs)
            # a downscale inside the window removes the victim's
            # counters from the fleet sum, so the post-retirement delta
            # undercounts — the down-window rate is a FLOOR, clamped
            hits = max(0, fleet_sum(e1, "prefix_hit_tokens")
                       - fleet_sum(e0, "prefix_hit_tokens"))
            return {"arrivals": n,
                    "completed": sum(1 for r in recs if r["ok"]),
                    "hit_rate": round(hits / toks, 4) if toks else 0.0,
                    "prompt_tokens": toks}

        # ---- phase 1: steady state at base replicas ------------------
        steady = window("steady", win)

        # ---- phase 2: scale up (+1), warm or cold --------------------
        ray_tpu.get(ctl.set_target_replicas.remote(
            app_name, target=up_replicas,
            reason=f"bench_up_{tag}"), timeout=30)
        wait_fleet(ctl, full_name, replicas=up_replicas)
        # the crater lives in the TRANSIENT right after publish: a cold
        # replica converges organically within seconds on cpu-tiny, so a
        # long window averages the dip away — measure it first, alone
        transient = window("transient", max(win / 3.0, 2.0))
        post_up = window("post_up", win)

        # ---- phase 3: downscale MID-WINDOW under open-loop arrivals --
        def scale_down():
            ray_tpu.get(ctl.set_target_replicas.remote(
                app_name, target=base_replicas,
                reason=f"bench_down_{tag}"), timeout=30)

        down = window("down", win, at=(win / 3.0, scale_down))
        wait_fleet(ctl, full_name, replicas=base_replicas)

        # downscale acceptance: 100% stream completion, zero divergence
        with lock:
            down_recs = [r for r in records if r["phase"] == "down"]
        incomplete = [r for r in down_recs if not r["ok"]]
        if incomplete:
            raise SystemExit(
                f"elastic [{tag}]: {len(incomplete)}/{len(down_recs)} "
                f"streams failed across the mid-window downscale — drain "
                f"is not kill-free: "
                f"{[r['error'] for r in incomplete[:5]]}")
        resumed = [r for r in down_recs if r["resumes"]]
        diverged = []
        for r in resumed:
            # greedy re-serve of the same prompt is the ground truth the
            # spliced stream must match token-for-token
            ref = _post_stream_resume(
                bases[0], {"prompt": r["prompt"], "max_tokens": 4,
                           "temperature": 0.0}, rid="elref", timeout=120.0)
            if ref["text"] != r["text"]:
                diverged.append((r["prompt"][-40:], r["text"],
                                 ref["text"]))
        if diverged:
            raise SystemExit(
                f"elastic [{tag}]: {len(diverged)} resumed streams "
                f"diverged from greedy ground truth across the "
                f"downscale: {diverged[:3]!r}")

        det = ray_tpu.get(ctl.detailed_status.remote(),
                          timeout=60)[full_name]

        def _p99(rs):
            ts = sorted(r["ttft_s"] for r in rs
                        if r.get("ttft_s") is not None)
            return (ts[min(len(ts) - 1, int(0.99 * len(ts)))] * 1e3
                    if ts else float("nan"))

        # the SLO judges the serving path while capacity is at or above
        # baseline; the down window deliberately sheds a third of the
        # fleet mid-stream and is judged on completion + divergence, so
        # its turbulence is reported separately, not folded into the p99
        ttfts = [r for r in records if r["phase"] != "down"]
        p99 = _p99(ttfts)
        p99_down = _p99([r for r in records if r["phase"] == "down"])
        slo = state_api.slo_report(deployment="llm")
        dominant = (max(slo.get("dominant_stage") or {"": 0},
                        key=(slo.get("dominant_stage") or {"": 0}).get)
                    or None)
        row = {
            "label": f"elastic_{tag}",
            "tenants": tenants, "arrival_rate": rate,
            "window_s": win, "seed": args.open_loop_seed,
            "proxies": len(proxies),
            "steady": steady, "transient": transient,
            "post_up": post_up, "down": down,
            "downscale_streams": len(down_recs),
            "downscale_completed": len(down_recs) - len(incomplete),
            "downscale_resumes": sum(r["resumes"] for r in down_recs),
            "client_p99_ttft_ms": round(p99, 2),
            "client_p99_ttft_ms_down": round(p99_down, 2),
            "slo_violations": slo.get("violations"),
            "slo_budget_ms": args.open_loop_slo_ms,
            "p99_hard_ceiling_ms": 2.5 * args.open_loop_slo_ms,
            "slo_dominant_stage": dominant,
            "slo_ttft_ms": slo.get("ttft_ms"),
            "warm": det.get("warm"),
            "scale_counters": det.get("scale_counters"),
            "scale_decisions": (det.get("scale_decisions") or [])[-6:],
        }
        print(json.dumps({f"elastic_arm_{tag}": row}))
        if warm and not smoke:
            w = det.get("warm") or {}
            if not w.get("replicas_warmed") or not w.get("pages"):
                raise SystemExit(
                    f"elastic [warm]: the scale-up replica pulled no "
                    f"pages from the tier (warm stats {w}) — the tier "
                    f"index or the ChainStream pull is inert, the A/B "
                    f"would compare cold vs cold")
        # p99 SLO judged by dominant-stage attribution (full run, WARM
        # arm only — the cold arm is the demonstration of what blowing
        # the SLO looks like, its queue-dominant tail is the expected
        # result, not a failure): the assert NAMES the stage that ate
        # the tail so a red run is actionable, not just red. Violations
        # against --open-loop-slo-ms are counted and attributed above;
        # the HARD kill line is 2.5x that budget, so a shared CI box's
        # scheduler tail doesn't flake the bench while a genuine queue
        # collapse (cold-arm territory) still fails the run
        hard_ms = 2.5 * args.open_loop_slo_ms
        if warm and not smoke and ttfts and p99 > hard_ms:
            raise SystemExit(
                f"elastic [{tag}]: client p99 TTFT {p99:.1f}ms blew the "
                f"{hard_ms:.0f}ms hard ceiling (2.5x the "
                f"{args.open_loop_slo_ms}ms SLO budget); attribution "
                f"blames '{dominant}' (stage_ms {slo.get('stage_ms')}) "
                f"— scale the fleet if queue/prefill, fix the engine "
                f"if decode")
        serve.shutdown()
        ray_tpu.shutdown()
        return row

    warm_row = arm(True)
    cold_row = None if smoke else arm(False)

    # retention and crater are judged on the post-publish TRANSIENT —
    # the first arrivals the scaled-up fleet serves, before organic
    # convergence can launder a cold replica into a warm-looking one
    retention = (warm_row["transient"]["hit_rate"]
                 / warm_row["steady"]["hit_rate"]
                 if warm_row["steady"]["hit_rate"] else 0.0)
    elastic = {
        "label": "elastic_open_loop_ab",
        "env": "cpu-tiny", "smoke": smoke,
        "base_replicas": base_replicas, "up_replicas": up_replicas,
        "warm": warm_row, "cold": cold_row,
        "warm_hit_retention": round(retention, 4),
        "min_hit_retention": 0.8,
        "cold_crater": (round(warm_row["transient"]["hit_rate"]
                              - cold_row["transient"]["hit_rate"], 4)
                        if cold_row else None),
    }
    print(json.dumps({"elastic": elastic}))

    if not smoke:
        if retention < 0.8:
            raise SystemExit(
                f"elastic A/B: warm scale-up retained only "
                f"{retention:.3f} of the steady-state hit rate through "
                f"the post-publish transient (steady "
                f"{warm_row['steady']['hit_rate']} -> transient "
                f"{warm_row['transient']['hit_rate']}; floor 0.8) — the "
                f"warm start is not protecting cache warmth")
        if elastic["cold_crater"] < 0.05:
            raise SystemExit(
                f"elastic A/B: warm transient hit rate "
                f"{warm_row['transient']['hit_rate']} is not materially "
                f"above the cold arm's "
                f"{cold_row['transient']['hit_rate']} — either the cold "
                f"arm didn't crater (scale-up invisible) or the warm "
                f"start is inert")

    merged = {"metric": "serve_elastic_hit_retention",
              "value": elastic["warm_hit_retention"], "unit": "ratio",
              "extra": {"elastic": elastic}}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                merged = json.load(f)
            merged.setdefault("extra", {})["elastic"] = elastic
        except ValueError:
            pass
    with open(args.out, "w") as f:
        json.dump(merged, f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--prompt-tokens", type=int, default=128)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model on CPU (smoke mode)")
    ap.add_argument("--curve", action="store_true",
                    help="sweep concurrency levels up to --concurrency and "
                         "record a TTFT-vs-throughput curve")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="measure the shared_prefix_1024 operating point "
                         "(1024-token shared prefix, unique suffixes) with "
                         "the prefix cache on vs off; merges the result "
                         "into --out (implied by --curve)")
    ap.add_argument("--spec-ab", action="store_true",
                    help="A/B speculative decoding on a repetitive-suffix "
                         "greedy workload: spec-on vs spec-off deployments, "
                         "hard-asserts token identity, reports accepted "
                         "draft tokens per verify round; merges the result "
                         "into --out")
    ap.add_argument("--kv-tier-ab", action="store_true",
                    help="A/B the cluster tiered KV cache on a shared-"
                         "prefix greedy workload: a COLD replica B "
                         "restoring replica A's spilled prefix pages "
                         "through the CP index vs cold prefill, "
                         "hard-asserts token identity; merges the result "
                         "into --out")
    ap.add_argument("--tp-ab", action="store_true",
                    help="A/B tensor-parallel serving (ISSUE 20): "
                         "in-process TP=1 vs TP=2 engine pairs (full "
                         "stack: prefix cache + spec decode + sharded "
                         "kv-tier restore), hard-asserts greedy token "
                         "identity on the lossless path, reports decode "
                         "throughput + restore time per arm; merges into "
                         "--out under extra.tp and skips the LLM "
                         "headline bench")
    ap.add_argument("--profile-ab", action="store_true",
                    help="A/B the engine phase timers (profiling_enabled "
                         "on vs off) on the headline point; exits nonzero "
                         "if the p50 TTFT overhead exceeds noise")
    ap.add_argument("--slo-ab", action="store_true",
                    help="A/B the per-request SLO attribution pipeline "
                         "(timeline stamping + exemplar shipping) on the "
                         "headline point: rerun with "
                         "slo_attribution_enabled=False on a fresh cluster "
                         "and assert the p50 TTFT delta is within noise")
    ap.add_argument("--metrics-ab", action="store_true",
                    help="A/B the built-in metrics pipeline: rerun the "
                         "headline point with metrics_enabled=False on a "
                         "fresh cluster and assert the p50 TTFT delta is "
                         "within noise (ISSUE 4 overhead bound)")
    ap.add_argument("--events-ab", action="store_true",
                    help="A/B the flight-recorder event journal: rerun "
                         "the headline point with events_enabled=False on "
                         "a fresh cluster and assert the p50 TTFT delta "
                         "is within noise (ISSUE 19 overhead bound); "
                         "merges into --out under extra.events")
    ap.add_argument("--chaos-suite", action="store_true",
                    help="run the deterministic multi-fault chaos suite "
                         "(worker kill, node kill, node drain, CP restart) "
                         "against a plain serve app with hard SLO asserts; "
                         "merges into --out under extra.chaos_suite and "
                         "skips the LLM bench")
    ap.add_argument("--chaos-seed", type=int, default=7,
                    help="seed for the chaos suite's FaultSchedules")
    ap.add_argument("--fleet", action="store_true",
                    help="sustained-load fleet harness: multi-tenant "
                         "shared-prefix traffic over >=4 replicas, "
                         "affinity-on vs pow-2-only A/B with hard "
                         "fleet-hit-rate / p50-TTFT / greedy-identity / "
                         "chaos-SLO asserts; merges into --out under "
                         "extra.fleet and skips the LLM headline bench; "
                         "also runs the prefill/decode disagg arm "
                         "(colocated vs streamed-handoff vs int8 wire) "
                         "into extra.disagg")
    ap.add_argument("--failover-ab", action="store_true",
                    help="mid-stream failover harness: sustained greedy "
                         "streaming over 3 replicas with the KV tier on, "
                         "chaos-kills the busiest replica mid-decode, "
                         "hard-asserts >=99%% stream completion, "
                         "token-identical resumed streams vs an "
                         "uninterrupted reference, and bounded added "
                         "latency; merges into --out under extra.failover "
                         "and skips the LLM headline bench")
    ap.add_argument("--failover-streams", type=int, default=64,
                    help="streams per failover pass (reference and chaos)")
    ap.add_argument("--failover-tokens", type=int, default=64,
                    help="greedy tokens per failover stream (long enough "
                         "that the kill lands mid-decode)")
    ap.add_argument("--failover-concurrency", type=int, default=8)
    ap.add_argument("--failover-min-complete", type=float, default=0.99,
                    help="stream-completion SLO for the chaos pass")
    ap.add_argument("--fleet-replicas", type=int, default=4)
    ap.add_argument("--fleet-tenants", type=int, default=8)
    ap.add_argument("--fleet-requests", type=int, default=128,
                    help="measured requests per fleet arm")
    ap.add_argument("--fleet-concurrency", type=int, default=16)
    ap.add_argument("--fleet-chaos-requests", type=int, default=128)
    ap.add_argument("--open-loop", action="store_true",
                    help="Poisson-arrival open-loop ELASTIC harness "
                    "(ISSUE 17): warm vs cold scale-up A/B with a "
                    "scale-up-then-scale-down schedule mid-window; "
                    "merges into --out under extra.elastic")
    ap.add_argument("--smoke", action="store_true",
                    help="with --open-loop: fast deterministic CI leg — "
                    "seeded arrivals, single warm arm, no SLO/ratio "
                    "asserts (stream completion + divergence stay hard)")
    ap.add_argument("--open-loop-rate", type=float, default=8.0,
                    help="Poisson arrival rate (req/s) for the open-loop "
                    "generator (also paces the --fleet measured window). "
                    "Open-loop arrivals never gate on completions, so a "
                    "rate above the box's service capacity diverges the "
                    "queue by design — size it to the hardware")
    ap.add_argument("--open-loop-window", type=float, default=10.0,
                    help="seconds per elastic phase window")
    ap.add_argument("--open-loop-seed", type=int, default=17,
                    help="seed for the arrival sequence (both arms "
                    "replay the same draws)")
    ap.add_argument("--open-loop-slo-ms", type=float, default=5000.0,
                    help="client p99 TTFT SLO for the full elastic run; "
                    "violations are judged by dominant-stage attribution")
    ap.add_argument("--fleet-min-hit-rate", type=float, default=0.90,
                    help="fleet prefix-cache hit-rate SLO for the "
                         "affinity-on arm")
    ap.add_argument("--out", default="SERVE_BENCH.json",
                    help="JSON file the shared-prefix result merges into")
    ap.add_argument("--no-preflight", action="store_true",
                    help="skip the serve-LLM smoke tests before benching")
    args = ap.parse_args()
    args.shared_prefix = args.shared_prefix or args.curve

    if args.chaos_suite:
        # the chaos suite is a robustness harness, not a perf number: it
        # runs a plain (non-LLM) app, so the LLM preflight doesn't apply.
        # Flight-recorder coverage does: the suite hard-asserts
        # fault→symptom causal adjacency out of the event journal, which
        # is only as good as the store/flusher/emitters behind it.
        if not args.no_preflight:
            import os
            import subprocess
            import sys
            repo = os.path.dirname(os.path.abspath(__file__))
            chaos_tests = ["tests/test_events.py"]
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", *chaos_tests],
                cwd=repo,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
            if rc != 0:
                sys.exit(f"preflight failed: pytest -q "
                         f"{' '.join(chaos_tests)} exited {rc} "
                         f"(--no-preflight to override)")
        _run_chaos_suite(args)
        return

    if args.tp_ab:
        if not args.no_preflight:
            import os
            import subprocess
            import sys
            repo = os.path.dirname(os.path.abspath(__file__))
            # sharding coverage first: a TP throughput number over a mesh
            # that silently changes tokens is a lie — the identity tests
            # run the same host-device mesh this arm uses, and the
            # partition-rule unit tests stand behind the weight shardings
            tp_tests = ["tests/test_tp_serving.py",
                        "tests/test_parallel.py",
                        "tests/test_paged_kernels.py"]
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", *tp_tests],
                cwd=repo,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
            if rc != 0:
                sys.exit(f"preflight failed: pytest -q "
                         f"{' '.join(tp_tests)} exited {rc} "
                         f"(--no-preflight to override)")
        _run_tp_ab(args)
        return

    if args.fleet:
        if not args.no_preflight:
            import os
            import subprocess
            import sys
            repo = os.path.dirname(os.path.abspath(__file__))
            # affinity unit/integration coverage first: a fleet hit-rate
            # number from a broken scorer is a lie with a decimal point.
            # attribution coverage too: the fleet report now carries the
            # per-stage tail breakdown, which is only as good as the
            # timeline stamping + exemplar store it reads from. failover
            # coverage rides along: the fleet chaos leg kills a preferred
            # holder mid-load, so its SLO leans on the resume path.
            # disagg coverage too: the fleet run now carries the streamed
            # prefill/decode handoff arm, whose identity assert is only
            # as good as the codec/restore tests behind it.
            # elastic coverage rides along: the fleet window is now an
            # open-loop arrival process over an elastically-scalable
            # controller, so the warm-start/drain/scale races must hold
            # flight-recorder coverage too: the fleet's scale/failover
            # story is debugged through the event journal
            # TP coverage rides along (ISSUE 20): a fleet may mix
            # tp_degree replicas, and the namespace/identity guarantees
            # those tests pin are what keep mixed fleets coherent
            fleet_tests = ["tests/test_affinity_routing.py",
                           "tests/test_attribution.py",
                           "tests/test_failover.py",
                           "tests/test_serve_disagg.py",
                           "tests/test_elastic.py",
                           "tests/test_events.py",
                           "tests/test_tp_serving.py"]
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", *fleet_tests],
                cwd=repo,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
            if rc != 0:
                sys.exit(f"preflight failed: pytest -q "
                         f"{' '.join(fleet_tests)} exited {rc} "
                         f"(--no-preflight to override)")
        _run_fleet(args)
        _run_fleet_disagg(args)
        return

    if args.open_loop:
        if not args.no_preflight and not args.smoke:
            import os
            import subprocess
            import sys
            repo = os.path.dirname(os.path.abspath(__file__))
            # elastic coverage first: a hit-retention number over broken
            # warm-start/drain races is a lie; failover coverage rides
            # along because the downscale leg leans on the drain path
            el_tests = ["tests/test_elastic.py", "tests/test_failover.py"]
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", *el_tests],
                cwd=repo,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
            if rc != 0:
                sys.exit(f"preflight failed: pytest -q "
                         f"{' '.join(el_tests)} exited {rc} "
                         f"(--no-preflight to override)")
        _run_open_loop(args)
        return

    if args.failover_ab:
        if not args.no_preflight:
            import os
            import subprocess
            import sys
            repo = os.path.dirname(os.path.abspath(__file__))
            # continuation-path coverage first: a completion-rate number
            # from a broken resume splice is a lie — and the harness
            # reads resumed-stream timelines out of the exemplar store,
            # so attribution coverage rides along
            fo_tests = ["tests/test_failover.py",
                        "tests/test_attribution.py"]
            rc = subprocess.run(
                [sys.executable, "-m", "pytest", "-q", *fo_tests],
                cwd=repo,
                env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
            if rc != 0:
                sys.exit(f"preflight failed: pytest -q "
                         f"{' '.join(fo_tests)} exited {rc} "
                         f"(--no-preflight to override)")
        _run_failover(args)
        return

    # Preflight: a perf number from a broken engine is worse than no
    # number. The smoke tests run tiny-on-CPU in a subprocess so the
    # driver stays off the TPU (one process per chip).
    if not args.no_preflight:
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.abspath(__file__))
        # graftlint first: it is ~2s and catches the exact bug classes
        # (host syncs in the decode path, RPCs under locks) that turn a
        # bench run into a misleading number
        rc = subprocess.run(
            [sys.executable, "-m", "ray_tpu.scripts.cli", "lint"],
            cwd=repo, env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
        if rc != 0:
            sys.exit(f"preflight failed: ray-tpu lint exited {rc} — fix "
                     f"the findings, pragma the sites, or regenerate the "
                     f"baseline (--no-preflight to override)")
        preflight_tests = ["tests/test_serve_llm.py"]
        if args.slo_ab:
            preflight_tests.append("tests/test_attribution.py")
        if args.spec_ab:
            preflight_tests.append("tests/test_spec_decode.py")
            # interpret-mode pallas identity + kernel equivalence: the
            # CPU-side coverage behind the on-device backend legs
            preflight_tests.append("tests/test_paged_kernels.py")
        if args.kv_tier_ab:
            # no -m filter here, so this includes the slow two-replica
            # cross-restore stress test — exactly the coverage a kv-tier
            # perf number needs behind it
            preflight_tests.append("tests/test_kv_tier.py")
            preflight_tests.append("tests/test_kv_codec.py")
            # sharded-blob coverage (ISSUE 20): the tier wire format now
            # has a per-shard payload mode, and a tier perf number is
            # only as good as the reassembly + namespace tests behind it
            preflight_tests.append("tests/test_tp_serving.py")
            if "tests/test_paged_kernels.py" not in preflight_tests:
                preflight_tests.append("tests/test_paged_kernels.py")
        rc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", *preflight_tests],
            cwd=repo, env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
        if rc != 0:
            sys.exit(f"preflight failed: pytest -q "
                     f"{' '.join(preflight_tests)} exited {rc} — not "
                     f"benchmarking a broken serve path "
                     f"(--no-preflight to override)")

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig, build_openai_app

    # Logical CPUs: serving actors (controller + replicas) are IO-bound hosts
    # around the chip-bound engine; don't let a small host starve scheduling.
    bench_cpus = max(8, (__import__("os").cpu_count() or 1))
    # metrics/events A/B: the "on" arm flushes aggressively (1 s / 0.5 s
    # vs the defaults) so the pipeline is actually exercised during a
    # short run
    _ab_cfg = None
    if args.metrics_ab:
        _ab_cfg = {"metrics_enabled": True, "metrics_flush_interval_s": 1.0}
    elif args.events_ab:
        _ab_cfg = {"events_enabled": True, "events_flush_interval_s": 0.5}
    ray_tpu.init(num_cpus=bench_cpus, _system_config=_ab_cfg)
    has_tpu = any(n.get("resources", {}).get("TPU", 0) > 0
                  for n in ray_tpu.nodes())
    if not args.tiny and not has_tpu:
        raise SystemExit(
            "bench_serve.py: no TPU found on this node (no chip device "
            "nodes, or JAX_PLATFORMS pins jax to the cpu); pass --tiny for "
            "the cpu-tiny control-flow run — there is no silent CPU "
            "substitute for the chip configuration")

    if args.tiny:
        model_cfg = llama.llama_tiny(vocab_size=2048)
        # the shared-prefix point carries 1024-token prompts: size the
        # window and the page pool for 8 concurrent long requests plus
        # parked cached pages
        llm_cfg = LLMConfig(
            model_id="llama-tiny", model_config=model_cfg,
            max_batch_size=8, page_size=32,
            num_pages=448 if args.shared_prefix else 256,
            max_prompt_len=1280 if args.shared_prefix else 256,
            max_seq_len=1536 if args.shared_prefix else 512,
            max_tokens=args.max_tokens)
    else:
        # ~1.2B on one v5e chip, bf16 weights + paged bf16 KV. 32 decode
        # slots: admission must keep up with the offered concurrency or
        # TTFT becomes queue wait (r3: b16 under 32-deep load queued ~7s)
        model_cfg = llama.llama3_1b(max_seq_len=2048)
        # decode_block 8 x pipeline_depth 3, pressure blocks of 2: measured
        # best TTFT/throughput point on one v5e with the Pallas paged-
        # attention kernel + async host fetches (engine sweep in
        # BENCH_NOTES.md: 498 tok/s, p50 TTFT 323ms at concurrency 16)
        # shared-prefix mode widens the prompt window (prefix + suffix >
        # 1024) and adds pool headroom so parked cached pages never starve
        # admissions at full slot occupancy (32 slots * 9 pages = 288)
        llm_cfg = LLMConfig(
            model_id="llama3-1b", model_config=model_cfg,
            max_batch_size=32, page_size=128,
            num_pages=320 if args.shared_prefix else 288,
            max_prompt_len=1280 if args.shared_prefix else 1024,
            max_seq_len=2048,
            decode_block=8, pipeline_depth=3, pressure_decode_block=2,
            max_tokens=args.max_tokens,
            ray_actor_options={"resources": {"TPU": 1}})

    app = build_openai_app(llm_cfg, route_prefix="/v1")
    serve.run(app, name="llm-bench", route_prefix="/v1")
    proxy = serve.start_http_proxy(port=0)
    base = f"http://127.0.0.1:{proxy.port}/v1/completions"

    # label every row with the device the engine actually ran on (its own
    # report through /v1/stats), not with what the node advertised
    with urllib.request.urlopen(base.replace("/completions", "/stats"),
                                timeout=600) as _r:
        _dev = json.loads(_r.read())
    if not args.tiny and (_dev.get("device_platform") != "tpu"
                          or _dev.get("attn_interpret")):
        raise SystemExit(
            f"bench_serve.py: the engine reports platform="
            f"{_dev.get('device_platform')!r} attn_interpret="
            f"{_dev.get('attn_interpret')!r}; the chip configuration must "
            f"run compiled on a TPU")
    env_label = "cpu-tiny" if args.tiny else _dev["device_platform"]

    prompt = "the quick brown fox jumps over the lazy dog " * (
        max(1, args.prompt_tokens // 9))

    # warmup: compile prefill buckets + decode program (incl. the widest
    # bucket for the long-prompt point) and the SSE path
    _post(base, {"prompt": prompt, "max_tokens": 4})
    _post_stream(base, {"prompt": prompt, "max_tokens": 4})
    if args.curve:
        _post_stream(base, {"prompt": "dog " * 1024, "max_tokens": 4})

    import os

    def _proc_cpu_s() -> float:
        parts = open(f"/proc/{os.getpid()}/stat").read().rsplit(") ", 1)[1]
        f = parts.split()
        return (int(f[11]) + int(f[12])) / os.sysconf("SC_CLK_TCK")

    def run_point(concurrency: int, requests: int,
                  point_prompt: str | None = None,
                  label: str | None = None,
                  prompt_fn=None, max_tokens: int | None = None) -> dict:
        """Drive one operating point over SSE; TTFT is CLIENT-observed
        (first data: byte), engine-side ttft recorded alongside so the
        proxy/router/transport share is visible per point. prompt_fn(i)
        gives per-request prompts (shared-prefix point: unique suffixes)."""
        p = point_prompt if point_prompt is not None else prompt
        mt = args.max_tokens if max_tokens is None else max_tokens
        ttfts: list[float] = []
        engine_ttfts: list[float] = []
        latencies: list[float] = []
        tokens = 0
        prompt_tokens = 0

        def one(i: int):
            out = _post_stream(
                base, {"prompt": prompt_fn(i) if prompt_fn else p,
                       "max_tokens": mt})
            return (out["client_ttft_s"], out["client_latency_s"],
                    out["engine"].get("ttft_s"),
                    out["usage"].get("completion_tokens", 0),
                    out["usage"].get("prompt_tokens", 0))

        cpu0 = _proc_cpu_s()
        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(concurrency) as pool:
            for ttft, lat, engine_ttft, ntok, nptok in pool.map(
                    one, range(requests)):
                if ttft is not None:
                    ttfts.append(ttft)
                if engine_ttft is not None:
                    engine_ttfts.append(engine_ttft)
                if lat is not None:
                    latencies.append(lat)
                tokens += ntok
                prompt_tokens += nptok
        wall = time.monotonic() - t0
        proxy_cpu = _proc_cpu_s() - cpu0
        p50 = statistics.median(ttfts) * 1e3 if ttfts else float("nan")
        p90 = (statistics.quantiles(ttfts, n=10)[-1] * 1e3
               if len(ttfts) >= 10 else p50)
        row = {
            "concurrency": concurrency,
            "requests": requests,
            "req_per_s": round(requests / wall, 3),
            "p50_ttft_ms": round(p50, 2),
            "p90_ttft_ms": round(p90, 2),
            "p50_engine_ttft_ms": round(
                statistics.median(engine_ttfts) * 1e3, 2)
            if engine_ttfts else None,
            "p50_latency_ms": round(
                statistics.median(latencies) * 1e3, 2) if latencies else None,
            "gen_tokens_per_s": round(tokens / wall, 1),
            "prompt_tokens_total": prompt_tokens,
            # driver-process (proxy+router+client threads) CPU share of the
            # point's wall time: the "is the proxy eating the core?" number
            "proxy_cpu_share": round(proxy_cpu / wall, 3),
        }
        if label:
            row["label"] = label
        return row

    # TTFT-vs-throughput curve: light load -> saturation. The headline row
    # is the point the driver tracks (args.concurrency); the curve shows
    # what TTFT costs each throughput level (the reference's serve release
    # tests sweep operating points the same way).
    if args.curve:
        levels = sorted({max(1, args.concurrency // 8),
                         max(2, args.concurrency // 4),
                         max(4, args.concurrency // 2),
                         args.concurrency})
        points = [run_point(c, max(8, min(args.requests, c * 8)))
                  for c in levels]
        # long-prompt operating point: >=1024 prompt tokens exercises
        # chunked prefill + pressure decode blocks under measurement
        long_prompt = "the quick brown fox jumps over the lazy dog " * 128
        points.append(run_point(
            max(2, args.concurrency // 4), max(8, args.requests // 4),
            point_prompt=long_prompt, label="long_prompt_1024"))
    else:
        points = [run_point(args.concurrency, args.requests)]
    head = points[-2] if args.curve else points[-1]

    # metrics pipeline A/B (ISSUE 4): the headline point above ran with
    # every process flushing deltas to the CP store each second; rerun the
    # same point on a fresh cluster with the pipeline disabled and bound
    # the p50 TTFT overhead. Tolerance is noise-sized, not zero-sized:
    # cpu-tiny run-to-run variance dominates any real flusher cost.
    metrics_overhead = None
    if args.metrics_ab:
        serve.shutdown()
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=bench_cpus,
                     _system_config={"metrics_enabled": False})
        app = build_openai_app(llm_cfg, route_prefix="/v1")
        serve.run(app, name="llm-bench-nometrics", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        _post(base, {"prompt": prompt, "max_tokens": 4})
        _post_stream(base, {"prompt": prompt, "max_tokens": 4})
        off_row = run_point(args.concurrency, args.requests,
                            label="metrics_flusher_off")
        points.append(off_row)
        delta_ms = round(head["p50_ttft_ms"] - off_row["p50_ttft_ms"], 2)
        tol_ms = round(max(0.25 * off_row["p50_ttft_ms"], 30.0), 2)
        metrics_overhead = {
            "flusher_on": {k: head[k] for k in
                           ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                            "proxy_cpu_share")},
            "flusher_off": {k: off_row[k] for k in
                            ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                             "proxy_cpu_share")},
            "p50_delta_ms": delta_ms,
            "tolerance_ms": tol_ms,
            "within_noise": delta_ms <= tol_ms,
        }
        if not metrics_overhead["within_noise"]:
            print(json.dumps({"metrics_overhead": metrics_overhead}))
            raise SystemExit(
                f"metrics pipeline overhead out of bounds: p50 TTFT "
                f"+{delta_ms}ms with the flusher on (tolerance {tol_ms}ms)")

    # flight-recorder A/B (ISSUE 19): the headline point above ran with
    # the event journal on (emitters + batch flusher live); rerun the
    # same point on a fresh cluster with events_enabled=False and bound
    # the p50 TTFT overhead. Same noise-sized tolerance as the metrics
    # A/B — a healthy serving run emits a handful of events total, so
    # any measurable delta is a regression in the emit fast path.
    events_overhead = None
    if args.events_ab:
        serve.shutdown()
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=bench_cpus,
                     _system_config={"events_enabled": False})
        app = build_openai_app(llm_cfg, route_prefix="/v1")
        serve.run(app, name="llm-bench-noevents", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        _post(base, {"prompt": prompt, "max_tokens": 4})
        _post_stream(base, {"prompt": prompt, "max_tokens": 4})
        off_row = run_point(args.concurrency, args.requests,
                            label="events_journal_off")
        points.append(off_row)
        delta_ms = round(head["p50_ttft_ms"] - off_row["p50_ttft_ms"], 2)
        tol_ms = round(max(0.25 * off_row["p50_ttft_ms"], 30.0), 2)
        events_overhead = {
            "journal_on": {k: head[k] for k in
                           ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                            "proxy_cpu_share")},
            "journal_off": {k: off_row[k] for k in
                            ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                             "proxy_cpu_share")},
            "p50_delta_ms": delta_ms,
            "tolerance_ms": tol_ms,
            "within_noise": delta_ms <= tol_ms,
        }
        if not events_overhead["within_noise"]:
            print(json.dumps({"events_overhead": events_overhead}))
            raise SystemExit(
                f"event journal overhead out of bounds: p50 TTFT "
                f"+{delta_ms}ms with the journal on (tolerance {tol_ms}ms)")

    # phase-timer A/B (ISSUE 6): the headline point ran with the engine
    # profiler on (the default); redeploy the same engine with
    # profiling_enabled=False and bound the p50 TTFT cost of the timers.
    # Same noise-sized tolerance as the metrics A/B: on cpu-tiny the
    # run-to-run spread dwarfs a few perf_counter calls per loop pass.
    profiling_overhead = None
    if args.profile_ab:
        import dataclasses as _dc

        serve.shutdown()
        app = build_openai_app(
            _dc.replace(llm_cfg, profiling_enabled=False),
            route_prefix="/v1")
        serve.run(app, name="llm-bench-noprof", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        _post(base, {"prompt": prompt, "max_tokens": 4})
        _post_stream(base, {"prompt": prompt, "max_tokens": 4})
        off_row = run_point(args.concurrency, args.requests,
                            label="phase_timers_off")
        points.append(off_row)
        delta_ms = round(head["p50_ttft_ms"] - off_row["p50_ttft_ms"], 2)
        tol_ms = round(max(0.25 * off_row["p50_ttft_ms"], 30.0), 2)
        profiling_overhead = {
            "timers_on": {k: head[k] for k in
                          ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                           "proxy_cpu_share")},
            "timers_off": {k: off_row[k] for k in
                           ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                            "proxy_cpu_share")},
            "p50_delta_ms": delta_ms,
            "tolerance_ms": tol_ms,
            "within_noise": delta_ms <= tol_ms,
        }
        if not profiling_overhead["within_noise"]:
            print(json.dumps({"profiling_overhead": profiling_overhead}))
            raise SystemExit(
                f"phase-timer overhead out of bounds: p50 TTFT "
                f"+{delta_ms}ms with profiling on (tolerance {tol_ms}ms)")

    # SLO-attribution A/B (ISSUE 12): the headline point ran with the
    # per-request timeline stamping + exemplar shipping on (the default);
    # rerun it on a fresh cluster with slo_attribution_enabled=False and
    # bound the p50 TTFT cost of the stamping. Needs a full cluster
    # restart (system config is fixed at init), like the metrics A/B.
    # Same noise-sized tolerance: a handful of dict appends per request
    # is far under cpu-tiny run-to-run spread.
    slo_overhead = None
    if args.slo_ab:
        serve.shutdown()
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=bench_cpus,
                     _system_config={"slo_attribution_enabled": False})
        app = build_openai_app(llm_cfg, route_prefix="/v1")
        serve.run(app, name="llm-bench-noslo", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        _post(base, {"prompt": prompt, "max_tokens": 4})
        _post_stream(base, {"prompt": prompt, "max_tokens": 4})
        off_row = run_point(args.concurrency, args.requests,
                            label="slo_attribution_off")
        points.append(off_row)
        delta_ms = round(head["p50_ttft_ms"] - off_row["p50_ttft_ms"], 2)
        tol_ms = round(max(0.25 * off_row["p50_ttft_ms"], 30.0), 2)
        slo_overhead = {
            "attribution_on": {k: head[k] for k in
                               ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                                "proxy_cpu_share")},
            "attribution_off": {k: off_row[k] for k in
                                ("p50_ttft_ms", "p90_ttft_ms", "req_per_s",
                                 "proxy_cpu_share")},
            "p50_delta_ms": delta_ms,
            "tolerance_ms": tol_ms,
            "within_noise": delta_ms <= tol_ms,
        }
        if not slo_overhead["within_noise"]:
            print(json.dumps({"slo_overhead": slo_overhead}))
            raise SystemExit(
                f"SLO attribution overhead out of bounds: p50 TTFT "
                f"+{delta_ms}ms with stamping on (tolerance {tol_ms}ms)")

    # shared_prefix_1024: every request carries the same 1024-token prefix
    # (system prompt) plus a short unique suffix — the workload automatic
    # prefix caching exists for. Measured cache-on against the live app,
    # then cache-off on a redeployed replica (same sizing), hit rate from
    # the engine's prefix counters over the point's offered prompt tokens.
    prefix_cache = None
    if args.shared_prefix:
        import dataclasses as _dc

        stats_url = base.replace("/completions", "/stats")

        def _stats() -> dict:
            with urllib.request.urlopen(stats_url, timeout=60) as r:
                return json.loads(r.read())

        prefix_text = (
            "You are a helpful, terse assistant. Cite your sources. " * 32
        )[:1024]

        def _mk_prompt(i: int) -> str:
            return prefix_text + f" Q{i:05d}: summarize item {i}."

        sp_req = max(8, args.requests // 2)
        sp_conc = max(2, min(args.concurrency, 8))
        sp_tokens = min(32, args.max_tokens)

        def shared_point(label: str) -> dict:
            # warm: compile the long-prompt bucket, then (cache on) the
            # suffix-chunk program, seeding the prefix in the index
            _post_stream(base, {"prompt": _mk_prompt(90000), "max_tokens": 4})
            _post_stream(base, {"prompt": _mk_prompt(90001), "max_tokens": 4})
            s0 = _stats()
            row = run_point(sp_conc, sp_req, label=label,
                            prompt_fn=_mk_prompt, max_tokens=sp_tokens)
            s1 = _stats()
            hit_toks = (s1.get("prefix_hit_tokens", 0)
                        - s0.get("prefix_hit_tokens", 0))
            if row["prompt_tokens_total"]:
                row["cache_hit_rate"] = round(
                    hit_toks / row["prompt_tokens_total"], 3)
            row["prefix_hit_tokens"] = hit_toks
            row["prefix_evictions"] = s1.get("prefix_evictions", 0)
            return row

        on_row = shared_point("shared_prefix_1024_cache_on")
        points.append(on_row)

        # A/B: fresh replica with the cache disabled, same pool sizing
        serve.shutdown()
        app = build_openai_app(
            _dc.replace(llm_cfg, prefix_cache_enabled=False),
            route_prefix="/v1")
        serve.run(app, name="llm-bench-off", route_prefix="/v1")
        proxy = serve.start_http_proxy(port=0)
        base = f"http://127.0.0.1:{proxy.port}/v1/completions"
        stats_url = base.replace("/completions", "/stats")
        off_row = shared_point("shared_prefix_1024_cache_off")
        points.append(off_row)

        prefix_cache = {
            "label": "shared_prefix_1024",
            "prefix_tokens": len(prefix_text),
            "model": llm_cfg.model_id,
            "env": env_label,
            "cache_on": on_row,
            "cache_off": off_row,
            "cache_hit_rate": on_row.get("cache_hit_rate"),
            "ttft_speedup": round(
                off_row["p50_ttft_ms"] / on_row["p50_ttft_ms"], 2)
            if on_row["p50_ttft_ms"] else None,
        }

    # speculative decoding A/B (ISSUE 5): repetitive-suffix greedy
    # completions — the workload n-gram drafting exists for — against a
    # spec-on and a spec-off deployment of the same engine. Token identity
    # is a HARD assert: speculation must be a pure perf knob. On cpu-tiny
    # the point runs a deeper tiny model (dim 256, 4 layers) so a forward
    # pass is weights-bound like real serving; the default 2-layer dim-64
    # model is dispatch-bound on CPU, which hides the verify round's
    # extra-positions-are-nearly-free economics and makes any spec
    # measurement noise.
    spec_decode = None
    if args.spec_ab:
        import dataclasses as _dc

        if args.tiny:
            spec_cfg = LLMConfig(
                model_id="llama-tiny-d256",
                model_config=llama.llama_tiny(
                    vocab_size=2048, dim=256, n_layers=4, n_heads=8,
                    n_kv_heads=4, ffn_dim=1024),
                max_batch_size=8, page_size=32, num_pages=256,
                max_prompt_len=256, max_seq_len=512, max_tokens=64,
                warmup_compile=True, spec_draft_len=8)
        else:
            spec_cfg = _dc.replace(llm_cfg, spec_draft_len=8)
        # single-stream: speculative decoding is a LATENCY feature — it
        # spends extra FLOPs per pass to cut sequential passes, so its
        # home turf is the latency-bound low-concurrency regime (at high
        # batch the chip is already compute-saturated and the extra verify
        # positions just displace other slots' work)
        sp_req = max(3, min(args.requests, 4))
        sp_conc = 1
        sp_tokens = min(64, spec_cfg.max_tokens)

        def _spec_prompt(i: int) -> str:
            return "the cat sat on the mat. " * 6 + f"Q{i}: "

        def spec_arm(enabled: bool, attn: str = "auto") -> dict:
            serve.shutdown()
            tag = ("on" if enabled else "off") + \
                ("" if attn == "auto" else f"-{attn}")
            arm_app = build_openai_app(
                _dc.replace(spec_cfg, spec_decode_enabled=enabled,
                            attention_kernel=attn),
                route_prefix="/v1")
            serve.run(arm_app, name=f"llm-bench-spec-{tag}",
                      route_prefix="/v1")
            arm_proxy = serve.start_http_proxy(port=0)
            url = f"http://127.0.0.1:{arm_proxy.port}/v1/completions"
            surl = url.replace("/completions", "/stats")

            def _arm_stats() -> dict:
                with urllib.request.urlopen(surl, timeout=60) as r:
                    return json.loads(r.read())

            # warm: compile prefill buckets (decode + verify programs are
            # covered by warmup_compile at replica init)
            _post(url, {"prompt": _spec_prompt(0), "max_tokens": 4,
                        "temperature": 0.0})
            s0 = _arm_stats()

            def one(i: int) -> dict:
                return _post(url, {"prompt": _spec_prompt(i),
                                   "max_tokens": sp_tokens,
                                   "temperature": 0.0})

            t0 = time.monotonic()
            with concurrent.futures.ThreadPoolExecutor(sp_conc) as pool:
                outs = list(pool.map(one, range(sp_req)))
            wall = time.monotonic() - t0
            s1 = _arm_stats()
            row = {
                "label": f"spec_{tag}",
                "requests": sp_req, "concurrency": sp_conc,
                "max_tokens": sp_tokens,
                "gen_tokens_per_s": round(sum(
                    o["usage"]["completion_tokens"] for o in outs) / wall, 1),
                # per-request (text, n_tokens): the identity fingerprint
                "completions": [(o["choices"][0]["text"],
                                 o["usage"]["completion_tokens"])
                                for o in outs],
            }
            for key in ("spec_rounds", "spec_drafted_tokens",
                        "spec_accepted_tokens"):
                row[key] = s1.get(key, 0) - s0.get(key, 0)
            return row

        off_row = spec_arm(False)
        on_row = spec_arm(True)
        identical = off_row["completions"] == on_row["completions"]
        rounds = on_row["spec_rounds"]
        spec_decode = {
            "label": "spec_repetitive_suffix",
            "model": spec_cfg.model_id,
            "env": env_label,
            "draft_len": spec_cfg.spec_draft_len,
            "greedy_identical": identical,
            "spec_rounds": rounds,
            # the headline acceptance number: mean accepted DRAFT tokens
            # per verify round (each round additionally emits one
            # verified bonus token on top of these)
            "accepted_per_round": round(
                on_row["spec_accepted_tokens"] / rounds, 2) if rounds
            else 0.0,
            "gen_tokens_per_s_on": on_row["gen_tokens_per_s"],
            "gen_tokens_per_s_off": off_row["gen_tokens_per_s"],
            "speedup": round(on_row["gen_tokens_per_s"]
                             / off_row["gen_tokens_per_s"], 2)
            if off_row["gen_tokens_per_s"] else None,
        }
        # fused-kernel identity leg (ISSUE 18): on a TPU whose shapes the
        # kernel tiling accepts, re-run the spec-on arm under BOTH
        # attention backends and hard-assert greedy identity — decode AND
        # multi-query verify both go through the pallas kernels here.
        # Elsewhere the interpret-mode equivalent already ran in the
        # tests/test_paged_kernels.py preflight, so the slow duplicate is
        # skipped and recorded as such.
        from ray_tpu.serve.llm import kv_cache as _kvc
        if not args.tiny and _kvc.resolve_attention_backend(
                "auto", spec_cfg.llama(), spec_cfg.page_size) == "pallas":
            g_row = spec_arm(True, attn="gather")
            p_row = spec_arm(True, attn="pallas")
            kernels_identical = \
                g_row["completions"] == p_row["completions"]
            spec_decode["attention_kernel_leg"] = {
                "greedy_identical": kernels_identical,
                "gen_tokens_per_s_gather": g_row["gen_tokens_per_s"],
                "gen_tokens_per_s_pallas": p_row["gen_tokens_per_s"],
            }
            if not kernels_identical:
                print(json.dumps({"spec_decode": spec_decode}))
                raise SystemExit(
                    "pallas attention backend changed greedy output vs "
                    "gather under speculative decoding — kernel identity "
                    "contract broken, not benchmarking it")
        else:
            spec_decode["attention_kernel_leg"] = {
                "skipped": "no TPU-tileable shapes here; interpret-mode "
                           "identity covered by tests/test_paged_kernels.py"}
        for row in (off_row, on_row):
            row.pop("completions")
            points.append(row)
        if not identical:
            print(json.dumps({"spec_decode": spec_decode}))
            raise SystemExit(
                "speculative decoding changed greedy output: spec-on and "
                "spec-off completions differ — the accept/rollback path is "
                "broken, not benchmarking it")

    # tiered-KV-cache A/B (ISSUE 7, codec arms ISSUE 15): shared-prefix
    # greedy completions against a tier-off control (cold-prefill TTFT)
    # and, per codec arm, a tier-on replica A that seeds and spills the
    # prefix chains plus a COLD tier-on replica B that has never seen the
    # prompts and must STREAM A's spilled pages back through the CP index
    # + object plane. Arms: "none" (the PR 7 raw wire format), "lossless"
    # (identity is a HARD assert), "int8" (identity NOT asserted —
    # divergence recorded; its ratio is the >=3x capacity claim).
    # Runs the deeper cpu-tiny model (like --spec-ab) so prefill is
    # weights-bound and the restored-scatter-vs-recompute delta is real.
    kv_tier = None
    if args.kv_tier_ab:
        import dataclasses as _dc

        from ray_tpu.serve.llm import LLMEngine

        kvt_cfg = LLMConfig(
            model_id="llama-tiny-d256",
            model_config=llama.llama_tiny(
                vocab_size=2048, dim=256, n_layers=4, n_heads=8,
                n_kv_heads=4, ffn_dim=1024),
            max_batch_size=4, page_size=32, num_pages=128,
            max_prompt_len=704, max_seq_len=768, max_tokens=16,
            warmup_compile=True,
            # small retention cap: drained prefix chains spill promptly
            # instead of parking in the local LRU forever
            prefix_cache_max_pages=2, kv_tier_enabled=True)
        shared = "shared context " * 40             # 600 tokens ~ 18 pages
        kv_prompts = [shared + f"Q{i}: " for i in range(4)]

        def kvt_run(eng) -> tuple[list, list, list]:
            ttfts, comps, restores = [], [], []
            for p in kv_prompts:
                out = eng.generate(p, max_tokens=16, temperature=0.0)
                if out["error"]:
                    raise SystemExit(f"kv-tier A/B request failed: "
                                     f"{out['error']}")
                ttfts.append(out["ttft_s"])
                comps.append((out["text"], len(out["tokens"])))
                restores += [s["attrs"] for s in out.get("stages") or ()
                             if s["stage"] == "restore"]
            return ttfts, comps, restores

        def kvt_pair(codec: str, attn: str = "auto") -> dict:
            """One seeding replica A + one cold restoring replica B under
            ``codec``; A stays alive while B restores (its shutdown
            retracts the index entries and drops the blobs B streams)."""
            cfg = _dc.replace(kvt_cfg, kv_tier_codec=codec,
                              attention_kernel=attn)
            a_eng = LLMEngine(cfg, rng_seed=0)
            a_eng.start()
            b_eng = None
            try:
                _a_ttfts, a_comps, _ = kvt_run(a_eng)
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline and \
                        a_eng.engine_stats()["spilled_pages"] < 1:
                    time.sleep(0.05)
                a_st = a_eng.engine_stats()
                if a_st["spilled_pages"] < 1:
                    raise SystemExit(
                        f"kv-tier A/B [{codec}]: replica A spilled "
                        f"nothing — eviction->spill path inert, not "
                        f"benchmarking it")
                b_eng = LLMEngine(cfg, rng_seed=0)
                b_eng.start()
                b_ttfts, b_comps, b_restores = kvt_run(b_eng)
                b_st = b_eng.engine_stats()
            finally:
                a_eng.shutdown()
                if b_eng is not None:
                    b_eng.shutdown()
            if b_st["restored_pages"] < 1:
                raise SystemExit(
                    f"kv-tier A/B [{codec}]: cold replica B restored "
                    f"nothing — the CP index/object-plane path is inert, "
                    f"not benchmarking it")
            p50_warm = statistics.median(b_ttfts) * 1e3
            # restore-stall breakdown from B's attribution stages: wall
            # restore time, how much of it overlapped other work instead
            # of blocking the loop, codec decode cost, encoded wire bytes
            n_r = max(1, len(b_restores))
            return {
                "codec": codec,
                "a_completions": a_comps, "b_completions": b_comps,
                "spilled_pages_a": a_st["spilled_pages"],
                "codec_ratio_a": a_st["tier_codec_ratio"],
                "encode_ms_p50_a": a_st["tier_encode_ms_p50"],
                "restored_pages_b": b_st["restored_pages"],
                "restore_partial_b": b_st["restore_partial"],
                "tier_hit_tokens_b": b_st["tier_hit_tokens"],
                "decode_ms_p50_b": b_st["tier_decode_ms_p50"],
                "p50_ttft_warm_b_ms": round(p50_warm, 2),
                "restore_ms_mean": round(sum(
                    r["restore_ms"] for r in b_restores) / n_r, 2),
                "overlap_ms_mean": round(sum(
                    r["overlap_ms"] for r in b_restores) / n_r, 2),
                "decode_ms_mean": round(sum(
                    r["decode_ms"] for r in b_restores) / n_r, 2),
                "bytes_wire_b": sum(r["bytes_wire"] for r in b_restores),
                "bytes_raw_b": sum(r["restore_bytes"]
                                   for r in b_restores),
            }

        cold_eng = LLMEngine(_dc.replace(kvt_cfg, kv_tier_enabled=False,
                                         prefix_cache_enabled=False),
                             rng_seed=0)
        cold_eng.start()
        try:
            cold_ttfts, want, _ = kvt_run(cold_eng)
        finally:
            cold_eng.shutdown()

        arms = {c: kvt_pair(c) for c in ("none", "lossless", "int8")}
        lossless, raw, int8 = arms["lossless"], arms["none"], arms["int8"]
        identical = want == lossless["a_completions"] \
            == lossless["b_completions"]
        raw_identical = want == raw["a_completions"] == raw["b_completions"]
        int8_diverged = sum(1 for w, got in zip(want, int8["b_completions"])
                            if got != w)
        p50_cold = statistics.median(cold_ttfts) * 1e3
        p50_warm = lossless["p50_ttft_warm_b_ms"]
        # fused-kernel identity leg (ISSUE 18): a cold replica restoring
        # spilled pages and decoding through the pallas kernels must
        # reproduce the gather tokens exactly. Only meaningful where the
        # TPU kernel tiling accepts this arm's model; elsewhere the
        # interpret-mode equivalent ran in the tests/test_paged_kernels.py
        # preflight.
        from ray_tpu.serve.llm import kv_cache as _kvc
        if not args.tiny and _kvc.resolve_attention_backend(
                "auto", kvt_cfg.llama(), kvt_cfg.page_size) == "pallas":
            pal = kvt_pair("lossless", attn="pallas")
            pallas_leg = {
                "greedy_identical": want == pal["b_completions"],
                "p50_ttft_warm_b_ms": pal["p50_ttft_warm_b_ms"],
                "restored_pages_b": pal["restored_pages_b"],
            }
            if not pallas_leg["greedy_identical"]:
                raise SystemExit(
                    "pallas attention backend changed greedy output vs "
                    "the cold gather control after a tier restore — "
                    "kernel identity contract broken, not benchmarking it")
        else:
            pallas_leg = {
                "skipped": "no TPU-tileable shapes here; interpret-mode "
                           "identity covered by tests/test_paged_kernels.py"}
        for arm in arms.values():
            arm.pop("a_completions")
            arm.pop("b_completions")
        kv_tier = {
            "label": "kv_tier_cross_replica",
            "model": kvt_cfg.model_id,
            "env": env_label,
            "requests": len(kv_prompts),
            "shared_prefix_tokens": len(shared),
            "greedy_identical": identical,
            "int8_diverged_completions": int8_diverged,
            "p50_ttft_cold_ms": round(p50_cold, 2),
            "p50_ttft_warm_b_ms": p50_warm,
            "ttft_speedup": round(p50_cold / p50_warm, 2)
            if p50_warm else None,
            "ttft_vs_raw": round(
                p50_warm / raw["p50_ttft_warm_b_ms"], 3)
            if raw["p50_ttft_warm_b_ms"] else None,
            "attention_kernel_leg": pallas_leg,
            "codec_arms": arms,
        }
        if not (identical and raw_identical):
            print(json.dumps({"kv_tier": kv_tier}))
            raise SystemExit(
                "kv-tier restore changed greedy output: tier-restored "
                "completions differ from cold prefill — the spill/restore "
                "path is corrupting KV, not benchmarking it")
        if int8["codec_ratio_a"] < 3.0:
            print(json.dumps({"kv_tier": kv_tier}))
            raise SystemExit(
                f"kv-tier A/B: int8 codec ratio "
                f"{int8['codec_ratio_a']}x < 3x on the tiny-model tier — "
                f"the quantized width cut is not reaching the stored "
                f"bytes")

    serve.shutdown()

    result = {
        "metric": "serve_p50_ttft_ms",
        "value": head["p50_ttft_ms"],
        "unit": "ms",
        "vs_baseline": None,  # reference publishes no number (BASELINE.md)
        "extra": {
            **{k: v for k, v in head.items() if k != "p50_ttft_ms"},
            "max_tokens": args.max_tokens,
            "model": llm_cfg.model_id,
            "operating_points": points,
        },
    }
    if metrics_overhead is not None:
        result["extra"]["metrics_overhead"] = metrics_overhead
    if profiling_overhead is not None:
        result["extra"]["profiling_overhead"] = profiling_overhead
    if slo_overhead is not None:
        result["extra"]["slo_overhead"] = slo_overhead
    if events_overhead is not None:
        result["extra"]["events"] = events_overhead
    # events rides the file merge too: `--events-ab` alone must land in
    # SERVE_BENCH.json extra.events without clobbering earlier rows
    mergeable = {"prefix_cache": prefix_cache, "spec_decode": spec_decode,
                 "kv_tier": kv_tier, "events": events_overhead}
    mergeable = {k: v for k, v in mergeable.items() if v is not None}
    if mergeable:
        result["extra"].update(mergeable)
        # merge into --out WITHOUT clobbering earlier headline rows (e.g.
        # a TPU curve recorded by a previous run)
        import os
        merged = result
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    merged = json.load(f)
                merged.setdefault("extra", {}).update(mergeable)
            except ValueError:
                merged = result
        with open(args.out, "w") as f:
            json.dump(merged, f)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
