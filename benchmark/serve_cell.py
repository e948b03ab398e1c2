"""One run of a serve cell through the entry points users call:
ray_tpu.init -> serve.run(build_openai_app(LLMConfig)) -> HTTP proxy ->
/v1/completions with stream=true. This process never initialises a jax
backend; the replica's worker holds the chip, and the checks child holds
it after the replica has gone.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time

from benchmark import checks, common, http_client

HOST = "127.0.0.1"


def _engine_config(config: dict, rehearsal: bool, chips: int):
    """(sizes, engine section, LLMConfig). The model is the family's, the
    engine's layout the file's: every field of LLMConfig the harness sets
    comes from the configuration's ``engine`` section. A cell runs one
    replica, so a replica that spans another number of chips than the
    cell's is refused here, before any process starts."""
    from ray_tpu.serve.llm import LLMConfig
    fam = common.family(config)
    sz = fam.sizes(config, rehearsal)
    eng = common.section(config, "engine", rehearsal)
    tp = eng.get("tp_degree", 1)
    if tp != chips:
        raise common.BenchError(f"the configuration's engine spans "
                                f"tp_degree={tp} chip(s), the cell asks "
                                f"for {chips}")
    return sz, eng, LLMConfig(
        model_config=fam.model_config(sz),
        ray_actor_options={"resources": {"TPU": chips}}, **eng)


def _warm_up(port: int, traffic: dict, eng: dict, report: dict) -> None:
    """One request for every prefill program the cell's file allows (the
    engine compiles those on first use; decode programs it warms itself)."""
    lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    prompts = common.warm_prompts(lo, hi, eng)
    report["warm_prompt_lengths"] = [n for n, _text in prompts]
    for i, (n, text) in enumerate(prompts):
        rec = http_client.stream_completion(
            HOST, port, {"index": -1 - i, "prompt_tokens": n, "max_tokens": 2,
                         "prompt": text}, time.monotonic())
        if rec["error"] or rec["done"] is None:
            raise common.BenchError(f"warm-up request of {n} tokens failed: "
                                    f"{rec['error']}")


class _Watch(threading.Thread):
    """Reads /v1/stats at the window's edges and once a second between,
    and brackets the traced part of a --trace 1 run."""

    def __init__(self, port, t0, seconds, trace_dir, trace_s):
        super().__init__(daemon=True)
        self.port, self.t0, self.t1 = port, t0, t0 + seconds
        self.trace_dir, self.trace_s = trace_dir, trace_s
        self.before = self.after = None
        self.samples: list[tuple[float, dict]] = []
        self.trace = {}
        self.error = None

    def _stats(self):
        return http_client.get_json(HOST, self.port, "/v1/stats")

    def run(self):
        try:
            self._run()
        except Exception as e:  # noqa: BLE001 - reported by the parent
            self.error = repr(e)

    def _run(self):
        from ray_tpu.util import state
        time.sleep(max(0.0, self.t0 - time.monotonic()))
        self.before = self._stats()
        trace_at = self.t0 + 0.4 * (self.t1 - self.t0) if self.trace_dir \
            else None
        tracing = False
        while True:
            now = time.monotonic()
            if trace_at is not None and not tracing and now >= trace_at:
                self.trace["start"] = state.profiling_start(
                    logdir=self.trace_dir)
                self.trace["stats_start"] = self._stats()
                self.trace["t_start"] = time.monotonic()
                tracing = True
            if tracing and now >= self.trace["t_start"] + self.trace_s:
                self._stop_trace(state)
                tracing, trace_at = False, None
            if now >= self.t1:
                break
            self.samples.append((now - self.t0, self._stats()))
            time.sleep(min(1.0, max(0.0, self.t1 - time.monotonic())))
        if tracing:
            self._stop_trace(state)
        self.after = self._stats()

    def _stop_trace(self, state):
        self.trace["t_stop"] = time.monotonic()
        self.trace["stats_stop"] = self._stats()
        self.trace["stop"] = state.profiling_stop()


class _Samples(threading.Thread):
    """Check 2's requests: sent when the window closes, while its load is
    still on the engine (the open loop's last requests are decoding; the
    closed loop keeps going through its cool-down), so they are prefilled
    and decoded beside other slots' work. They go through the deployment
    handle, which (unlike the HTTP proxy) returns token ids. Every seed
    sends the same lengths: the stratified quantiles of the cell's prompt
    distribution, the median of its outputs; the text is the seed's."""

    def __init__(self, handle, traffic: dict, seed: int, n: int, at: float):
        super().__init__(daemon=True)
        from benchmark.traffic import lengths
        self.handle, self.at = handle, at
        rng = random.Random(seed ^ 0xC0FFEE)
        self.reqs = [
            {"prompt": lengths.prompt_text(rng, p),
             "max_tokens": int(traffic["output_tokens"]["median"])}
            for p in lengths.lognormal_lengths(traffic["prompt_tokens"], n)]
        self.out: list = [None] * n
        self.seconds = None

    def _one(self, i: int, r: dict) -> None:
        try:
            res = self.handle.generate.remote(
                r["prompt"], max_tokens=r["max_tokens"],
                temperature=0.0).result(timeout_s=180.0)
        except Exception as e:  # noqa: BLE001 - reported by the parent
            res = {"error": repr(e)}
        self.out[i] = {"prompt_ids": common.byte_encode(r["prompt"]),
                       "tokens": [int(t) for t in res.get("tokens") or []],
                       "max_tokens": r["max_tokens"],
                       "error": res.get("error")}

    def run(self):
        time.sleep(max(0.0, self.at - time.monotonic()))
        t = time.monotonic()
        threads = [threading.Thread(target=self._one, args=(i, r), daemon=True)
                   for i, r in enumerate(self.reqs)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(200.0)
        self.seconds = time.monotonic() - t

    def result(self) -> list[dict]:
        self.join(300.0)
        if self.is_alive() or any(s is None or s["error"] for s in self.out):
            raise common.BenchError(f"served sample failed: {self.out}")
        return self.out


def run_checks_child(spec: dict, out_dir: str, limit_s: float = 600.0) -> dict:
    from ray_tpu.core import compile_cache
    env = dict(os.environ)
    compile_cache.configure(env)
    spec_path = os.path.join(out_dir, "checks_spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    err_path = os.path.join(out_dir, "checks.err")
    with open(err_path, "wb") as ferr:
        proc = subprocess.run(
            [sys.executable, os.path.join(common.HERE, "checks.py"), spec_path],
            env=env, cwd=common.ROOT, stdout=subprocess.PIPE, stderr=ferr,
            timeout=limit_s)
    lines = proc.stdout.decode("utf-8", "replace").strip().splitlines()
    if proc.returncode != 0 or not lines:
        with open(err_path, "rb") as f:
            tail = f.read()[-3000:].decode("utf-8", "replace")
        raise common.BenchError(
            f"checks child exited {proc.returncode}:\n{tail}")
    return json.loads(lines[-1])


def run(entry: dict, cell: dict, config: dict, args, t_process: float) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.core import compile_cache
    from ray_tpu.serve.llm import build_openai_app

    rehearsal, chips = args.rehearsal, entry["chips"]
    traffic = (cell["rehearsal"] if rehearsal else cell)["traffic"]
    trace_s = (cell["rehearsal"] if rehearsal else cell).get("trace_seconds", 5)
    gen = common.load_module("traffic", traffic["generator"])
    plan = gen.plan(traffic, args.seed, args.seconds)
    out_dir = os.path.join(common.ROOT, ".bench_out", entry["name"])
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    report: dict = {"phases_s": {}, "rehearsal": rehearsal}
    phases = report["phases_s"]

    compile_cache.configure()
    sz, eng, llm_cfg = _engine_config(config, rehearsal, chips)
    t = time.monotonic()
    ray_tpu.init(num_cpus=max(8, os.cpu_count() or 1),
                 resources={"TPU": chips} if rehearsal else None)
    try:
        found = sum(n.get("resources", {}).get("TPU", 0)
                    for n in ray_tpu.nodes())
        if found < chips:
            raise common.BenchError(f"the node has {found} TPU chip(s), "
                                    f"the cell needs {chips}")
        handle = serve.run(build_openai_app(llm_cfg, route_prefix="/v1"),
                           name=entry["name"], route_prefix="/v1")
        port = serve.start_http_proxy(port=0).port
        phases["replica_ready"] = time.monotonic() - t

        stats = http_client.get_json(HOST, port, "/v1/stats")
        device = {"platform": stats["device_platform"],
                  "kind": stats["device_kind"],
                  "count": stats["device_count"]}
        if not rehearsal and (device["platform"] != "tpu"
                              or device["count"] != chips
                              or stats["attention_backend"] != "pallas"
                              or stats["attn_interpret"] != 0):
            print(f"benchmark: the replica runs on {device}, attention "
                  f"{stats['attention_backend']!r} interpret="
                  f"{stats['attn_interpret']}", file=sys.stderr)
            raise SystemExit(3)

        t = time.monotonic()
        _warm_up(port, traffic, eng, report)
        phases["warm_up_requests"] = time.monotonic() - t

        def send(req, due, stop_at=None):
            return http_client.stream_completion(HOST, port, req, due, stop_at)

        t0 = time.monotonic() + plan.get("ramp_s", 0.0) + 0.05
        setup_s = (time.time() - t_process) + (t0 - time.monotonic())
        watch = _Watch(port, t0, args.seconds, trace_dir, trace_s)
        watch.start()
        sampler = _Samples(
            handle, traffic, args.seed,
            common.section(config, "checks", rehearsal)[
                "served_tokens"]["sample"], t0 + args.seconds)
        sampler.start()
        records = gen.drive(plan, send, t0, args.seconds)
        watch.join(args.seconds + 120.0)
        if watch.error or watch.after is None:
            raise common.BenchError(f"stats watcher failed: {watch.error}")
        phases["window_and_drain"] = time.monotonic() - t0
        samples = sampler.result()
        phases["served_samples_after_drain"] = time.monotonic() - t0 \
            - phases["window_and_drain"]
        phases["served_samples"] = sampler.seconds
        stats_end = http_client.get_json(HOST, port, "/v1/stats")
    finally:
        t = time.monotonic()
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
        phases["shutdown"] = time.monotonic() - t
    phases["children_gone"] = common.wait_children_gone(30.0)
    if not rehearsal:
        phases["chips_free_after_replica"] = common.wait_chips_free(30.0)

    t = time.monotonic()
    child = run_checks_child({
        "rehearsal": rehearsal, "chips": chips, "seed": args.seed,
        "family": config["model_family"], "sizes": sz, "engine": eng, "samples": samples,
        "shape": {"width": traffic["prompt_tokens"]["max"],
                  "out_width": traffic["output_tokens"]["max"] + 1},
        "checks": common.section(config, "checks", rehearsal)}, out_dir)
    phases["checks_child"] = time.monotonic() - t
    structure = checks.structure_check(
        [r for r in records if r.get("done") is not None and not r["error"]],
        samples, sz["vocab_size"])

    return {
        "kind": "serve", "records": records, "plan_mode": plan["mode"],
        "window": {"t0": t0, "t1": t0 + args.seconds, "seconds": args.seconds},
        "setup_s": setup_s, "stats_before": watch.before,
        "stats_after": watch.after, "stats_samples": watch.samples,
        "stats_end": stats_end, "engine": eng, "sizes": sz,
        "trace_dir": trace_dir, "trace_marks": {
            k: watch.trace.get(k) for k in ("t_start", "t_stop", "stats_start",
                                            "stats_stop")},
        "device": {**device, "memory_peak_bytes":
                   int(stats_end.get("device_peak_bytes") or 0)},
        "checks": {"logits": child["logits"],
                   "served_tokens": child["served_tokens"],
                   "structure": structure},
        "child": {k: child[k] for k in ("device", "interpret", "logits_s",
                                        "served_weights_s", "served_tokens_s")},
        "report": report,
        "extra": _extra(records, watch, t0, args.seconds),
    }


def _extra(records, watch, t0, seconds) -> dict:
    """What is worth keeping beside the metrics: the backlog through the
    window (is the rate sustained?), how late the generator ran, medians,
    and the tails of a cell that is not judged by them."""
    waiting = [s.get("waiting", 0) for _t, s in watch.samples]
    half = len(waiting) // 2
    done = [r for r in records if r.get("done") is not None]
    late = [(r["sent"] - r["due"]) * 1e3 for r in records if "sent" in r]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in records
            if r.get("first") is not None]
    mean = lambda v: sum(v) / len(v) if v else None  # noqa: E731
    return {
        "requests": len(records), "completed": len(done),
        "abandoned": sum(1 for r in records if r.get("abandoned")),
        # streams cut at the end of the cool-down that had begun inside
        # the window: counted by serve_tokens_per_s as max_tokens
        "window_streams_cut": sum(
            1 for r in records if r.get("abandoned")
            and r.get("first") is not None and r["first"] <= t0 + seconds),
        "tokens_completed": sum(r["completion_tokens"] for r in done),
        "waiting_first_half": mean(waiting[:half]),
        "waiting_second_half": mean(waiting[half:]),
        "waiting_end": waiting[-1] if waiting else None,
        "waiting_max": max(waiting, default=None),
        "active_slots_mean": mean([s.get("active_slots", 0)
                                   for _t, s in watch.samples]),
        "drain_s": max((r["done"] for r in done), default=t0 + seconds)
        - (t0 + seconds),
        "generator_lateness_ms": {
            "p50": common.percentile(late, 50) if late else None,
            "max": max(late, default=None)},
        "ttft_ms": {"p50": common.percentile(ttft, 50) if ttft else None,
                    "p90": common.percentile(ttft, 90) if ttft else None,
                    "max": max(ttft, default=None), "n": len(ttft)},
        "stats_window": {k: watch.after.get(k, 0) - watch.before.get(k, 0)
                         for k in ("steps", "tokens_out", "prefills",
                                   "requests", "mid_traffic_compiles",
                                   "compile_events", "prefix_hit_tokens",
                                   "attn_decode_dispatches",
                                   "attn_chunk_dispatches")},
        "stats_end": {k: watch.after.get(k) for k in (
            "compile_s", "compile_events", "weights_bytes", "kv_pool_bytes",
            "device_bytes_in_use", "device_peak_bytes",
            "phase_queue_wait_p95_ms", "phase_harvest_p50_ms",
            "phase_decode_dispatch_p50_ms", "phase_prefill_p50_ms")},
    }
