"""LLMServer: the serve deployment wrapping the continuous-batching engine.

Matches the reference's LLMServer deployment
(python/ray/llm/_internal/serve/deployments/llm/llm_server.py): one engine
per replica, requests routed by serve's pow-2 router, OpenAI-shaped request
and response dicts. Streaming uses generator endpoints (drained through the
engine's per-request token queues).
"""

from __future__ import annotations

import time
import uuid
from typing import Any, Iterator, Optional

from ray_tpu.observability import profiling
from ray_tpu.serve.llm.config import LLMConfig
from ray_tpu.serve.llm.engine import LLMEngine

# Engine stats as one tagged gauge family through the util.metrics
# registry + flusher pipeline (delta reports into the CP time-series
# store — the legacy `metrics:<worker>` KV blob path is gone).
# Module-level singleton: the metrics registry is per-process and a
# replica restart in the same worker must not register a duplicate
# family. Phase/compile/ITL histograms are NOT re-exported here — the
# engine's profiler records those into their own metric families
# (observability/profiling.py); this family carries the scalar
# counters/gauges, including the profiler-derived scalars below.
_ENGINE_GAUGE = None
_EXPORTED_STATS = (
    "steps", "prefills", "tokens_out", "requests", "shed_expired",
    "active_slots", "waiting", "prefilling", "free_pages",
    "prefix_hits", "prefix_misses", "prefix_hit_tokens",
    "prefix_hit_pages", "prefix_cached_pages", "prefix_evictable_pages",
    "prefix_shared_pages", "prefix_evictions", "prefix_inserted_pages",
    "decode_block_effective", "pending_pipeline_depth",
    # the tier that chose each decode block's k, and the idle tier's own
    # (lead.py: how far the loop runs ahead of the device with no queue)
    "dispatch_tier_admit_total", "dispatch_tier_pressure_total",
    "dispatch_tier_idle_total", "idle_lead_k", "lead_climbs_total",
    "lead_descents_total",
    # tiered KV cache (ISSUE 7): spill/restore economy + per-tier bytes
    "spilled_pages", "restored_pages", "tier_hit_tokens",
    "tier_bytes_shm", "tier_bytes_disk",
    # prefix-affinity routing (ISSUE 10): tier-hint prefetch economy +
    # the summary the router sees (version/pages exported to the CP)
    "tier_prefetch_hints", "tier_prefetch_pages", "tier_prefetch_hit_pages",
    "prefix_summary_version", "prefix_summary_pages",
    "spec_rounds", "spec_drafted_tokens", "spec_accepted_tokens",
    # mid-stream failover (ISSUE 14): continuations admitted + tokens of
    # dead-replica work recovered without recompute (prefix + tier pages)
    "failover_resumed", "failover_restored_tokens",
    # fleet disagg (ISSUE 16): remote-prefill handoffs restored here +
    # their encoded wire bytes and decode-overlapped restore milliseconds
    "disagg_prefills", "handoff_bytes_wire", "handoff_overlap_ms",
    # elastic fleet (ISSUE 17): cache-warm scale-up restore economy
    "warm_start_pages", "warm_start_ms",
    # paged-attention kernel family (ISSUE 18): resolved backend (string
    # — exported as a one-hot stat tag; numeric twin alongside) + per-
    # kernel compile/dispatch counters, so a fleet mixing gather/pallas
    # replicas is visible in `ray-tpu` status and on the dashboard
    "attention_backend", "attn_backend_pallas", "attn_kernel_compiles",
    "attn_decode_dispatches", "attn_verify_dispatches",
    "attn_chunk_dispatches",
    # a program's tail does only what is used (ISSUE 56): chunks that ran
    # no head, decode dispatches that drew nothing
    "chunk_heads_skipped", "greedy_dispatches",
    # the device the engine ran on, as jax reports it (strings one-hot
    # like attention_backend), and whether the pallas kernels are being
    # interpreted rather than compiled
    "device_platform", "device_kind", "device_count", "attn_interpret",
    # tensor parallelism (ISSUE 20): sharding degree + mesh shape (string
    # — one-hot export like attention_backend) and one chip's slice of
    # the KV pool in bytes (page counts elsewhere stay whole-replica)
    "tp_degree", "mesh_shape", "kv_shard_pool_bytes",
    "kv_shard_page_occupancy",
    # introspection scalars (ISSUE 6): compile tracker + memory gauges;
    # None-valued entries (no samples yet / cpu backend) are skipped
    "compile_events", "mid_traffic_compiles", "compile_s",
    # the start-up ledger's flat totals (never the nested ``startup``)
    *profiling.STARTUP_TOTALS,
    "weights_bytes", "kv_pool_bytes", "kv_page_occupancy",
    "device_bytes_in_use", "device_peak_bytes", "itl_s",
    # stalls of the loop's host (ISSUE 39): spans of host work past 50 ms,
    # the process's garbage collector, dispatches that found the device
    # with nothing queued (dry_s_total: an upper bound of the idle)
    "host_stall_s_total", "host_stall_n", "gc_pause_s_total", "gc_pause_n",
    "gc_pause_max_ms", "gc_young_s_total", "gc_young_n",
    "dry_dispatches_total", "dry_s_total")


def _export_engine_stats(model_id: str, stats: dict) -> None:
    """Record engine counters as registry gauges and flush (best-effort:
    benches/tests run engines with no runtime up)."""
    global _ENGINE_GAUGE
    try:
        from ray_tpu.core import api
        from ray_tpu.util import metrics
        if _ENGINE_GAUGE is None:
            _ENGINE_GAUGE = metrics.Gauge(
                "ray_tpu_llm_engine",
                "LLM engine counters (incl. prefix-cache hit/miss/evict)",
                tag_keys=("model", "replica", "stat"))
        rt = api._try_get_runtime()
        replica = rt.worker_id.hex()[:8] if rt is not None else "local"
        for key in _EXPORTED_STATS:
            val = stats.get(key)
            if val is None:
                continue
            if isinstance(val, str):
                # string-valued stats (attention_backend) export as a
                # one-hot gauge keyed "stat:value" — a float() here would
                # raise and silently drop every later key's export
                _ENGINE_GAUGE.set(
                    1.0, tags={"model": model_id, "replica": replica,
                               "stat": f"{key}:{val}"})
                continue
            _ENGINE_GAUGE.set(
                float(val),
                tags={"model": model_id, "replica": replica,
                      "stat": key})
        # immediate flush (not the 10s interval): dashboards scrape engine
        # gauges right after probing stats, so they must be current
        metrics.flush_now()
    except Exception:  # noqa: BLE001 — observability must not fail serving
        pass


def _resume_plan(resume_tokens, resume_count, cfg: LLMConfig):
    """Decide how a re-dispatched stream resumes: `(use_continuation,
    skip)`. Continuation admits prompt+resume through the cache-aware
    path and emits only new tokens. Past `failover_max_resumes` (or with
    failover off) the request degrades to a plain retry-from-scratch:
    regenerate everything and suppress the first `skip` tokens so the
    spliced client stream still carries no duplicates (greedy regenerates
    the identical prefix)."""
    n = len(resume_tokens or ())
    if not n:
        return False, 0
    if cfg.failover_enabled and int(resume_count or 0) <= \
            cfg.failover_max_resumes:
        return True, 0
    return False, n


def _chat_prompt(messages: list[dict]) -> str:
    """Minimal chat template (role-tagged concatenation)."""
    parts = []
    for m in messages:
        parts.append(f"<|{m.get('role', 'user')}|>{m.get('content', '')}")
    parts.append("<|assistant|>")
    return "".join(parts)


class LLMServer:
    """Deployment callable. Each replica owns one engine (and therefore the
    TPU chips of its placement bundle — one engine process per chip group,
    SURVEY.md §7 hard-part 7)."""

    def __init__(self, llm_config: LLMConfig | dict):
        # the process's start-up ledger: a worker has waited for this
        # constructor since it registered (core/worker_main.py)
        startup = profiling.startup()
        startup.stamp_since_last("actor_wait", after="worker_boot")
        if isinstance(llm_config, dict):
            llm_config = LLMConfig(**llm_config)
        self.cfg = llm_config
        self.engine = LLMEngine(llm_config)
        self.engine.start()
        # Eager in-flight spill on SIGTERM (ISSUE 14): a graceful kill
        # pushes every live chain's computed pages into the KV tier
        # before the process dies, so the failover continuation restores
        # instead of re-prefilling. Best-effort: actors run handlers off
        # the main thread (ValueError) and tests embed servers in-process.
        try:
            import signal

            prev = signal.getsignal(signal.SIGTERM)

            def _on_term(signum, frame):
                try:
                    self.eager_spill()
                finally:
                    if callable(prev):
                        prev(signum, frame)

            signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, OSError, RuntimeError):
            pass
        startup.mark_ready()

    # ---- OpenAI-shaped endpoints --------------------------------------
    def completions(self, payload: dict) -> Any:
        prompt = payload.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        params = self._sampling(payload)
        if payload.get("stream"):
            return self._stream_completion(prompt, params, chat=False,
                                           resume=self._resume_spec(payload))
        out = self.engine.generate(prompt, **params)
        return self._completion_response(out, chat=False)

    def chat(self, payload: dict) -> Any:
        prompt = _chat_prompt(payload.get("messages", []))
        params = self._sampling(payload)
        if payload.get("stream"):
            return self._stream_completion(prompt, params, chat=True,
                                           resume=self._resume_spec(payload))
        out = self.engine.generate(prompt, **params)
        return self._completion_response(out, chat=True)

    def models(self) -> dict:
        return {"object": "list",
                "data": [{"id": self.cfg.model_id, "object": "model",
                          "owned_by": "ray_tpu"}]}

    # ---- plumbing ------------------------------------------------------
    def _sampling(self, payload: dict) -> dict:
        out = {}
        if payload.get("max_tokens") is not None:
            out["max_tokens"] = int(payload["max_tokens"])
        if payload.get("temperature") is not None:
            out["temperature"] = float(payload["temperature"])
        if payload.get("top_k") is not None:
            out["top_k"] = int(payload["top_k"])
        # Fleet disagg handoff marker (ISSUE 16): the proxy already ran
        # the remote prefill and the chain is registered in the tier —
        # the engine's ordinary restore path IS the handoff; the flag
        # only routes the restore's accounting to the disagg counters.
        if payload.get("_disagg_handoff"):
            out["disagg"] = True
        # Ingress page-chain digests (ISSUE 10): the proxy computed them
        # once for routing; the replica carries them request-scoped
        # (serve/replica.py set the contextvar before dispatch) and the
        # engine reuses them for its tier restore after a page-0 check.
        from ray_tpu.serve import affinity
        digests = affinity.get_request_prefix_digests()
        if digests:
            out["prefix_digests"] = digests
        # Proxy-assigned X-Request-Id (ISSUE 12): reuse it as the engine
        # request id so the exemplar/timeline and client logs correlate.
        from ray_tpu.observability import attribution
        rid = attribution.get_request_id()
        if rid:
            out["request_id"] = rid
        return out

    @staticmethod
    def _resume_spec(payload: dict):
        """Continuation spec from a proxy re-dispatch (ISSUE 14): token
        ids already streamed to the client + how many resumes this
        request has burned. None for ordinary first-leg requests."""
        toks = payload.get("resume_tokens")
        if not toks:
            return None
        return ([int(t) for t in toks], int(payload.get("resume_count", 1)))

    def _completion_response(self, out: dict, chat: bool) -> dict:
        oid = f"cmpl-{uuid.uuid4().hex[:24]}"
        if chat:
            choice = {"index": 0, "finish_reason": "stop",
                      "message": {"role": "assistant", "content": out["text"]}}
            obj = "chat.completion"
        else:
            choice = {"index": 0, "finish_reason": "stop",
                      "text": out["text"]}
            obj = "text_completion"
        return {
            "id": oid, "object": obj, "created": int(time.time()),
            "model": self.cfg.model_id, "choices": [choice],
            "usage": {
                "prompt_tokens": out.get("num_prompt_tokens", 0),
                "completion_tokens": out.get("num_generated_tokens", 0),
                "total_tokens": out.get("num_prompt_tokens", 0)
                + out.get("num_generated_tokens", 0),
            },
            # engine-side timing + critical-path attribution (the bench
            # harness and the proxy's SLO finalizer read these)
            "ray_tpu": {"ttft_s": out.get("ttft_s"),
                        "latency_s": out.get("latency_s"),
                        "queue_wait_s": out.get("queue_wait_s"),
                        "request_id": out.get("request_id"),
                        "stages": out.get("stages") or []},
        }

    async def _stream_completion(self, prompt: str, params: dict, chat: bool,
                                 resume=None):
        """Async generator of OpenAI stream chunks (SSE payloads minus
        framing). Async so the poll sleep yields the replica's event loop —
        N streaming requests drain concurrently instead of serializing.

        `resume` (ISSUE 14) is a proxy continuation spec
        `(token_ids, resume_count)`: within the resume cap the request is
        admitted as prompt+tokens through the cache-aware path and emits
        only post-resume tokens; past the cap it degrades to a plain
        retry-from-scratch with the already-streamed prefix suppressed.
        Every delta chunk carries `token_ids` (the proxy's emitted-token
        journal — text deltas alone are not token-identifiable) and the
        first chunk of a resumed leg carries restore accounting for the
        proxy's `failover` attribution stage."""
        import asyncio

        import time as _time

        t0 = _time.monotonic()
        n_prompt = len(self.engine.tokenizer.encode(prompt)) \
            if isinstance(prompt, str) else len(prompt)
        resume_tokens, resume_count = resume if resume else ([], 0)
        use_resume, skip = _resume_plan(resume_tokens, resume_count, self.cfg)
        if use_resume:
            rid = self.engine.submit(prompt, resume_tokens=resume_tokens,
                                     **params)
        elif skip:
            # retry-from-scratch: the caller sent the REMAINING budget, so
            # restore the original cap — the suppressed regenerated prefix
            # must not eat into the tokens still owed to the client
            p2 = dict(params)
            if p2.get("max_tokens") is not None:
                p2["max_tokens"] = int(p2["max_tokens"]) + skip
            rid = self.engine.submit(prompt, **p2)
        else:
            rid = self.engine.submit(prompt, **params)
        oid = f"cmpl-{uuid.uuid4().hex[:24]}"
        obj = "chat.completion.chunk" if chat else "text_completion"
        ntok = 0
        ttft = None
        resume_meta_due = resume is not None
        try:
            while True:
                d = self.engine.drain(rid)
                # gate on TOKENS, not decoded text: a tokenizer can decode
                # a batch to "" (byte tokenizer on unprintable ids) and the
                # stream must still emit the chunk — TTFT is first-token
                # time
                toks = list(d.get("tokens") or ())
                text = d.get("text", "")
                if toks and skip:
                    drop = min(skip, len(toks))
                    skip -= drop
                    toks = toks[drop:]
                    text = self.engine.tokenizer.decode(toks) if toks else ""
                if toks:
                    if ttft is None:
                        ttft = _time.monotonic() - t0
                    ntok += len(toks)
                    if chat:
                        delta = {"delta": {"content": text}, "index": 0,
                                 "finish_reason": None}
                    else:
                        delta = {"text": text, "index": 0,
                                 "finish_reason": None}
                    chunk = {"id": oid, "object": obj,
                             "model": self.cfg.model_id, "choices": [delta],
                             "token_ids": toks}
                    if resume_meta_due:
                        resume_meta_due = False
                        prog = self.engine.request_progress(rid) or {}
                        chunk["resume_meta"] = {
                            "resumed": use_resume,
                            "restored_tokens": prog.get("restored_tokens", 0),
                            "restore_bytes": prog.get("restore_bytes", 0),
                            "restore_ms": prog.get("restore_ms", 0.0),
                            "cached_tokens": prog.get("cached_tokens", 0)}
                    yield chunk
                if d["done"]:
                    err = d.get("error")
                    reason = "error" if err else "stop"
                    fin = ({"delta": {}, "index": 0, "finish_reason": reason}
                           if chat else
                           {"text": "", "index": 0, "finish_reason": reason})
                    # final chunk carries usage + engine-side timing so
                    # streaming clients (and the bench) get the same
                    # accounting as the non-streaming path
                    final = {"id": oid, "object": obj,
                             "model": self.cfg.model_id, "choices": [fin],
                             "usage": {"prompt_tokens": n_prompt,
                                       "completion_tokens": ntok,
                                       "total_tokens": n_prompt + ntok},
                             "ray_tpu": {"ttft_s": ttft,
                                         "latency_s":
                                         _time.monotonic() - t0,
                                         "queue_wait_s":
                                         d.get("queue_wait_s"),
                                         "request_id": d.get("request_id"),
                                         "stages": d.get("stages") or []}}
                    if err:
                        final["error"] = {"message": str(err)}
                    yield final
                    return
                await asyncio.sleep(0.01)
        finally:
            # abandoned stream (client disconnect -> generator close): stop
            # burning batch slots and reap the engine entry — nothing will
            # drain it again
            self.engine.cancel(rid)

    # raw engine access (bench, composition)
    def generate(self, prompt: str, **kw) -> dict:
        return self.engine.generate(prompt, **kw)

    def submit(self, prompt: str, **kw) -> str:
        return self.engine.submit(prompt, **kw)

    def drain(self, request_id: str) -> dict:
        return self.engine.drain(request_id)

    def engine_stats(self) -> dict:
        stats = self.engine.engine_stats()
        _export_engine_stats(self.cfg.model_id, stats)
        return stats

    def warm_start(self, max_bytes: Optional[int] = None,
                   budget_s: Optional[float] = None) -> dict:
        """Cache-warm scale-up hook (ISSUE 17): the controller calls this
        through `handle_request` after readiness but BEFORE publishing
        the replica into the routing table. Restores the fleet's hottest
        tier chains into the local prefix cache under the configured
        byte/time budgets; {"supported": False, "pages": 0} when the KV
        tier or warm start is off (the controller then publishes
        immediately — same unsupported idiom as prefix_summary)."""
        return self.engine.warm_start(max_bytes=max_bytes,
                                      budget_s=budget_s)

    def eager_spill(self) -> dict:
        """Drain/SIGTERM hook (ISSUE 14): spill every in-flight chain's
        computed pages into the KV tier NOW, so continuations on
        surviving replicas restore this replica's work instead of
        recomputing it. No-op (0 pages) when the tier is off."""
        return {"spilled_pages": self.engine.spill_inflight()}

    # ---- prefix-affinity routing (ISSUE 10) ---------------------------
    def prefix_summary(self, since: Optional[int] = None) -> dict:
        """Bounded summary of this replica's resident prefix chains, for
        the controller's summary collector. `since` is the version the
        caller already holds — an unchanged index answers with a tiny
        "unchanged" marker instead of re-shipping the digest list.
        {"supported": False} permanently when the prefix cache is off."""
        snap = self.engine.prefix_summary(self.cfg.prefix_summary_max_pages)
        if snap is None:
            return {"supported": False}
        version, digests = snap
        meta = {
            "tokenizer": self.cfg.tokenizer,
            "page_size": self.cfg.page_size,
            "max_prompt_len": self.cfg.max_prompt_len,
            "kv_tier": bool(self.cfg.kv_tier_enabled
                            and self.cfg.prefix_cache_enabled),
            "model_id": self.cfg.model_id,
            # fleet disagg placement inputs (ISSUE 16): the router's
            # disagg_plan reads these off rs.meta — which prefill pool
            # serves this deployment and past how many estimated
            # prefill tokens the handoff pays
            "disagg_prefill": self.cfg.disagg_prefill_deployment,
            "disagg_prompt_threshold": int(
                self.cfg.disagg_prompt_threshold or 0),
        }
        if since is not None and int(since) == version:
            return {"supported": True, "version": version,
                    "unchanged": True, "meta": meta}
        return {"supported": True, "version": version, "meta": meta,
                "digests": digests}

    def prefetch_hint(self, digests: list) -> dict:
        """Router's tier-hint: start fetching the non-resident tail of
        this chain from the KV tier now, overlapping admission."""
        return self.engine.prefetch_hint(digests)

    def check_health(self) -> bool:
        """Raises (the replica's unhealthy signal) once the engine loop
        has died: its in-flight requests were failed and it can serve no
        more, so the controller must replace the replica."""
        if self.engine.loop_error is not None:
            raise RuntimeError(self.engine.loop_error)
        # periodic health checks double as the metrics heartbeat: every
        # probe refreshes this replica's engine gauges on the CP
        _export_engine_stats(self.cfg.model_id, self.engine.engine_stats())
        return True

    # ---- HTTP ingress dispatch (proxy calls handle_http when defined) --
    def handle_http(self, path: str, method: str, payload: Any) -> Any:
        path = "/" + path.strip("/")
        if path.endswith("/chat/completions"):
            return self.chat(payload if isinstance(payload, dict) else {})
        if path.endswith("/completions"):
            return self.completions(
                payload if isinstance(payload, dict) else {})
        if path.endswith("/models"):
            return self.models()
        if path.endswith("/stats"):
            return self.engine_stats()
        return {"error": {"message": f"no route for {path}", "code": 404}}


def build_llm_deployment(llm_config: LLMConfig, *, name: Optional[str] = None):
    """LLMServer as a serve Deployment (one engine per replica). TPU
    placement comes from llm_config.ray_actor_options (e.g.
    {"resources": {"TPU": 4}}) — each replica then lands on a TPU worker
    process owning those chips."""
    from ray_tpu import serve

    return serve.deployment(
        LLMServer,
        name=name or llm_config.name,
        num_replicas=llm_config.num_replicas,
        max_ongoing_requests=4 * llm_config.max_batch_size,
        ray_actor_options=dict(llm_config.ray_actor_options or {}),
        slo_ttft_p99_ms=llm_config.slo_ttft_p99_ms,
        slo_e2e_p99_ms=llm_config.slo_e2e_p99_ms,
        slo_sample_rate=llm_config.slo_sample_rate,
        # first requests compile XLA programs for minutes on TPU; don't let
        # routine health checking kill the replica mid-compile
        health_check_timeout_s=600.0,
    )
