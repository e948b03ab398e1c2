"""Prefill/decode disaggregated serving.

TPU-native analog of the reference's prefill-decode disaggregation
(python/ray/llm/_internal/serve/deployments/prefill_decode_disagg/
prefill_decode_disagg.py:1): prefill replicas run ONLY the prompt pass and
hand the resulting KV pages to decode replicas, which run ONLY the
continuous-batching token loop. Prefill is compute-bound and bursty; decode
is memory-bandwidth-bound and steady — separating them lets each replica
pool scale and batch independently.

KV handoff rides the OBJECT PLANE (the reference uses vLLM KV-transfer
connectors/NIXL): the prefill replica extracts the request's KV pages to
host memory, the blob travels as a task return through the shared-memory
object store (chunked cross-node pulls when the pools live on different
hosts), and the decode replica scatters it into its own paged pool with a
donated-buffer jitted program (no full-pool copy per injection).

Pieces:
- ``prefill_only(engine, ...)``     — prompt pass + KV extraction on a
  NON-started LLMEngine (prefill replicas have no decode loop).
- ``DecodeEngine.submit_prefilled`` — admits a prefilled request into the
  decode loop: allocates slot+pages, scatters the KV blob, continues from
  the handed-off first token.
- ``build_disagg_openai_app``       — OpenAI ingress whose completions
  path is prefill-replica → KV blob → local decode engine.

Fleet path (ISSUE 16): ``build_disagg_fleet_app`` lifts the handoff onto
the STREAMED object plane instead of a whole-blob transfer. Prefill
replicas gain ``prefill_stream``: the prompt pass's full KV pages spill
through the tier codec into a local KVTierStore and register in the CP
``kv_tier:`` index (namespace shared with decode engines via
``engine.kv_tier_namespace``); what returns is a LIGHT descriptor, not
the KV. The decode pool is plain tier-enabled ``LLMServer`` replicas
(``FleetDecodeServer``): an ordinary submit finds the prefill-registered
chain, opens a ``ChainStream`` and starts decoding as pages land — the
PR 15 ``_restoring`` machinery IS the handoff, so a dead prefill replica
mid-stream degrades to a partial restore + tail prefill instead of
failing the request. The proxy/router pick the branch per request
(``Router.disagg_plan`` when estimated prefill tokens exceed
``disagg_prompt_threshold``) and stamp an ordered ``prefill_remote``
attribution stage.

Prefix caching: the disagg path BYPASSES the prefix-cache index by
decision (``_disable_prefix_cache``), not by accident. Prefill replicas
allocate and free their pages inside one call, so nothing survives to
index; decode pools only ever receive handed-off KV blobs whose prompt
computation happened on another engine — indexing those pages would
advertise KV this engine never computed against its own admission path,
and the KV-handoff accounting (pool fully recycled per request) is an
invariant the disagg tests pin. Cross-replica prefix reuse belongs in the
prefill tier's router, not here.
"""

from __future__ import annotations

import dataclasses
import threading
import time
import uuid
from typing import Any, Optional

import numpy as np

from ray_tpu.serve.llm import llm_server as _llm_server
from ray_tpu.serve.llm.config import LLMConfig
from ray_tpu.serve.llm.engine import LLMEngine, _Request


def _disable_prefix_cache(cfg: LLMConfig) -> LLMConfig:
    """Disagg engines run with the prefix cache OFF (module docstring);
    returns the config unchanged when it already is."""
    if not cfg.prefix_cache_enabled:
        return cfg
    return dataclasses.replace(cfg, prefix_cache_enabled=False)


def _disable_spec_decode(cfg: LLMConfig) -> LLMConfig:
    """Prefill replicas run with speculative decoding OFF by decision
    (same pattern as the prefix cache): a prefill engine never enters the
    decode loop, so a verify-k program would only waste warmup compile
    time there. DECODE engines keep the caller's setting — handed-off
    requests satisfy the spec path's length invariant (seq_len ==
    prompt + generated - 1) exactly like locally prefilled ones."""
    if not cfg.spec_decode_enabled:
        return cfg
    return dataclasses.replace(cfg, spec_decode_enabled=False)


# ---------------------------------------------------------------------------
# handoff wire codec (ISSUE 16)
# ---------------------------------------------------------------------------

def _encode_state(state: dict, mode: str) -> dict:
    """Encode a handoff blob's KV pages for the wire (compiled-pipeline
    channel or object-plane task return). Pages encode independently —
    the same per-page layout the tier stores — so the decode side can
    reuse the one codec. ``none`` passes through untouched."""
    if mode == "none" or "kv_k" not in state:
        return state
    from ray_tpu.serve.llm import kv_codec
    n = int(state["n_pages"])
    pages = [(kv_codec.encode_page(state["kv_k"][:, :, i:i + 1], mode),
              kv_codec.encode_page(state["kv_v"][:, :, i:i + 1], mode))
             for i in range(n)]
    out = {k: v for k, v in state.items() if k not in ("kv_k", "kv_v")}
    out["enc_pages"] = pages
    out["wire_bytes"] = sum(
        kv_codec.encoded_nbytes(ek) + kv_codec.encoded_nbytes(ev)
        for ek, ev in pages)
    return out


def _decode_state(state: dict) -> dict:
    """Invert :func:`_encode_state`; raw blobs pass through (mixed-codec
    rollouts: the decode side accepts both shapes regardless of its own
    wire setting)."""
    if "enc_pages" not in state:
        return state
    from ray_tpu.serve.llm import kv_codec
    ks = [kv_codec.decode_page(ek) for ek, _ in state["enc_pages"]]
    vs = [kv_codec.decode_page(ev) for _, ev in state["enc_pages"]]
    out = {k: v for k, v in state.items() if k != "enc_pages"}
    out["kv_k"] = np.concatenate(ks, axis=2)
    out["kv_v"] = np.concatenate(vs, axis=2)
    return out


def int8_wire_divergence(ref_tokens, got_tokens) -> float:
    """Greedy-output divergence between a lossless-wire reference and an
    int8-wire run: fraction of positions that differ (length mismatch
    counts every unmatched position). What :func:`int8_wire_allowed`
    takes."""
    ref = list(ref_tokens or [])
    got = list(got_tokens or [])
    n = max(len(ref), len(got), 1)
    diff = sum(1 for a, b in zip(ref, got) if a != b) \
        + abs(len(ref) - len(got))
    return diff / n


def int8_wire_allowed(cfg: LLMConfig, measured_divergence: float) -> bool:
    """Per-deployment quality policy gating int8 on the disagg wire: the
    lossy codec is only policy-approved when the MEASURED divergence
    stays within the deployment's bound. The default bound (0.0) demands
    bit-identity — int8 never silently defaults on."""
    return float(measured_divergence) <= max(
        0.0, float(cfg.disagg_int8_max_divergence))


# ---------------------------------------------------------------------------
# prefill side
# ---------------------------------------------------------------------------

def prefill_only(eng: LLMEngine, prompt, *, temperature: float | None = None,
                 top_k: int | None = None) -> dict:
    """Run the prompt pass on a prefill-role engine and extract the KV.

    The engine must NOT have its decode loop started; calls are serialized
    on the engine lock (prefill replicas scale by replica count, not by
    intra-process concurrency — each call owns the chip while it runs).

    Returns a host-side handoff blob:
      {prompt_tokens, plen, n_pages, first_token, kv_k, kv_v,
       temperature, prefill_ttft_s}
    """
    t0 = time.monotonic()
    eng.refuse_stateful("disaggregated prefill")
    if isinstance(prompt, str):
        toks = eng.tokenizer.encode(prompt)
    else:
        toks = list(prompt)
    toks = toks[: eng.cfg.max_prompt_len]
    temperature = eng.cfg.temperature if temperature is None else temperature
    if top_k is not None and top_k != eng.cfg.top_k:
        pass  # sampling uses the engine top_k (static to the programs)

    plen = max(1, len(toks))
    n_pages = -(-plen // eng.cfg.page_size)
    if n_pages > eng.cfg.num_pages - 1:  # page 0 is the trash page
        raise ValueError(
            f"prompt needs {n_pages} KV pages but the pool has "
            f"{eng.cfg.num_pages - 1}; raise num_pages or page_size")
    with eng._lock:
        # each call allocates AND frees inside this lock scope, so the pool
        # is always fully free here — a failed alloc can never resolve by
        # waiting (hence the hard error above instead of a retry loop)
        pages = eng.allocator.alloc(n_pages)
        if pages is None:
            raise RuntimeError("prefill page pool unexpectedly exhausted")
        try:
            table = np.zeros((eng.max_pages_per_seq,), np.int32)
            table[:n_pages] = pages
            bucket = eng._bucket(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = toks
            fn = eng._prefill_fn(bucket)
            eng._rng, sub = eng._split_key(eng._rng)
            # no slot is armed here: the program's first-token write goes
            # to the trash row of the engine's token vector
            tok_dev, eng._dev_tokens, eng.kv = fn(
                eng.params, eng.kv, eng._dev_tokens, table, padded,
                np.int32(plen), sub, np.full((1,), temperature, np.float32),
                np.int32(eng.cfg.max_batch_size))
            # extract this request's pages to host (the handoff payload)
            kv_k, kv_v = eng._kvc.fetch_pages(
                *eng._kvc.gather_pages(eng.kv, pages), n_pages)
            first = int(tok_dev)
        finally:
            eng.allocator.free(pages)
        eng.stats["prefills"] += 1
    return {
        "prompt_tokens": toks, "plen": plen, "n_pages": n_pages,
        "first_token": first, "kv_k": kv_k, "kv_v": kv_v,
        "temperature": temperature,
        "prefill_ttft_s": time.monotonic() - t0,
    }


# ---------------------------------------------------------------------------
# decode side
# ---------------------------------------------------------------------------

class DecodeEngine(LLMEngine):
    """LLMEngine that can admit PREFILLED requests: the prompt KV arrives
    as a host blob and is scattered into the local paged pool; decode
    continues from the handed-off first token."""

    def __init__(self, cfg: LLMConfig, params=None, rng_seed: int = 0):
        super().__init__(_disable_prefix_cache(cfg), params=params,
                         rng_seed=rng_seed)
        self._inject_q: list[tuple[_Request, dict]] = []

    def submit_prefilled(self, state: dict, *,
                         max_tokens: Optional[int] = None,
                         request_id: Optional[str] = None) -> str:
        self.refuse_stateful("disaggregated adoption")
        state = _decode_state(state)  # wire-encoded blobs decode HERE
        toks = list(state["prompt_tokens"])
        req = _Request(
            request_id=request_id or uuid.uuid4().hex[:16],
            prompt_tokens=toks,
            max_tokens=max(1, min(max_tokens or self.cfg.max_tokens,
                                  self.cfg.max_seq_len - len(toks))),
            temperature=float(state.get("temperature", 0.0)),
            top_k=self.cfg.top_k,
            stop_token=getattr(self.tokenizer, "eos_token_id", None))
        req.dispatched = 1
        with self._lock:
            self._requests[req.request_id] = req
            self.stats["requests"] += 1
            # the first token already exists — record it through the normal
            # bookkeeping so stop/max handling is uniform
            self._record_token(req, int(state["first_token"]))
            if req.done:
                req.done_event.set()
                return req.request_id
            self._inject_q.append((req, state))
        self._wake.set()
        return req.request_id

    def _admissions_blocked(self) -> bool:
        # prefilled requests queued for injection count as blocked
        # admissions too: shrink decode blocks so their pages/slots free up
        # promptly (lock held by _step)
        return super()._admissions_blocked() or (
            bool(self._inject_q) and bool(self.free_slots))

    def engine_stats(self) -> dict:
        stats = super().engine_stats()
        stats["waiting"] += len(self._inject_q)
        return stats

    def _admit(self) -> int:
        admitted = super()._admit()
        while True:
            with self._lock:
                if not self._inject_q or not self.free_slots:
                    return admitted
                req, state = self._inject_q[0]
                need = -(-max(state["plen"] + req.max_tokens, 1)
                         // self.cfg.page_size)
                need = min(need, self.max_pages_per_seq)
                pages = self.allocator.alloc(need)
                if pages is None:
                    return admitted  # page pool exhausted; retry next loop
                self._inject_q.pop(0)
                slot = self.free_slots.pop()
                req.slot = slot
                req.pages = pages
            self._inject(req, state)
            admitted += 1

    def _inject(self, req: _Request, state: dict):
        """Scatter the handed-off KV pages into the local pool and arm the
        slot (loop thread only)."""
        table = np.zeros((self.max_pages_per_seq,), np.int32)
        table[: len(req.pages)] = req.pages
        self._inject_host_pages([(state["kv_k"], state["kv_v"])],
                                req.pages[:state["n_pages"]])
        with self._lock:
            self.page_tables[req.slot] = table
            self.seq_lens[req.slot] = state["plen"]
            self.slot_req[req.slot] = req
            self._dirty_slots[req.slot] = (state["plen"], req.temperature)
            # continue decoding from the handed-off first token
            self._overrides[req.slot] = int(state["first_token"])


# ---------------------------------------------------------------------------
# serve deployments
# ---------------------------------------------------------------------------

class PrefillServer:
    """Prefill-role replica: owns a non-started engine; each call runs one
    prompt pass and returns the KV handoff blob (reference: the "p" servers
    of prefill_decode_disagg)."""

    def __init__(self, llm_config: LLMConfig | dict):
        if isinstance(llm_config, dict):
            llm_config = LLMConfig(**llm_config)
        self.cfg = llm_config
        # loop NOT started; prefix cache + spec decode off (module
        # docstring / _disable_spec_decode)
        self.engine = LLMEngine(
            _disable_spec_decode(_disable_prefix_cache(llm_config)))
        # streamed-handoff tier store (ISSUE 16), built on first
        # prefill_stream: the engine's own tier requires the prefix
        # cache (off here by decision), so the prefill role spills
        # through a store of its own — SAME namespace as the decode
        # engines (kv_tier_namespace over the same config), which is
        # what makes the registrations restorable over there
        self._tier = None
        self._tier_lock = threading.Lock()

    def _tier_store(self):
        with self._tier_lock:
            if self._tier is None:
                from ray_tpu.serve.llm import kv_tier as kvt
                from ray_tpu.serve.llm.engine import kv_tier_namespace
                cfg = self.cfg
                self._tier = kvt.KVTierStore(
                    max_bytes=cfg.kv_tier_max_bytes,
                    disk_dir=None,  # handoffs are transient; no disk tier
                    disk_max_bytes=0,
                    ttl_s=cfg.kv_tier_ttl_s,
                    page_size=cfg.page_size,
                    namespace=kv_tier_namespace(
                        cfg, self.engine.model_cfg,
                        self.engine._kvc.pool_dtype(self.engine.kv)),
                    codec=cfg.kv_tier_codec)
            return self._tier

    def prefill(self, prompt, sampling: dict) -> dict:
        state = prefill_only(
            self.engine, prompt,
            temperature=sampling.get("temperature"),
            top_k=sampling.get("top_k"))
        return _encode_state(state, self.cfg.disagg_wire_codec)

    def prefill_one(self, req: dict) -> dict:
        """Single-argument stage entry for the compiled pipeline (the KV
        blob then rides the mutable-channel edge to the decode node instead
        of the object plane)."""
        return {"rid": req["rid"],
                "state": self.prefill(req["prompt"],
                                      req.get("sampling") or {})}

    def prefill_stream(self, subpath: str, payload: dict) -> dict:
        """Streamed fleet handoff (ISSUE 16): run the prompt pass, spill
        the full KV pages through the tier codec into this replica's
        store, and register them in the CP ``kv_tier:`` index. Returns a
        LIGHT descriptor — the KV itself travels later, chunk by chunk,
        when the decode replica's ``ChainStream`` pulls it.

        ``flush_index`` is the handshake that makes the return value
        mean something: once this call returns, the decode side's
        ``_match_entries`` can see every page, so the proxy may dispatch
        the decode leg immediately. KV pages are sampling-independent,
        so the decode leg re-applies the request's own sampling params.
        """
        from ray_tpu.serve import affinity
        prompt = affinity.prompt_from_payload(subpath, payload)
        if prompt is None:
            raise ValueError(f"no prompt in disagg prefill payload "
                             f"for route {subpath!r}")
        state = prefill_only(self.engine, prompt, temperature=0.0)
        ps = self.cfg.page_size
        toks = state["prompt_tokens"]
        full = len(toks) // ps
        registered = 0
        wire = 0
        if full > 0:
            tier = self._tier_store()
            digest = b""
            digs, tokens = [], []
            for i in range(full):
                digest = self.engine._kvc._chain_digest(
                    digest, toks[i * ps:(i + 1) * ps])
                digs.append(digest.hex())
                tokens.append((i + 1) * ps)
            with self._tier_lock:
                enc0 = tier.counters["put_bytes_enc"]
                registered = tier.put(
                    state["kv_k"][:, :, :full], state["kv_v"][:, :, :full],
                    digests=digs, tokens=tokens)
                wire = tier.counters["put_bytes_enc"] - enc0
            tier.flush_index(2.0)
        return {"plen": state["plen"], "pages_registered": int(registered),
                "wire_bytes": int(wire),
                "prefill_ttft_s": state["prefill_ttft_s"]}

    def wire_ratio_probe(self) -> float:
        """Measured raw/encoded ratio of this model's real prefill KV
        under the wire codec (one deterministic max-length prompt pass).
        Feeds `_handoff_channel_capacity`'s encoded sizing — a guess
        would either re-over-provision the channel or overflow it."""
        mode = self.cfg.disagg_wire_codec
        if mode == "none":
            return 1.0
        from ray_tpu.serve.llm import kv_codec
        vocab = max(2, int(self.engine.tokenizer.vocab_size))
        toks = [(i * 37 + 11) % vocab
                for i in range(max(1, self.cfg.max_prompt_len))]
        state = prefill_only(self.engine, toks, temperature=0.0)
        raw = int(state["kv_k"].nbytes) + int(state["kv_v"].nbytes)
        enc = 0
        for i in range(state["n_pages"]):
            for a in (state["kv_k"], state["kv_v"]):
                enc += kv_codec.encoded_nbytes(
                    kv_codec.encode_page(a[:, :, i:i + 1], mode))
        return raw / max(1, enc)

    def engine_stats(self) -> dict:
        stats = {**self.engine.engine_stats(), "mode": "prefill"}
        if self._tier is not None:
            stats["handoff_bytes_wire"] = int(
                self._tier.counters["put_bytes_enc"])
        return stats

    def check_health(self) -> bool:
        return True


def _handoff_channel_capacity(cfg: LLMConfig,
                              measured_ratio: float | None = None) -> int:
    """Channel capacity sized for the largest KV handoff blob this config
    can produce (a max_prompt_len prompt's pages, k+v in the model dtype),
    not the default 8 MiB: Channel.write hard-fails on overflow — an
    undersized pipe would poison every later request on it.

    Since PR 15 the blob travels ENCODED (``disagg_wire_codec``), so raw
    model-dtype sizing over-provisions the channel by the codec ratio
    (~4–9× on bf16 KV). With a ``measured_ratio`` (raw/encoded, from
    ``PrefillServer.wire_ratio_probe`` on the real model) the capacity
    shrinks accordingly — but only trusting HALF the measured ratio and
    never dropping below raw sizing: the probe samples one prompt, other
    prompts compress worse, and overflow poisons the pipe while idle
    headroom only costs shm."""
    from ray_tpu.serve.llm import kv_cache
    pages = -(-cfg.max_prompt_len // cfg.page_size)
    kv_bytes = pages * kv_cache.page_raw_nbytes(cfg.model(), cfg.page_size)
    if cfg.disagg_wire_codec != "none":
        ratio = max(1.0, 0.5 * float(measured_ratio or 0.0))
        kv_bytes = int(kv_bytes / ratio)
    # prompt tokens + pickle/ndarray framing + slack
    return int(kv_bytes * 1.25) + (1 << 20)


class DisaggLLMServer:
    """Decode-role ingress: completions run prefill on a prefill replica,
    then decode locally from the handed-off KV (reference: the "d" servers
    + PDProxyServer routing).

    Two prefill transports:
    - ``prefill_handle``: a serve deployment handle; the KV blob travels as
      a task return through the object plane.
    - ``prefill_actors`` (compiled-pipeline path): raw prefill actors, each
      compiled into a CompiledPipeline whose prompt→KV edge is a mutable
      channel (agent-relayed across nodes) — the aDAG shape of the same
      handoff (reference compiled_dag_node.py:805 over
      experimental/channel)."""

    def __init__(self, llm_config: LLMConfig | dict, prefill_handle=None,
                 prefill_actors: list | None = None):
        if isinstance(llm_config, dict):
            llm_config = LLMConfig(**llm_config)
        self.cfg = llm_config
        self.prefill = prefill_handle
        self._pipes = []
        self._pipe_lock = threading.Lock()
        self._pipe_rr = 0
        self._rid = 0
        if prefill_actors:
            import ray_tpu
            from ray_tpu.dag import CompiledPipeline
            ratio = None
            if llm_config.disagg_wire_codec != "none":
                # size the channels from a MEASURED codec ratio (one real
                # prefill on actor 0) — conservative floor inside
                # _handoff_channel_capacity; a failed probe sizes raw
                try:
                    ratio = ray_tpu.get(
                        prefill_actors[0].wire_ratio_probe.remote(),
                        timeout=600.0)
                except Exception:  # noqa: BLE001 — raw sizing is safe
                    ratio = None
            cap = _handoff_channel_capacity(llm_config,
                                            measured_ratio=ratio)
            self._pipes = [
                CompiledPipeline([(a, "prefill_one")], capacity=cap).compile()
                for a in prefill_actors]
        self.engine = DecodeEngine(llm_config)
        self.engine.start()

    # ---- OpenAI surface (mirrors llm_server.LLMServer) ----------------
    def completions(self, payload: dict) -> Any:
        prompt = payload.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        return self._run(prompt, payload, chat=False)

    def chat(self, payload: dict) -> Any:
        from ray_tpu.serve.llm.llm_server import _chat_prompt
        return self._run(_chat_prompt(payload.get("messages", [])),
                         payload, chat=True)

    def _pipeline_prefill(self, prompt, sampling: dict) -> dict:
        """Prefill through a compiled pipeline (round-robin over prefill
        stages); execute() raising over-capacity just means that pipe has
        its buffers full — try the next, else wait briefly."""
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            with self._pipe_lock:
                pipe = self._pipes[self._pipe_rr % len(self._pipes)]
                self._pipe_rr += 1
                self._rid += 1
                rid = self._rid
            try:
                ref = pipe.execute(
                    {"rid": rid, "prompt": prompt, "sampling": sampling})
            except RuntimeError:
                time.sleep(0.05)  # all slots busy: prefill is chip-bound
                continue
            out = ref.get(timeout=600.0)
            if out["rid"] != rid:
                # belt over the pipeline's write-order lock: a cross-wired
                # prefill would decode the WRONG prompt's KV silently
                raise RuntimeError(
                    f"prefill pipeline returned rid {out['rid']} for "
                    f"request {rid}")
            return out["state"]
        raise TimeoutError("prefill pipeline saturated for 600s")

    def _run(self, prompt, payload: dict, chat: bool) -> Any:
        from ray_tpu.serve.llm.llm_server import LLMServer
        sampling = {k: payload[k] for k in ("temperature", "top_k")
                    if payload.get(k) is not None}
        t0 = time.monotonic()
        if self._pipes:
            state = self._pipeline_prefill(prompt, sampling)
        else:
            state = self.prefill.options(
                method_name="prefill", timeout_s=600.0).remote(
                prompt, sampling).result(timeout_s=600.0)
        rid = self.engine.submit_prefilled(
            state, max_tokens=payload.get("max_tokens"))
        out = self.engine.result(rid, timeout=600.0)
        out["ttft_s"] = state["prefill_ttft_s"]
        out["latency_s"] = time.monotonic() - t0
        # reuse the OpenAI response shaping
        return LLMServer._completion_response(self, out, chat=chat)

    def models(self) -> dict:
        return {"object": "list",
                "data": [{"id": self.cfg.model_id, "object": "model",
                          "owned_by": "ray_tpu", "mode": "disagg"}]}

    def engine_stats(self) -> dict:
        from ray_tpu.serve.llm.llm_server import _export_engine_stats
        stats = {**self.engine.engine_stats(), "mode": "disagg"}
        _export_engine_stats(self.cfg.model_id, stats)
        return stats

    def check_health(self) -> bool:
        return True

    def handle_http(self, path: str, method: str, payload: Any) -> Any:
        path = "/" + path.strip("/")
        # chat first: "/chat/completions".endswith("/completions") is True
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


def build_disagg_openai_app(llm_config: LLMConfig | dict,
                            route_prefix: str = "/v1",
                            num_prefill: int = 1, num_decode: int = 1,
                            prefill_actor_options: dict | None = None,
                            decode_actor_options: dict | None = None,
                            use_pipeline: bool = False):
    """Disaggregated OpenAI application: num_prefill prefill replicas feed
    num_decode decode ingress replicas (reference:
    prefill_decode_disagg.build_pd_app). With ``use_pipeline`` the
    prefill→decode handoff rides compiled mutable-channel pipelines
    (the aDAG path) instead of object-plane task returns."""
    import ray_tpu
    from ray_tpu import serve

    if isinstance(llm_config, dict):
        llm_config = LLMConfig(**llm_config)
    if use_pipeline:
        # raw prefill actors, compiled into pipelines by each decode server
        # (max_concurrency 2: the resident stage loop + health checks)
        opts = dict(prefill_actor_options or {})
        opts.setdefault("max_concurrency", 2)
        actors = [ray_tpu.remote(PrefillServer).options(**opts).remote(
            llm_config) for _ in range(num_prefill)]
        decode_dep = serve.deployment(
            DisaggLLMServer, name=f"{llm_config.name}-decode",
            num_replicas=num_decode,
            max_ongoing_requests=4 * llm_config.max_batch_size,
            ray_actor_options=dict(decode_actor_options or {}),
            health_check_timeout_s=600.0)
        decode_dep.route_prefix = route_prefix
        return decode_dep.bind(llm_config, None, actors)
    prefill_dep = serve.deployment(
        PrefillServer, name=f"{llm_config.name}-prefill",
        num_replicas=num_prefill,
        max_ongoing_requests=2,  # a prefill owns the chip while it runs
        ray_actor_options=dict(prefill_actor_options or {}),
        health_check_timeout_s=600.0)
    decode_dep = serve.deployment(
        DisaggLLMServer, name=f"{llm_config.name}-decode",
        num_replicas=num_decode,
        max_ongoing_requests=4 * llm_config.max_batch_size,
        ray_actor_options=dict(decode_actor_options or {}),
        health_check_timeout_s=600.0)
    decode_dep.route_prefix = route_prefix
    return decode_dep.bind(llm_config, prefill_dep.bind(llm_config))


# ---------------------------------------------------------------------------
# fleet disaggregation on the streamed KV plane (ISSUE 16)
# ---------------------------------------------------------------------------

class FleetDecodeServer(_llm_server.LLMServer):
    """Decode-role replica for the FLEET disagg path: a plain tier-
    enabled ``LLMServer`` — prefix cache ON, ordinary submit path — plus
    an ignored second init arg that anchors the prefill pool in the
    serve bind graph (``serve.run`` deploys bound sub-apps; the decode
    ingress never calls the prefill handle, the PROXY dispatches
    ``prefill_stream`` through the router's disagg plan). A real
    subclass, not a trampoline: the controller's ingress probe checks
    the CLASS for ``handle_http``."""

    def __init__(self, llm_config: LLMConfig | dict, prefill_handle=None):
        super().__init__(llm_config)


def build_disagg_fleet_app(llm_config: LLMConfig | dict,
                           route_prefix: str = "/v1",
                           num_prefill: int = 2, num_decode: int = 2,
                           prefill_actor_options: dict | None = None,
                           decode_actor_options: dict | None = None):
    """Fleet-level disaggregated application (ISSUE 16): ``num_prefill``
    prefill replicas (controller role ``prefill``) stream KV to
    ``num_decode`` tier-enabled decode replicas through the CP
    ``kv_tier:`` index. The decode deployment is the ingress; its config
    carries ``disagg_prefill_deployment`` + ``disagg_prompt_threshold``,
    which the replicas export via ``prefix_summary`` meta so the
    router's ``disagg_plan`` can take the third placement mode."""
    from ray_tpu import serve

    if isinstance(llm_config, dict):
        llm_config = LLMConfig(**llm_config)
    prefill_name = f"{llm_config.name}-prefill"
    decode_cfg = dataclasses.replace(
        llm_config,
        prefix_cache_enabled=True,
        kv_tier_enabled=True,
        disagg_prefill_deployment=prefill_name)
    prefill_dep = serve.deployment(
        PrefillServer, name=prefill_name,
        num_replicas=num_prefill,
        max_ongoing_requests=2,  # a prefill owns the chip while it runs
        ray_actor_options=dict(prefill_actor_options or {}),
        health_check_timeout_s=600.0)
    prefill_dep.config.role = "prefill"
    decode_dep = serve.deployment(
        FleetDecodeServer, name=llm_config.name,
        num_replicas=num_decode,
        max_ongoing_requests=4 * llm_config.max_batch_size,
        ray_actor_options=dict(decode_actor_options or {}),
        health_check_timeout_s=600.0)
    decode_dep.config.role = "decode"
    decode_dep.route_prefix = route_prefix
    return decode_dep.bind(decode_cfg, prefill_dep.bind(llm_config))
