"""LLM serving config (reference: python/ray/llm/_internal/serve/configs/
server_models.py LLMConfig — model id + engine kwargs; here the engine knobs
are first-class because the engine is in-framework)."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class LLMConfig:
    """Model + continuous-batching engine sizing.

    TPU notes: `max_batch_size` fixes the decode slot count (static shapes —
    one compiled decode program); prompt prefill pads to power-of-two buckets
    bounded by `max_prompt_len` (bounded compile cache); the KV cache is
    paged so long and short sequences share one HBM pool.
    """

    # model
    model_id: str = "llama-tiny"
    model_config: Any = None          # LlamaConfig | Lfm2MoeConfig | ...
    checkpoint_path: Optional[str] = None  # llama.save_params npz; None = random init
    tokenizer: str = "byte"           # "byte" | HF tokenizer local path

    # engine sizing
    max_batch_size: int = 8           # decode slots
    page_size: int = 128              # tokens per KV page
    num_pages: int = 256              # total pages in the HBM pool
    max_prompt_len: int = 512
    max_seq_len: int = 1024           # prompt + generation cap per request
    # prompts longer than this prefill in chunks of this many tokens,
    # interleaved with decode blocks (chunked prefill): a long admission
    # stalls active generations by at most one chunk, not the whole prompt
    prefill_chunk: int = 512
    # Paged-attention backend (serve/llm/kv_cache.py +
    # ops/paged_attention.py): "pallas" runs the fused kernel family —
    # decode, multi-query speculative verify, and chunked prefill all
    # read K/V pages directly from the pool via the slot page table
    # (no materialized gather per layer per step) with numerics
    # bit-identical to the gather path; "gather" materializes the full
    # per-slot view + dense softmax. "auto" (default) resolves to pallas
    # on TPU when the kernel tiling accepts the model's shapes and
    # gather elsewhere; tests force "pallas" on CPU, where the kernels
    # run in Pallas interpreter mode.
    attention_kernel: str = "auto"    # "auto" | "gather" | "pallas"
    # Tensor parallelism (ISSUE 20): one engine replica spans tp_degree
    # chips along the mesh "tensor" axis — Megatron-style intra-layer
    # sharding (attention heads / KV heads / ffn hidden / vocab split;
    # wo and w_down row-parallel), the paged KV pool sharded per-KV-head,
    # and every compiled program (fused decode, chunked prefill,
    # verify-k, the Pallas paged-attention family) partitioned under
    # pjit/shard_map. tp_degree=1 (default) builds no mesh and is
    # bit-identical to the single-chip engine. Requires n_kv_heads,
    # n_heads, ffn_dim and vocab_size all divisible by tp_degree, and
    # tp_degree visible devices. KV pages spilled by a TP engine are
    # per-shard-encoded and namespace-isolated by layout (the `|tp{N}`
    # rule — see engine.kv_tier_namespace), so TP=1 and TP=2 stores
    # never exchange incompatible pages.
    tp_degree: int = 1
    # The three values that size the engine loop's lead over the device.
    # The device runs one ordered stream, so an arriving prompt's prefill
    # runs behind whatever is in flight: at most pipeline_depth entries
    # of k decode steps each, and the loop admits nothing while it waits
    # for the oldest of them (engine.py _select_block, _step).
    #
    # decode_block: the CEILING of k, the decode steps fused into one
    # dispatched program (and the rows of that program's token buffer:
    # k is its operand). With nothing queued the engine dispatches the
    # smallest tier that keeps the device fed (one step, then the
    # pressure tier's k) and climbs to this value only while it sees the
    # device run dry with less (lead.py; engine_stats idle_lead_k). Streaming
    # granularity and stop-token lag grow with k. With a block length B
    # above 1 (generation by diffusion over blocks) the tiers count
    # whole blocks: this many tokens over B, at least one.
    decode_block: int = 8
    # k while requests queue for slots (slot-starved): smaller blocks
    # detect stop tokens (and free slots for the queue) sooner; also the
    # idle tier's middle rung. A block pays its dispatch, its state
    # gather / scatter and a re-layout of the attention weights once for
    # k steps. 1-2 for latency-sensitive serving; decode_block makes the
    # queue-pressure tier that size.
    pressure_decode_block: int = 2
    # the ENTRY BOUND: dispatched-but-unharvested entries (decode blocks,
    # verify rounds, prefills' first tokens). A first token waits behind
    # at most pipeline_depth * k steps of device work, so the bound is as
    # shallow as keeps the device fed while the host harvests, emits and
    # dispatches (3 entries of ONE step of 13 ms do, on a v5e: the device
    # idles 0.024 % of a trace; PERF.md section 6, PR 42).
    pipeline_depth: int = 3

    # compile every bucket width's decode program at start() instead of
    # on first use mid-traffic (a compile stalls every active request)
    warmup_compile: bool = True

    # Engine performance introspection (observability/profiling.py):
    # phase timers (admit/prefill/chunk/decode/verify/harvest p50+p95),
    # inter-token-latency ring, and device-memory gauges in engine_stats().
    # Default ON — overhead is host-side clock reads on a loop that
    # dispatches device work asynchronously. Compile-event tracking stays
    # on even when this is False (it only does work on first-dispatch-per-
    # shape, and silent mid-traffic compiles are the failure class it
    # catches).
    profiling_enabled: bool = True

    # Automatic prefix caching (RadixAttention/vLLM-style): full pages of
    # prompt KV are kept in a refcounted hash-chained index after a request
    # finishes prefill, and later admissions with a matching token prefix
    # point their page tables at the shared pages and prefill ONLY the
    # suffix. Host-side bookkeeping between steps — compiled programs and
    # their static shapes are untouched. Disabled automatically on the
    # disaggregated path (disagg.py), where the prefill tier owns prompt
    # computation and decode pools only ever receive handed-off KV.
    prefix_cache_enabled: bool = True
    # cap on refcount-zero cached pages retained for reuse (LRU beyond it);
    # 0 = bounded only by the pool (cached pages evict under alloc pressure
    # either way, so the pool can never be starved by the cache)
    prefix_cache_max_pages: int = 0

    # Speculative decoding (n-gram draft + batched verify-k): greedy slots
    # whose recent tokens end with an n-gram seen earlier in their own
    # prompt+output get up to spec_draft_len tokens drafted for free
    # (prompt lookup — no draft model), and ONE fused verify program
    # scores the whole batch's drafts against the paged KV in a single
    # dispatch. Accepted tokens are bit-identical to ordinary greedy
    # decode (the verify pass computes the same logits step-by-step);
    # rejected drafts roll seq_lens back with no page traffic. Wins on
    # repetitive/long outputs; costs one wasted lane-step per rejected
    # token, so it is off by default. Disabled automatically on the
    # disagg prefill tier (no decode loop there — same bypass-by-decision
    # as the prefix cache); decode-side disagg engines support it.
    spec_decode_enabled: bool = False
    # drafted tokens per verify round (k). The verify program runs k+1
    # fused steps, so each round emits 1..k+1 tokens; k is static to the
    # compiled program (one verify program per bucket width).
    spec_draft_len: int = 4
    # longest suffix n-gram used for the lookup (longer match first)
    spec_ngram_max: int = 3

    # Tiered KV cache (serve/llm/kv_tier.py): prefix pages evicted from
    # the pool spill host-side into the node's shm object plane (backed
    # by a bounded local disk tier under pressure) and register in a
    # cluster-wide CP index, so ANY replica — including a cold one —
    # restores a spilled prefix instead of re-prefilling it. Greedy
    # outputs stay bit-identical to cold prefill; every tier failure
    # degrades to a plain cache miss. Requires prefix_cache_enabled.
    # Default OFF: spilling trades host copies + shm for prefill FLOPs,
    # which only pays on shared-prefix traffic.
    kv_tier_enabled: bool = False
    kv_tier_max_bytes: int = 256 * 1024 * 1024   # shm tier byte cap
    kv_tier_disk_dir: Optional[str] = None       # None = disk tier off
    kv_tier_disk_max_bytes: int = 1024 * 1024 * 1024
    kv_tier_ttl_s: float = 600.0                 # entry lifetime; <=0 = none
    # Page codec (serve/llm/kv_codec.py): pages are stored in the tiers
    # and shipped over the object plane ENCODED, so both byte caps hold
    # codec-ratio more prefix tokens and restores move fewer wire bytes.
    # "lossless" (byte-plane shuffle + DEFLATE) keeps greedy outputs
    # bit-identical; "int8" (per layer/kv-head scale quantization, ~4x
    # on fp32 before entropy coding) trades bounded reconstruction
    # error for ratio — opt-in; "none" is the raw PR 7 wire format.
    kv_tier_codec: str = "lossless"              # "none"|"lossless"|"int8"
    # Streaming restore: pages land chunk-by-chunk and inject while
    # later chunks are still in flight. chunk_pages is the fetch
    # granularity; the PR 7 fetch budget applies PER CHUNK (one dead
    # peer = one chunk stall -> partial restore, landed pages kept);
    # the landed-but-uninjected buffer is byte-bounded by the window.
    kv_tier_chunk_pages: int = 8
    kv_tier_chunk_timeout_s: float = 2.0
    kv_tier_stream_window_bytes: int = 8 * 1024 * 1024

    # Cache-warm scale-up (ISSUE 17): before a freshly started replica
    # enters the routing table, it pre-populates its prefix cache from
    # the CP `kv_tier:` index through the compressed ChainStream —
    # hottest chains first under the byte/time budgets below — so the
    # router's affinity scoring sees a warm holder from the replica's
    # first request instead of a cold one cratering the fleet hit rate.
    # No-op unless kv_tier_enabled (there is nothing to restore from).
    warm_start_enabled: bool = True
    warm_start_max_bytes: int = 64 * 1024 * 1024   # wire-byte budget
    warm_start_budget_s: float = 5.0               # time budget
    warm_start_max_chains: int = 64                # plan cap (hottest first)

    # Mid-stream generation failover (ISSUE 14): a replica dying
    # mid-decode no longer drops its streams — the proxy re-dispatches
    # each one with a continuation spec (original prompt + the tokens
    # already generated) and the target engine admits it through the
    # ordinary cache-aware path (local prefix match, then kv-tier
    # restore of the dead replica's spilled pages, then suffix-only
    # chunked prefill), resuming decode at the exact next token. Greedy
    # continuations are bit-identical to an uninterrupted run.
    failover_enabled: bool = True
    # resumes allowed per request before degrading to a plain
    # retry-from-scratch (the PR 2 retry path, minus the continuation)
    failover_max_resumes: int = 2

    # Fleet prefill/decode disaggregation (ISSUE 16): long-prompt
    # requests are prefilled on a dedicated prefill pool, the KV chain
    # spills through the tier codec into the CP `kv_tier:` index, and
    # the decode replica restores it as a streamed ChainStream — decode
    # starts while later chunks are still on the wire. The proxy/router
    # take the disagg branch when the request's estimated prefill
    # tokens (prompt minus the best resident prefix match in the decode
    # pool) exceed the threshold; 0 disables the mode entirely. Set by
    # build_disagg_fleet_app on the DECODE deployment's config.
    disagg_prompt_threshold: int = 0
    # serve deployment name of the paired prefill pool (set by the fleet
    # builder on decode configs; None on standalone deployments)
    disagg_prefill_deployment: Optional[str] = None
    # Codec for the disagg handoff wire specifically (the compiled-
    # pipeline channel blobs in disagg.py; the streamed fleet path uses
    # kv_tier_codec so prefill and decode share a tier namespace).
    # "int8" here is governed by the quality policy below.
    disagg_wire_codec: str = "lossless"          # "none"|"lossless"|"int8"
    # Quality policy gating int8 on the disagg wire: whoever turns int8
    # on measures greedy-output divergence (disagg.int8_wire_divergence:
    # fraction of positions where the int8-wire output differs from
    # lossless) and int8 is only policy-approved
    # (disagg.int8_wire_allowed) when it is <= this bound. 0.0 = int8 must
    # be bit-identical to pass (i.e. effectively requires lossless).
    disagg_int8_max_divergence: float = 0.0

    # Prefix-affinity routing (ISSUE 10): cap on the resident page-chain
    # digests each replica exports to the router through the controller
    # long-poll. Low chain positions win the cut (a leading page is what
    # lets the router match any prefix). 512 digests ≈ 16 KB of hex per
    # replica per ship — bounded by construction.
    prefix_summary_max_pages: int = 512

    # sampling defaults (overridable per request)
    max_tokens: int = 128
    temperature: float = 0.0          # 0 = greedy
    top_k: int = 0                    # 0 = full softmax

    # serving
    num_replicas: int = 1
    name: str = "llm"
    ray_actor_options: Optional[dict] = None  # e.g. {"resources": {"TPU": 1}}

    # SLO policy (ISSUE 12): threaded onto the serve DeploymentConfig so
    # the proxy captures critical-path exemplars for requests that blow
    # the objective (observability/attribution.py). None = no check.
    slo_ttft_p99_ms: Optional[float] = None
    slo_e2e_p99_ms: Optional[float] = None
    slo_sample_rate: float = 0.01

    def model(self):
        """The model configuration: any architecture whose module is a
        serving block (models/block.py); a tiny Llama where none is set."""
        if self.model_config is not None:
            return self.model_config
        from ray_tpu.models import llama
        return llama.llama_tiny()
