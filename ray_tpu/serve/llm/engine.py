"""Continuous-batching LLM engine (TPU-native vLLM-engine analog).

Matches the role of the reference's VLLMEngine
(python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:101):
requests enter a waiting queue; the engine loop admits them into fixed
decode slots (prefill), then every iteration runs ONE fused decode step
across all active slots and streams sampled tokens out per request.

TPU-first properties:
- the decode step is a single jitted program with static shapes
  ([max_batch_size] slots, fixed page table width) — compiled once;
- prefill pads prompts to power-of-two length buckets, so at most
  log2(max_prompt_len) prefill programs ever compile;
- KV lives in a paged HBM pool (kv_cache.py) so long and short requests
  share memory; page exhaustion simply delays admission (no OOM);
- sampling (greedy/temperature/top-k) happens on device; only the sampled
  token ids [B] come back to the host each step.

Threading model: the engine owns a single loop thread (the TPU admits one
process; within it one thread drives the device). `submit()` / `drain()` /
`result()` are thread-safe and may be called from replica request handlers.
"""

from __future__ import annotations

import hashlib
import itertools
import logging
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

from ray_tpu.observability import events as _fr
from ray_tpu.serve.llm.config import LLMConfig
from ray_tpu.serve.llm.lead import IdleLead
from ray_tpu.serve.llm.tokenizer import get_tokenizer

logger = logging.getLogger(__name__)


def kv_tier_namespace(cfg: LLMConfig, model_cfg, kv_dtype,
                      rng_seed: int = 0) -> str:
    """Cluster-index namespace for a config's KV pages. A chain digest
    encodes the token prefix, NOT which model computed the KV — two
    architecturally identical models would cross-restore each other's
    pages and silently decode garbage. Scope the index to everything
    that makes KV bytes interchangeable: model id, weights (checkpoint
    path, or the init seed for random weights), architecture config, KV
    dtype, page size. Shared by LLMEngine and the disagg PrefillServer
    (ISSUE 16): both sides deriving the namespace from the same config
    is what lets a prefill replica's spills be visible to decode
    replicas' restores."""
    ident = "|".join([
        str(cfg.model_id),
        str(cfg.checkpoint_path or f"seed:{rng_seed}"),
        repr(model_cfg),
        str(cfg.page_size),
        str(kv_dtype)])
    if cfg.kv_tier_codec == "int8":
        # lossy pages are NOT interchangeable with exact ones: a
        # lossless replica restoring quantized KV would silently break
        # its bit-identity guarantee, so quantized stores index under
        # their own namespace. none<->lossless mix freely (both decode
        # to identical bytes).
        ident += "|int8"
    if getattr(cfg, "tp_degree", 1) > 1:
        # sharding layout is part of the codec identity (ISSUE 20), same
        # precedent as |int8: a TP engine writes mode="shards" blobs
        # split per-KV-head at its tp_degree, and replicas with
        # different layouts index under different namespaces so byte
        # accounting, AB comparisons and fleet warm-starts never mix
        # blob layouts. TP=1 omits the suffix so existing single-chip
        # namespaces — and every already-spilled blob — stay valid.
        ident += f"|tp{int(cfg.tp_degree)}"
    return hashlib.sha256(ident.encode()).hexdigest()[:16]


@dataclass
class _Request:
    request_id: str
    prompt_tokens: list[int]
    max_tokens: int
    temperature: float
    top_k: int
    stop_token: Optional[int]
    # state
    slot: int = -1
    pages: list[int] = field(default_factory=list)
    # a block with window layers: the pages of the slot's ring (the window
    # pool's), and how many ring entries its context has written again
    window_pages: list[int] = field(default_factory=list)
    ring_recycled: int = 0
    waited_state_row: bool = False   # refused once for want of a state row
    generated: list[int] = field(default_factory=list)
    dispatched: int = 0  # tokens whose computation has been dispatched
    prefill_pos: int = 0  # prompt tokens already prefilled (chunked prefill)
    # prompt tokens served from the prefix cache (shared pages; prefill_pos
    # starts here so only the suffix is computed)
    cached_tokens: int = 0
    # cancelled/shed while mid chunked prefill: the loop frees slot+pages
    # promptly via _abort_prefilling instead of finishing the prompt pass
    prefill_cancelled: bool = False
    # speculative decoding: per-request n-gram proposer (spec_decode.py),
    # created lazily on the first draft attempt; spec_inflight marks a slot
    # with an unharvested verify round so the decode path never dispatches
    # it concurrently (its device seq_len is k+1 ahead until rollback)
    spec: Any = None
    spec_inflight: bool = False
    drained_upto: int = 0
    done: bool = False
    error: Optional[str] = None
    submitted_at: float = field(default_factory=time.monotonic)
    # set under the lock when _admit pops this request off _waiting; the
    # submit→admit gap is the queue wait surfaced in result()/engine_stats
    admitted_at: Optional[float] = None
    # KV-tier restore accounting (ISSUE 12 attribution): tokens whose KV
    # came back from the tier, decoded payload size, and the restore
    # wall time (stream open -> finalize; the stream overlaps other
    # requests' work, so wall != loop time — see restore_blocked_ms)
    restored_tokens: int = 0
    restore_bytes: int = 0
    restore_ms: float = 0.0
    # streaming restore (ISSUE 15): the live ChainStream while this
    # request sits in _restoring, plus its attribution split — encoded
    # bytes off the wire, codec decode time, loop time actually spent
    # on this stream (take/decode/inject); overlap = wall - blocked,
    # i.e. how much restore latency hid under other engine work
    restore_stream: Any = None
    restore_started: float = 0.0        # perf_counter at stream open
    restore_page0: int = 0              # first chain slot the stream fills
    restore_pages: int = 0              # pages injected so far
    restore_wire_bytes: int = 0
    restore_decode_ms: float = 0.0
    restore_blocked_ms: float = 0.0
    restore_overlap_ms: float = 0.0
    # stream ended short of its plan (peer death / chunk timeout): the
    # landed pages were kept and the tail re-prefilled (ISSUE 16)
    restore_partial: bool = False
    # fleet disagg handoff (ISSUE 16): the prompt KV was prefilled by a
    # remote prefill replica and registered in the tier before this
    # submit — the restore this request performs IS the handoff, so its
    # wire/overlap numbers feed the disagg engine counters
    disagg: bool = False
    first_token_at: Optional[float] = None
    # inter-token latency: host record-time of the last token plus the
    # per-token gaps (pipelined harvests record blocks in bursts, so the
    # gap distribution shows the streaming cadence a drain() consumer
    # actually sees — k-1 near-zero gaps then one block-sized one)
    last_token_at: Optional[float] = None
    itl_gaps: list[float] = field(default_factory=list)
    finished_at: Optional[float] = None
    done_event: threading.Event = field(default_factory=threading.Event)
    # distributed tracing: carrier captured at submit (the engine loop
    # thread has no ambient span context), wall-clock start for the span
    trace_ctx: Optional[dict] = None
    submitted_wall: float = field(default_factory=time.time)
    # end-to-end request deadline (core/deadline.py, epoch seconds),
    # captured at submit: the admission loop sheds waiting requests whose
    # deadline passed instead of prefilling answers nobody will read
    deadline: Optional[float] = None
    # leading page-chain digests (hex) computed at serve ingress (ISSUE
    # 10): _kv_tier_restore reuses them instead of re-hashing the prompt,
    # after verifying page 0 against a local recompute (a tokenizer
    # mismatch between ingress and engine must degrade to the recompute
    # path, never restore wrong KV)
    ingress_digests: Optional[list] = None
    # mid-stream failover (ISSUE 14): number of already-generated tokens
    # from the dead replica appended to prompt_tokens as a continuation
    # spec. 0 = ordinary request. The admission path is unchanged — the
    # continuation rides the same prefix-match / tier-restore / chunked
    # suffix prefill machinery, and decode resumes at the exact next
    # token (greedy continuations are bit-identical to an uninterrupted
    # run: same KV prefix, same argmax).
    resume_len: int = 0


class LLMEngine:
    def __init__(self, cfg: LLMConfig, params=None, rng_seed: int = 0):
        # the process's start-up ledger (observability/profiling.py): the
        # stages below are stamped where their work happens, and jax's
        # compile events are listened to from the first program on
        from ray_tpu.observability import profiling as profiling_mod
        self._startup = startup = profiling_mod.startup()
        startup.built_on()
        with startup.stage("backend"):
            import jax
            import jax.numpy as jnp

            startup.listen()
            jax.devices()

        from ray_tpu.models.block import block_of, head_major_nbytes
        from ray_tpu.serve.llm import kv_cache as kvc

        self.cfg = cfg
        self.model_cfg = cfg.model()
        # the architecture, through the one seam (models/block.py): its
        # initialiser, its partition rules, and the cache spec that says
        # what the manager holds for it beside pages
        self._block = block_of(self.model_cfg)
        self._cache_spec = self._block.cache_spec(self.model_cfg)
        # a block with slot state: prefix reuse, the kv tier, speculative
        # rollback and disaggregated handoff all restore seq_len and pages
        # only, so none of them happens for it; each is counted where it
        # would have (<x>_bypassed_stateful)
        self._stateful = kvc.has_slot_state(self.model_cfg)
        # a block that generates by diffusion over blocks (block_length B
        # above 1): a slot carries a pending block of B tokens across
        # dispatches, a dispatch runs whole blocks (the denoise passes,
        # the first of which also keeps the block before: _block_impl) and
        # yields B tokens a slot and block, a prefill yields none; the
        # device's seq_lens lag a clean pending block behind the tokens
        # given out. Speculation has no meaning for it; the kv tier and
        # disaggregated hand-off move pages and ONE token, not a pending
        # block: each is turned off or refused, and counted
        # (<x>_bypassed_block, disagg_refused_block)
        self._block_len = int(self._cache_spec.block_length)
        # a block with a latent cache (one row a token, a pool of ONE
        # array): prefix reuse, speculation and everything else that is
        # page bookkeeping work as they are; the kv tier's and the
        # hand-off's host blobs are pairs of K and V pages, so each is
        # turned off or refused, and counted (kv_tier_bypassed_latent,
        # disagg_refused_latent)
        self._latent = kvc.has_latent_cache(self.model_cfg)
        # a block with window layers: a window layer's pages are a ring
        # that is written again while the sequence lives, so a page of it
        # never holds a prefix for another sequence, a spilled chain, a
        # draft to roll back or a hand-off: prefix reuse, the kv tier,
        # speculation and disaggregated hand-off are off or refused, and
        # counted (<x>_bypassed_window, disagg_refused_window). Its slots
        # hold pages of TWO kinds, each with its allocator: the growing
        # table's and the ring's (_ring_pages entries, kv_cache.ring_pages
        # of the window and the widest span a prompt pass writes)
        self._windowed = kvc.has_window_layers(self.model_cfg)
        self._ring_pages = 0
        if self._windowed:
            span = cfg.prefill_chunk if cfg.prefill_chunk > 0 \
                else cfg.max_prompt_len
            self._ring_pages = kvc.ring_pages(
                self._cache_spec.window, cfg.page_size, span)
            if -(-self.model_cfg.max_seq_len // cfg.page_size) \
                    != -(-cfg.max_seq_len // cfg.page_size):
                raise ValueError(
                    f"max_seq_len={cfg.max_seq_len} and the model's "
                    f"max_seq_len={self.model_cfg.max_seq_len} differ in "
                    f"pages: a block with window layers reads the width of "
                    f"the full layers' table off its configuration")
            if self._block_len > 1:
                raise ValueError("window layers under generation by "
                                 "diffusion over blocks are not written")
        if self._block_len > 1:
            for name in ("page_size", "max_seq_len", "prefill_chunk"):
                if getattr(cfg, name) % self._block_len:
                    raise ValueError(
                        f"{name}={getattr(cfg, name)} is no multiple of "
                        f"the model's block length {self._block_len}: "
                        f"page, chunk and sequence edges must be block "
                        f"edges")
        self.tokenizer = get_tokenizer(cfg.tokenizer)
        self._jax = jax
        self._jnp = jnp
        self._kvc = kvc
        # Paged-attention backend, resolved ONCE (ops/paged_attention.py
        # fused kernels vs the materialized-gather path). Static for the
        # engine's lifetime: it's baked into every compiled program, and
        # resolving here keeps the jitted impls free of backend probing.
        self._tp = max(1, int(getattr(cfg, "tp_degree", 1)))
        self._attn_backend = kvc.resolve_attention_backend(
            cfg.attention_kernel, self.model_cfg, cfg.page_size, self._tp)

        # (each of the three stages waits for its arrays: the device runs
        # them in the order it was given them either way, and the host
        # work that follows each is far longer than what it waits for)
        with startup.stage("weights"):
            if params is None:
                if cfg.checkpoint_path:
                    params = self._block.load_params(cfg.checkpoint_path,
                                                     self.model_cfg)
                else:
                    params = self._block.init_params(
                        jax.random.PRNGKey(rng_seed), self.model_cfg)
            jax.block_until_ready(params)
        # the block's served form (models/block.py ``serve_params``), made
        # once, a leaf at a time, and the checkpoint's form let go before
        # the pool is allocated: no program re-lays a weight again
        with startup.stage("serve_form"):
            self.params = self._block.serve_params(params, self.model_cfg)
            del params
            jax.block_until_ready(self.params)
        self._weights_head_major = head_major_nbytes(self.params)

        b = cfg.max_batch_size
        self.max_pages_per_seq = -(-cfg.max_seq_len // cfg.page_size)
        # (rows a slot of the widest pass: a token, or two blocks)
        rows = b * (2 * self._block_len if self._block_len > 1 else 1)
        if self._cache_spec.routed_layers \
                and rows > self.model_cfg.max_seq_len:
            # the routing record holds a call's rows, max_seq_len of them
            raise ValueError(
                f"max_batch_size={b} ({rows} rows a pass) exceeds the "
                f"model's max_seq_len={self.model_cfg.max_seq_len}: a "
                f"routed block records its choice of experts for at most "
                f"max_seq_len rows a call")
        # (the window pool: a ring a slot and the trash page)
        pool_t0 = time.monotonic()
        # (a state pool a SLOT, models/block.py ``state_per_slot``: a row a
        # slot and the trash row; a sequence's row is its first page, which
        # the allocator hands from the reserved range 1..b)
        self._state_rows = b if self._cache_spec.state_per_slot else 0
        if self._state_rows and cfg.num_pages <= b:
            raise ValueError(
                f"num_pages={cfg.num_pages}: a block whose state pool has "
                f"a row a slot reserves max_batch_size={b} first pages")
        self.kv = kvc.init_paged_cache(
            self.model_cfg, cfg.num_pages, cfg.page_size, self._tp,
            window_pages=b * self._ring_pages + 1 if self._windowed else 0,
            state_rows=self._state_rows + 1 if self._state_rows else 0)
        # Tensor parallelism (ISSUE 20): one engine process drives a
        # tp_degree-chip "tensor" mesh. Weights get Megatron-style
        # partition-rule shardings (parallel/sharding.py — the SAME
        # match_partition_rules train/spmd.py uses), the page pool is
        # split per-KV-head, and everything else about the engine — the
        # loop, the allocator, page tables, the tier — keeps operating on
        # whole-replica logical state. tp_degree=1 builds no mesh and
        # compiles the exact single-chip programs (bit-identical to a
        # pre-TP engine).
        self._mesh = None
        if self._tp > 1:
            self._mesh = self._setup_tp_mesh()
        jax.block_until_ready(self.kv)
        startup.stamp("pool", pool_t0, time.monotonic())
        # the devices this engine's programs run on: the TP mesh's, or the
        # process default device. attn_interpret: the pallas kernels run in
        # the Pallas interpreter (any backend but TPU) — never a timing
        self._devices = (list(self._mesh.devices.flat) if self._mesh
                         is not None else jax.devices()[:1])
        from ray_tpu.ops import paged_attention as paged_ops
        self._attn_interpret = int(self._attn_backend == "pallas"
                                   and paged_ops.interpret_default())
        # the call kinds of this engine's programs whose kernel body walks
        # a slot's LIVE pages (ops/paged_attention.py WALKS_LIVE): where
        # attn_live_pages_total / attn_table_pages_total is the share of
        # a table a call reads
        self._attn_walks_live = paged_ops.walking_calls(
            bool(self._cache_spec.latent_dim), self._block_len,
            self._windowed) \
            if self._attn_backend == "pallas" else []
        # and those whose kernel also WRITES the call's own rows of K and
        # V, which nothing scatters before it (kv_cache._write_read)
        self._attn_writes_in_kernel = paged_ops.writing_calls(
            bool(self._cache_spec.latent_dim), self._block_len,
            self._windowed, self._tp) \
            if self._attn_backend == "pallas" else []
        # and those whose kernel takes a learned SINK of some layer of the
        # block (models/block.py ``LayerDef.sink``): the calls that walk
        self._attn_sink_calls = list(self._attn_walks_live) if any(
            ld.sink for ld in self._block.serve_layers(self.model_cfg) or ()
        ) else []
        # performance introspection (observability/profiling.py): phase
        # timers + ITL ring gate on cfg.profiling_enabled; compile-event
        # tracking is always on (work only on first-dispatch-per-shape).
        # Weights/KV-pool byte accounting is shape*dtype math — the KV
        # pool is donated every step but its layout never changes.
        self._prof = profiling_mod.EngineProfiler(
            enabled=bool(cfg.profiling_enabled))
        self._prof.set_memory_layout(
            profiling_mod.tree_bytes(self.params),
            profiling_mod.tree_bytes(self.kv))
        self._state = kvc.state_nbytes(self.kv)
        # Prefix caching (see kv_cache.PageAllocator): all bookkeeping is
        # host-side between steps — the page table indirection means shared
        # pages change WHICH pool pages a slot reads, never the compiled
        # programs or their shapes.
        self._prefix_cache_on = bool(cfg.prefix_cache_enabled) \
            and not self._stateful and not self._windowed
        # one-shot log guard: ingress digests disagreeing with the local
        # recompute (tokenizer skew) warns once, not once per request
        self._ingress_skew_warned = False
        self.allocator = kvc.PageAllocator(
            cfg.num_pages, cache_pages=cfg.prefix_cache_max_pages,
            first_pages=self._state_rows)
        # the rings' pages (a block with window layers), and the width of
        # a slot's page table: the growing table, then its ring table
        self.window_allocator = kvc.PageAllocator(
            b * self._ring_pages + 1) if self._windowed else None
        self._table_width = self.max_pages_per_seq + self._ring_pages
        self.page_tables = np.zeros((b, self._table_width), np.int32)
        self.seq_lens = np.zeros((b,), np.int32)
        self.slot_req: list[Optional[_Request]] = [None] * b
        self.free_slots = list(range(b))

        self._lock = threading.Lock()
        self._waiting: list[_Request] = []
        # chunked prefill: admitted (slot+pages held) but prompt not fully
        # prefilled; the loop dispatches one chunk per request per iteration
        # interleaved with decode blocks, so a long admission never stalls
        # active generations for its whole prompt pass
        self._prefilling: list[_Request] = []
        # streaming tier restore (ISSUE 15): admitted (slot+pages held),
        # restore stream open — the loop decodes+injects landed chunks
        # (_restore_steps) and routes each request on to its suffix
        # prefill when the stream ends (fully or partially)
        self._restoring: list[_Request] = []
        self._requests: dict[str, _Request] = {}
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._rng = jax.random.PRNGKey(rng_seed + 1)
        self._loop_thread: Optional[threading.Thread] = None
        # set once if the loop thread dies on an exception: every request
        # it stranded was failed with this message, submit() refuses new
        # ones, and LLMServer.check_health reports the replica unhealthy
        self.loop_error: Optional[str] = None
        self.stats = {"steps": 0, "prefills": 0, "tokens_out": 0,
                      "requests": 0, "shed_expired": 0,
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_hit_tokens": 0,
                      "spilled_pages": 0, "restored_pages": 0,
                      "tier_hit_tokens": 0, "restore_partial": 0,
                      "spec_rounds": 0, "spec_drafted_tokens": 0,
                      "spec_accepted_tokens": 0,
                      "failover_resumed": 0, "failover_restored_tokens": 0,
                      "disagg_prefills": 0, "handoff_bytes_wire": 0,
                      "handoff_overlap_ms": 0.0,
                      "warm_start_pages": 0, "warm_start_ms": 0.0,
                      # per-kernel dispatch counters (ISSUE 18): how many
                      # decode / verify / chunk programs — each containing
                      # the resolved attention backend's kernels — this
                      # engine dispatched; paired with attention_backend
                      # so a fleet mixing gather/pallas replicas is
                      # visible per replica
                      "attn_decode_dispatches": 0,
                      "attn_verify_dispatches": 0,
                      "attn_chunk_dispatches": 0,
                      # a program's tail does only what is used (ISSUE
                      # 56): chunk dispatches that armed no slot and so
                      # ran no head (of attn_chunk_dispatches), and decode
                      # / block dispatches none of whose rows asked for a
                      # temperature, so nothing was drawn (of
                      # attn_decode_dispatches; a verify round only ever
                      # holds greedy slots)
                      "chunk_heads_skipped": 0, "greedy_dispatches": 0,
                      # routed experts (0 for a block without them): over
                      # the decode blocks harvested, distinct experts
                      # touched and (token, expert) rows multiplied, summed
                      # over routed layers and steps, the times one
                      # grouped product passed an expert's matrix through
                      # the MXU (1 an expert with rows, 1 more for each
                      # further 128 rows of it), and those layer-steps
                      "experts_touched_total": 0, "expert_rows_total": 0,
                      "expert_visits_total": 0,
                      "routed_layer_steps_total": 0,
                      # what a block with slot state does not take part
                      # in: admissions that skipped the prefix index / the
                      # tier, requests speculation would have served,
                      # disaggregated calls refused
                      "prefix_bypassed_stateful": 0,
                      "kv_tier_bypassed_stateful": 0,
                      "spec_bypassed_stateful": 0,
                      "disagg_refused_stateful": 0,
                      "admission_waited_state_row": 0,
                      # generation by diffusion over blocks (0 for a block
                      # a step of which yields a token), over the block
                      # dispatches harvested: passes run (``steps`` counts
                      # them too; a pass of two blocks is ONE), of them
                      # passes that denoise, passes that kept a block AND
                      # denoised the next (counted on the device), commits
                      # that ran alone (none since the commit is deferred),
                      # live slots summed over passes, blocks committed
                      # (slots x blocks, counted on the device), tokens
                      # revealed and discarded past a stop token or
                      # max_tokens; and what such a block is kept out of
                      "block_passes_total": 0, "denoise_passes_total": 0,
                      "fused_passes_total": 0,
                      "commit_passes_total": 0, "slot_passes_total": 0,
                      "blocks_committed_total": 0, "tokens_cut_total": 0,
                      "spec_bypassed_block": 0,
                      "kv_tier_bypassed_block": 0,
                      "disagg_refused_block": 0,
                      # what a block with a latent cache is kept out of
                      "kv_tier_bypassed_latent": 0,
                      "disagg_refused_latent": 0,
                      # what a block with window layers is kept out of,
                      # and the ring entries its slots have written again
                      # (a page whose tokens no later query can see)
                      "prefix_bypassed_window": 0,
                      "kv_tier_bypassed_window": 0,
                      "spec_bypassed_window": 0,
                      "disagg_refused_window": 0,
                      "window_pages_recycled_total": 0,
                      # dispatches that found the device with nothing
                      # queued although slots were live, and the seconds
                      # since the loop last knew it busy (_dry): an UPPER
                      # bound of the idle they stand for
                      "dry_dispatches_total": 0, "dry_s_total": 0.0,
                      # decode / block dispatches by the tier that chose
                      # their k (_select_block): admissions blocked (one
                      # step), requests queue for slots (the pressure
                      # tier), nothing waits (the idle tier, whose k is
                      # ``idle_lead_k``: lead.py)
                      "dispatch_tier_admit_total": 0,
                      "dispatch_tier_pressure_total": 0,
                      "dispatch_tier_idle_total": 0,
                      # over the decode / block dispatches: the table
                      # pages that hold keys of the dispatch's first step
                      # (what a kernel that follows a slot's live length
                      # walks), and active slots x the table's width
                      "attn_live_pages_total": 0,
                      "attn_table_pages_total": 0}
        # Tiered KV cache (kv_tier.py): evicted cached page chains spill
        # host-side into a shm/disk tier + cluster index instead of dying,
        # and _admit extends its longest-match search past the local index
        # into the tier. The allocator hook only CAPTURES evictions and
        # dispatches one device gather (stream-ordered before any reuse of
        # the pages); the host copy + object-store put happen later on the
        # loop, off the admission hot path (_kv_tier_flush).
        self._kv_tier_on = bool(cfg.kv_tier_enabled) \
            and self._prefix_cache_on and self._block_len == 1 \
            and not self._latent
        self._kv_tier = None
        self._tier_pending: list = []  # [(dev_k, dev_v, [(page, dig, pos)])]
        # drain-time eager spill handshake (ISSUE 14): spill_inflight()
        # parks one (done_event, result_box) here and the loop performs
        # the gather+flush — the device stream has exactly one driver
        self._spill_req: Optional[tuple] = None
        # cache-warm scale-up handshake (ISSUE 17): warm_start() parks
        # (done_event, result_box, max_bytes, budget_s) here — same
        # one-driver discipline; the restore injections run on the loop
        self._warm_req: Optional[tuple] = None
        if self._kv_tier_on:
            from ray_tpu.serve.llm import kv_tier as kvt
            self._kv_tier = kvt.KVTierStore(
                max_bytes=cfg.kv_tier_max_bytes,
                disk_dir=cfg.kv_tier_disk_dir,
                disk_max_bytes=cfg.kv_tier_disk_max_bytes,
                ttl_s=cfg.kv_tier_ttl_s,
                page_size=cfg.page_size,
                namespace=kv_tier_namespace(
                    cfg, self.model_cfg, kvc.pool_dtype(self.kv), rng_seed),
                codec=cfg.kv_tier_codec,
                # per-shard encoded sub-payloads under ONE chain digest
                # (ISSUE 20): the namespace above already carries |tp{N}
                # so layouts never mix across stores
                shards=self._tp)
            self.allocator.spill_hook = self._spill_capture
        # Speculative decoding (spec_decode.py + the verify-k program
        # below): host-side n-gram drafts verified k-at-a-time in one
        # fused dispatch. Greedy-only guarantee — non-greedy slots are
        # never drafted and ride the normal decode path.
        self._spec_on = bool(cfg.spec_decode_enabled) \
            and not self._stateful and self._block_len == 1 \
            and not self._windowed
        # last decode-block k actually dispatched + live pipeline depth
        # (engine_stats gauges: the k=1/pressure/idle tier transitions are
        # observable instead of inferred from throughput wiggles), and
        # the tier that chose it
        self._last_block = 0
        self._last_tier = "idle"
        # the idle tier's k (lead.py): the smallest of the three
        # tiers until the loop sees the device run dry with it, then the
        # next; decode_block at most
        self._collector = profiling_mod.watch_gc()
        self._lead = IdleLead(
            (self._blocks_of(1),
             self._blocks_of(min(cfg.pressure_decode_block,
                                 cfg.decode_block)),
             self._blocks_of(cfg.decode_block)),
            gc_n=self._collector.pause_n)
        # Pipelined decode (vLLM-style async token processing): each step's
        # input tokens are the previous step's on-device output, so steps
        # dispatch back-to-back without a host sync — the host harvests
        # sampled tokens at most PIPELINE_DEPTH entries behind (_step holds
        # the bound). Token latency then tracks step execution time
        # instead of the host<->device round trip.
        self.PIPELINE_DEPTH = cfg.pipeline_depth
        # [(dev_tokens, [(col, slot, req)], k, seq, dev_touched)]; seq
        # numbers decode and verify blocks (the same in a block's dispatch
        # and harvest spans), -1 for a prefill's first token; dev_touched:
        # the device count of experts a decode block's steps touched (a
        # block with routed experts; a block program's counts, of which
        # that is the first), None otherwise
        self._pending: list = []
        self._block_seq = 0
        # the run-dry watch (_dry): an output of the NEWEST program put on
        # the device stream, of any kind; the last moment the loop saw it
        # not ready or dispatched; and whether the loop has parked in
        # loop_wait (or not run at all) since the last dispatch
        self._newest = None
        self._busy_seen = 0.0
        self._parked = True
        # the token every slot's next step consumes: [B+1] on device (row b
        # is the trash row, below). Only programs write it: a decode block
        # or verify round its last samples, a prefill (or last prefill
        # chunk) its first token at the row of the slot it arms. With a
        # block length B above 1 a row is the slot's PENDING BLOCK [B]:
        # known tokens, the mask token elsewhere.
        state_t0 = time.monotonic()
        self._dev_tokens = jnp.zeros(
            (b + 1,) + ((self._block_len,) if self._block_len > 1 else ()),
            jnp.int32)
        # slot -> a token the HOST knows and the device row does not hold:
        # a verify round's rollback, a disaggregated adoption. Host ints
        # only (a device value here would need an eager op to place).
        self._overrides: dict[int, int] = {}
        # device-resident decode state (page tables / seq lens / temps);
        # slot admissions mark entries dirty and patch them with one small
        # update before the next dispatch. Row b (one past the last slot)
        # is a PERMANENT TRASH ROW: bucketed dispatch pads its packed slot
        # index vector with it, so padding lanes write into the trash page
        # (page-table row of zeros) instead of any live slot's KV.
        self._pt_dev = jnp.zeros((b + 1, self._table_width), jnp.int32)
        self._sl_dev = jnp.zeros((b + 1,), jnp.int32)
        self._temps_dev = jnp.zeros((b + 1,), jnp.float32)
        if self._mesh is not None:
            # replicate-commit the small decode state on the TP mesh so
            # the donated state buffers keep one deterministic layout
            # step to step (uncommitted operands would let each program's
            # first compile pick, and donation would then pin whatever it
            # guessed)
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = NamedSharding(self._mesh, P())
            self._pt_dev, self._sl_dev, self._temps_dev, self._dev_tokens = \
                jax.device_put((self._pt_dev, self._sl_dev, self._temps_dev,
                                self._dev_tokens), rep)
        # (the device state beside the pool: the stage's second stamp)
        startup.stamp("pool", state_t0, time.monotonic())
        self._dirty_slots: dict[int, tuple] = {}  # slot -> (seq_len, temp)

        # jitted programs. The KV pool is DONATED, and every paged program
        # (kv_cache.paged_*) carries it through its loops as a carry that
        # is only ever scattered into — the scan over layers, and around it
        # here the loop over a block's steps — so the donated argument, the
        # loop carries and the returned pool are ONE buffer: no program
        # copies, slices or restacks the pool, and a program's temporaries
        # are megabytes beside the pool's gigabytes (tests/test_pool_carry.py
        # holds the structure). Without donation every dispatch would
        # materialize a second full pool. The decode program gathers the
        # packed active rows by index on device, runs the fused block at the
        # PACKED width, and scatters the carried state back — one program
        # a bucket width, so a lightly loaded engine pays for the requests
        # it has, not for max_batch_size. The number of steps (``n``) is an
        # operand: the tiers of k are dispatches of the width's one program.
        # With a block length above 1 the same slot in the loop holds the
        # block program (_block_impl: n whole blocks a dispatch), whose
        # last output is its counts (experts touched, fused passes, blocks
        # kept) where a routed decode block's is its experts touched.
        decode_impl = self._block_impl if self._block_len > 1 \
            else self._decode_impl
        self._decode = jax.jit(
            lambda params, kv, pt, sl, toks, rng, temp, idx, n:
            decode_impl(params, kv, pt, sl, toks, rng, temp, idx, n),
            donate_argnums=(1, 3, 4))
        # verify-k (speculative decoding): same packed-width shape as
        # _decode, but the scan consumes the DRAFTED tokens instead of its
        # own samples; the draft length is static via drafts.shape — one
        # verify program per bucket width, ever.
        self._verify = jax.jit(
            lambda params, kv, pt, sl, toks, rng, temp, idx, drafts:
            self._verify_impl(params, kv, pt, sl, toks, rng, temp, idx,
                              drafts),
            donate_argnums=(1, 3, 4))
        self._prefill_cache: dict[int, Any] = {}
        # host pages into the donated pool (_inject_host_pages: the tier's
        # restore and warm start, disagg's adoption) at ONE fixed shape; an
        # eager per-count scatter would compile per distinct page count
        self._inject_kv = jax.jit(kvc.scatter_pages, donate_argnums=(0,))
        # Slot-state patches run at ONE fixed shape (B+1 rows, trash-row
        # padded) through these jitted fns. Eager .at[idx].set() with a
        # dirty-count-sized idx compiled a fresh scatter per distinct count
        # — observed as multi-second TTFT stalls early in every serving
        # run while counts 1,2,3,... were each seen for the first time.
        self._patch_state = jax.jit(
            lambda pt, sl, temps, idx, ptv, slv, tv: (
                pt.at[idx].set(ptv), sl.at[idx].set(slv),
                temps.at[idx].set(tv)),
            donate_argnums=(0, 1, 2))
        self._patch_toks = jax.jit(
            lambda toks, idx, vals: toks.at[idx].set(vals),
            donate_argnums=(0,))

        # The sampling key of a prefill, split off the loop's key by ONE
        # two-output program: an eager split is a primitive applied on the
        # loop thread, and unpacking its result slices a device array
        # twice more. Same values as ``key, sub = jax.random.split(key)``.
        def split_key(key):
            pair = jax.random.split(key)
            return pair[0], pair[1]

        self._split_key = jax.jit(split_key)

    # ---- tensor parallelism (ISSUE 20) ---------------------------------
    def _setup_tp_mesh(self):
        """Build the tp_degree-device "tensor" mesh and commit the engine's
        device state to it: params via the block's serve partition rules,
        the KV pool by its module's spec (per KV head). Committed
        (device_put) shardings are what make every later jit — decode /
        verify / prefill / tier-inject — compile as a partitioned program
        without per-call annotations; donation then keeps the buffers
        sharded in place across steps. Small host-born operands (token
        patches, restore blobs) stay uncommitted and are resharded by the
        compiled programs' input layouts."""
        jax = self._jax
        from jax.sharding import NamedSharding

        from ray_tpu.parallel import sharding as shd
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        tp = self._tp
        self._block.check_tp_divides(self.model_cfg, tp)
        devices = jax.devices()
        if len(devices) < tp:
            raise ValueError(
                f"tp_degree={tp} needs {tp} devices, have {len(devices)}")
        mesh = build_mesh(MeshSpec(tensor=tp), devices[:tp])
        self.params = jax.device_put(
            self.params,
            shd.rule_shardings(self._block.serve_partition_rules(),
                               self.params, mesh))
        self.kv = jax.device_put(
            self.kv, NamedSharding(mesh, self._kvc.pool_spec()))
        logger.info("TP mesh up: %s over %d devices", dict(mesh.shape), tp)
        return mesh

    # ---- compiled impls ------------------------------------------------
    def _run_steps(self, one, num, carry, counts):
        """``num`` iterations of ``one`` in one ``lax.while_loop``. ``num``
        is an OPERAND of the program (a traced int32 scalar), not a static
        argument: the tiers of k (_select_block) are dispatches of ONE
        program a width, whose loop body is traced, lowered and compiled
        once. ``one(carry)`` gives (carry, the iteration's tokens, its
        counts): the tokens go to row i of a buffer of the CEILING tier's
        rows (_blocks_of(decode_block); the rows past ``num`` stay zeros
        the host never reads), the counts (int32 like ``counts``, or None)
        are summed. The carry holds the donated pool as a scan's would, so
        it still aliases (tests/test_pool_carry.py). Returns what a
        ``lax.scan(one, carry, length=num)`` returns, bit for bit: the
        carry, the tokens [rows, ...] and the summed counts."""
        jax, jnp = self._jax, self._jnp
        rows = self._blocks_of(self.cfg.decode_block)

        def body(state):
            i, carry, buf, total = state
            carry, toks, n = one(carry)
            return (i + 1, carry,
                    jax.lax.dynamic_update_index_in_dim(buf, toks, i, 0),
                    jax.tree.map(jnp.add, total, n))

        toks0 = carry[2]            # a step's tokens: a row of the buffer
        _, carry, buf, counts = jax.lax.while_loop(
            lambda state: state[0] < num, body,
            (jnp.int32(0), carry,
             jnp.zeros((rows,) + toks0.shape, toks0.dtype), counts))
        return carry, buf, counts

    def _decode_one(self, params, pt, temps, idx):
        """One decode step at the packed width as a function of the carry
        (pool, lengths, tokens, key): the body of the decode program's
        loop. Gives the carry after the step, the step's tokens [W] and,
        for a routed block, what it touched (_experts_touched; else
        None)."""
        jax = self._jax

        @jax.named_scope("decode_step")
        def one(carry):
            kv_c, lens, toks, key = carry
            key, sub = jax.random.split(key)
            logits, kv_c, lens = self._kvc.paged_decode_step(
                params, kv_c, pt, lens, toks, self.model_cfg,
                self.cfg.page_size, self._attn_backend, mesh=self._mesh)
            toks = self._kvc.sample_tokens(
                logits, sub, temps, self.cfg.top_k)
            return (kv_c, lens, toks, key), toks, \
                self._experts_touched(kv_c, idx)

        return one

    def _decode_impl(self, params, kv, pt_full, sl_full, toks_full, rng,
                     temps_full, idx, num_steps):
        """``num_steps`` fused decode iterations in ONE program (_run_steps:
        the count is an operand), at the PACKED width ``len(idx)``.

        Every host->device dispatch has a fixed cost; fusing K steps
        amortizes it to 1/K per token (the standard TPU serving shape —
        cf. multi-step decode in TPU LLM stacks). ``idx``
        selects the active slots (padded with the trash row); the gather /
        scatter of the [W]-sized state stays on device. Returns the
        sampled tokens [_blocks_of(decode_block), W], of which rows
        [:num_steps] are the dispatch's, plus the full-size carried
        state."""
        jax = self._jax
        jnp = self._jnp
        # named scopes (compile-time metadata): a trace names the
        # program's device ops decode_block/{gather_state, decode_step/
        # <layer scope>, scatter_state}
        with jax.named_scope("decode_block"):
            with jax.named_scope("gather_state"):
                pt = pt_full[idx]
                lens0 = sl_full[idx]
                toks0 = toks_full[idx]
                temps = temps_full[idx]
            (kv, new_lens, last, rng), all_toks, touched = self._run_steps(
                self._decode_one(params, pt, temps, idx), num_steps,
                (kv, lens0, toks0, rng),
                # (what a routed block's steps touched: _experts_touched)
                jnp.zeros((2,), jnp.int32)
                if self._cache_spec.routed_layers else None)
            # padding lanes must not accumulate garbage into the trash row
            # (its seq_len would creep toward int32 overflow on a
            # long-lived engine): pin it back to zero on scatter
            trash = self.cfg.max_batch_size
            with jax.named_scope("scatter_state"):
                sl_full = sl_full.at[idx].set(
                    jnp.where(idx == trash, 0, new_lens))
                toks_full = toks_full.at[idx].set(last)
        if touched is not None:
            # a routed block: one more output, harvested with the tokens
            return all_toks, toks_full, kv, sl_full, rng, touched
        return all_toks, toks_full, kv, sl_full, rng

    def _experts_touched(self, kv, idx, rows_a_slot: int = 1):
        """What the routed layers of the last decode step (or block pass:
        ``rows_a_slot`` rows a slot, slot-major) did, summed over them
        (int32 [2]); None for a block without routed experts. [0]: the
        distinct experts the LIVE rows chose (``idx``: the packed slot
        index, whose padding lanes, the trash row, do not count). [1]: the
        visits of one grouped product (parallel/expert.product_visits over
        EVERY row of the call, as the kernel sees them): an expert with
        rows is one, and one more for each further 128 rows of it. Both
        are DERIVED here from the routing record; [1] is the kernel's rule
        applied to it, not a count the kernel reports."""
        spec = self._cache_spec
        if not spec.routed_layers:
            return None
        jnp = self._jnp
        from ray_tpu.parallel import expert
        with self._jax.named_scope("experts_touched"):
            live = idx != self.cfg.max_batch_size                    # [W]
            if rows_a_slot > 1:
                live = jnp.repeat(live, rows_a_slot)
            chosen = kv["routing"][:, :live.shape[0]]           # [L_r, W, k]
            hot = chosen[..., None] == jnp.arange(spec.n_experts)
            hit = jnp.any(hot & live[None, :, None, None], axis=(1, 2))
            sizes = jnp.sum(hot, axis=(1, 2), dtype=jnp.int32)    # [L_r, E]
            return jnp.stack([jnp.sum(hit, dtype=jnp.int32),
                              expert.product_visits(
                                  sizes, live.shape[0] * spec.top_k)])

    def _block_one(self, params, pt, temps, idx):
        """One whole block a slot at the packed width as a function of the
        carry (pool, lengths, pending blocks, key): the body of the block
        program's loop (generation by diffusion over blocks; the model's
        block length B, ``denoise_passes`` S and ``reveal_per_pass`` n).

        A block costs S passes: THE COMMIT IS DEFERRED. An iteration
        is one pass over 2B positions a slot (kv_cache.
        paged_block_pair_step: the slot's pending block, then an
        all-masked one) and S - 1 denoise passes of B
        (kv_cache.paged_block_step without commit), and yields one CLEAN
        block a slot, which stays the slot's pending block in
        ``toks_full``, uncommitted, its tokens already on their way to the
        host. What the pass of 2B is for a slot is read from the slot's
        pending block, inside the program:

        * clean (no position holds the mask token: every slot after its
          first iteration): the pass keeps that block's K / V (the slot
          advances by B) and is the first denoise pass of the next block,
          which sees exactly the cache a commit pass would have left;
        * not clean (what a prefill or chunk program left: the prompt's
          ``true_len % B`` tokens, then masks): the pass denoises that
          block as a pass of B would, keeps nothing, and its second half
          is filler (its K / V junk, its hidden states dropped).

        Every denoise pass ends in the head over B positions a slot and
        ``unmask``: of the positions that still hold the mask token, the n
        whose sampled token is most probable take that token; greedy at
        temperature 0; the mask token's own logit is left out, it is never
        produced. The last block of a stream is never committed: nothing
        reads it. Gives the carry after the block, the clean blocks [W, B]
        and the iteration's counts, int32 [4]: experts touched over its
        passes (0 without routed experts), whether its pass of 2B kept a
        block for a live slot and denoised the next, blocks kept (live
        slots summed), and the visits of the routed layers' grouped
        products (_experts_touched)."""
        jax = self._jax
        jnp = self._jnp
        mcfg = self.model_cfg
        b, mask_id = self._block_len, self._cache_spec.mask_token
        n_reveal = mcfg.reveal_per_pass
        live = idx != self.cfg.max_batch_size

        how = dict(cfg=mcfg, page_size=self.cfg.page_size,
                   attn_backend=self._attn_backend, mesh=self._mesh)

        def touched(kv_c, rows_a_slot):
            n = self._experts_touched(kv_c, idx, rows_a_slot)
            return jnp.zeros((2,), jnp.int32) if n is None else n

        def unmask(logits, blk, key):
            """blk [W, B] with n more of its masked positions revealed."""
            vocab = logits.shape[-1]
            logits = jnp.where(jnp.arange(vocab) == mask_id, -jnp.inf,
                               logits)
            # (the sampler draws its noise over B x the vocabulary a slot
            # only where a slot of the dispatch asks for a temperature)
            tok = self._kvc.sample_tokens(
                logits, key, jnp.broadcast_to(temps[:, None], blk.shape),
                self.cfg.top_k).astype(blk.dtype)
            conf = jnp.exp(
                jnp.take_along_axis(logits, tok[..., None], axis=-1)[..., 0]
                - jax.nn.logsumexp(logits, axis=-1))              # [W, B]
            masked = blk == mask_id
            _, best = jax.lax.top_k(jnp.where(masked, conf, -1.0), n_reveal)
            reveal = jnp.any(best[..., None] == jnp.arange(b), axis=1)
            return jnp.where(reveal & masked, tok, blk)

        @jax.named_scope("block_step")
        def one(carry):
            kv_c, lens, blk, key = carry
            fresh = jnp.full_like(blk, mask_id)
            key, sub = jax.random.split(key)
            with jax.named_scope("fused"):
                logits, kv_c, lens, kept = \
                    self._kvc.paged_block_pair_step(
                        params, kv_c, pt, lens,
                        jnp.concatenate([blk, fresh], axis=1), **how)
                with jax.named_scope("unmask"):
                    blk = unmask(
                        logits, jnp.where(kept[:, None], fresh, blk),
                        sub)
                n_touched = touched(kv_c, 2 * b)
            for _s in range(mcfg.denoise_passes - 1):
                key, sub = jax.random.split(key)
                with jax.named_scope("denoise"):
                    logits, kv_c, _ = self._kvc.paged_block_step(
                        params, kv_c, pt, lens, blk, **how,
                        commit=False)
                    with jax.named_scope("unmask"):
                        blk = unmask(logits, blk, sub)
                    n_touched += touched(kv_c, b)
            kept = kept & live
            return (kv_c, lens, blk, key), blk, jnp.stack(
                [n_touched[0], jnp.any(kept).astype(jnp.int32),
                 jnp.sum(kept, dtype=jnp.int32), n_touched[1]])

        return one

    def _block_impl(self, params, kv, pt_full, sl_full, toks_full, rng,
                    temps_full, idx, num_blocks):
        """The block program: ``num_blocks`` whole blocks (_block_one) in
        ONE program, at the packed width W = ``len(idx)``, as _decode_impl
        is ``num_steps`` steps (_run_steps: the count is an operand).
        ``toks_full`` [B+1 rows, B]: every slot's pending block. Returns
        the blocks' tokens as [rows x B, W], rows the ceiling tier's blocks
        (row j x B + i: position i of block j, known tokens included: the
        host skips the ones a prompt left, and reads no row past
        ``num_blocks`` x B), the carried state, and the program's counts
        (_block_one's, summed over the blocks)."""
        jax = self._jax
        jnp = self._jnp
        with jax.named_scope("block_program"):
            with jax.named_scope("gather_state"):
                pt = pt_full[idx]
                lens0 = sl_full[idx]
                blk0 = toks_full[idx]
                temps = temps_full[idx]
            (kv, new_lens, last, rng), blocks, counts = self._run_steps(
                self._block_one(params, pt, temps, idx), num_blocks,
                (kv, lens0, blk0, rng), jnp.zeros((4,), jnp.int32))
            all_toks = jnp.swapaxes(blocks, 1, 2).reshape(
                blocks.shape[0] * self._block_len, -1)     # [rows x B, W]
            with jax.named_scope("scatter_state"):
                sl_full = sl_full.at[idx].set(
                    jnp.where(idx != self.cfg.max_batch_size, new_lens, 0))
                toks_full = toks_full.at[idx].set(last)
        return all_toks, toks_full, kv, sl_full, rng, counts

    def _pending_block(self, tokens, start, true_len):
        """The block a prompt leaves pending (traced; a prefill or chunk
        program's tail): the ``true_len % B`` tokens past its whole blocks,
        read out of ``tokens`` [1, C] (which starts at position ``start``),
        then the mask token."""
        jax, jnp = self._jax, self._jnp
        b = self._block_len
        left = true_len % b
        row = jax.lax.dynamic_slice_in_dim(
            jnp.pad(tokens[0], (0, b)), true_len - left - start, b)
        return jnp.where(jnp.arange(b) < left, row,
                         self._cache_spec.mask_token).astype(jnp.int32)

    def _verify_impl(self, params, kv, pt_full, sl_full, toks_full, rng,
                     temps_full, idx, drafts):
        """Verify-k program (speculative decoding): k+1 token positions
        per slot — the current token followed by its k drafted tokens —
        scored in ONE fused multi-position pass (paged_verify_step) at the
        packed width W. logits[t] match what sequential decode would
        compute after consuming the first t draft tokens, so with greedy
        sampling output s[t] is bit-identical to baseline decode: the host
        accepts the longest prefix with drafts[t] == s[t] and emits
        s[:a+1] — one guaranteed token (s[0]) plus up to k free ones. The
        per-layer paged-cache read happens once per ROUND instead of once
        per token, which is the speedup (decode is memory-bound).

        Rejected tail positions wrote junk KV past the accepted length;
        the host rolls seq_lens back (dirty-slot patch), and the junk is
        harmless by kv_cache._span_step's junk-write rule (the block pass
        of generation by diffusion over blocks relies on the same rule).
        drafts: [W, k] int32 (-1 pads lanes/short drafts; -1 never
        equals a sampled token so padding can't be accepted, and junk
        from padded positions is causally invisible to earlier positions).
        Sampling uses one rng split for all positions — only greedy slots
        are ever drafted (_propose_locked), where sampling is argmax.
        Returns all samples [k+1, W] plus the carried full-size state."""
        jax = self._jax
        jnp = self._jnp
        pt = pt_full[idx]
        lens0 = sl_full[idx]
        temps = temps_full[idx]
        tokens = jnp.concatenate(
            [toks_full[idx][:, None], drafts.astype(jnp.int32)], axis=1)
        rng, sub = jax.random.split(rng)
        logits, kv, new_lens = self._kvc.paged_verify_step(
            params, kv, pt, lens0, tokens, self.model_cfg,
            self.cfg.page_size, self._attn_backend, mesh=self._mesh)
        t = tokens.shape[1]
        out = self._kvc.sample_tokens(
            logits.reshape(-1, logits.shape[-1]), sub,
            jnp.repeat(temps, t), self.cfg.top_k).reshape(-1, t)
        all_toks = jnp.swapaxes(out, 0, 1)                  # [k+1, W]
        # scattered lens are k+1 past the truth for every rejected draft;
        # the harvest marks every participating slot dirty with the
        # rolled-back length, so this value is never read by a later
        # dispatch. Trash row pinned to zero as in _decode_impl.
        trash = self.cfg.max_batch_size
        sl_full = sl_full.at[idx].set(jnp.where(idx == trash, 0, new_lens))
        toks_full = toks_full.at[idx].set(all_toks[-1])
        return all_toks, toks_full, kv, sl_full, rng

    def _prefill_fn(self, bucket: int):
        """Prefill + first-token sampling fused in ONE jitted program.

        Sampling on device keeps admission fully asynchronous: the engine
        loop never blocks on a host round trip per request (the old
        ``int(tok[0])`` sync serialized one device round trip per
        admission). The sampled token is returned as a device scalar; the
        harvest pipeline records it. The program also WRITES it where the
        next decode block reads it: row ``slot`` (traced: one program a
        bucket serves every slot) of the donated token vector. A caller
        that arms no slot (disagg's prefill_only) passes the trash row.

        top_k is the ENGINE's (static — per-request values would compile a
        new program per distinct k, each stalling the loop; decode already
        uses the engine setting, see submit()).

        With a block length above 1 the program commits the prompt's whole
        blocks, samples nothing (its logits mean nothing and are never
        computed) and writes the slot's PENDING BLOCK (_pending_block) to
        its row; the scalar it returns is a placeholder."""
        fn = self._prefill_cache.get(bucket)
        if fn is None:
            jax = self._jax
            top_k = self.cfg.top_k

            def impl(params, kv, toks_full, page_table, tokens, true_len,
                     rng, temp, slot):
                logits, kv = self._kvc.paged_prefill(
                    params, kv, page_table, tokens, true_len,
                    self.model_cfg, self.cfg.page_size)
                if self._block_len > 1:
                    return true_len, toks_full.at[slot].set(
                        self._pending_block(tokens, 0, true_len)), kv
                tok = self._kvc.sample_tokens(
                    logits[None, :], rng, temp, top_k)
                return tok[0], toks_full.at[slot].set(tok[0]), kv

            fn = jax.jit(impl, donate_argnums=(1, 2))
            self._prefill_cache[bucket] = fn
        return fn

    def _chunk_fn(self, clen: int):
        """Chunked-prefill program for a chunk of ``clen`` tokens: write the
        chunk's KV through the page pool, attend over everything cached so
        far and, where ``final`` (a traced flag beside ``slot``: the host
        knows which chunk is a prompt's last), sample the next token on
        device and write it to row ``slot`` of the donated token vector as
        in _prefill_fn. The program's tail (the last row's final norm, the
        head over the vocabulary, the sampler) stands under a ``cond`` on
        ``final``: every other chunk runs none of it and returns the
        placeholder 0, which it writes to the row it was handed (the trash
        row). ``slot`` is a write address and nothing else, as in
        _prefill_fn: a final chunk that arms no slot passes the trash row
        and still reads the token returned. One program per chunk bucket
        (full chunks share one shape; the padded tail adds at most
        log2(prefill_chunk))."""
        key = ("chunk", clen)
        fn = self._prefill_cache.get(key)
        if fn is None:
            jax = self._jax
            top_k = self.cfg.top_k

            def impl(params, kv, toks_full, page_table, tokens, start,
                     true_len, rng, temp, slot, final):
                x, kv = self._kvc.paged_chunk_walk(
                    params, kv, page_table, tokens, start, true_len,
                    self.model_cfg, self.cfg.page_size,
                    self._attn_backend, mesh=self._mesh)
                if self._block_len > 1:     # as _prefill_fn's
                    return true_len, toks_full.at[slot].set(
                        self._pending_block(tokens, start, true_len)), kv

                def head():
                    logits = self._kvc.chunk_head(
                        params, x, start, true_len, self.model_cfg)
                    return self._kvc.sample_tokens(
                        logits[None, :], rng, temp, top_k)[0]

                tok = jax.lax.cond(final, head, lambda: self._jnp.int32(0))
                return tok, toks_full.at[slot].set(tok), kv

            fn = jax.jit(impl, donate_argnums=(1, 2))
            self._prefill_cache[key] = fn
        return fn

    # ---- public API ----------------------------------------------------
    def start(self):
        if self._loop_thread is None:
            if self.cfg.warmup_compile:
                with self._startup.stage("warm_decode"):
                    self._warmup_decode_programs()
            self._loop_thread = threading.Thread(
                target=self._loop, name="llm-engine", daemon=True)
            self._loop_thread.start()

    def _warmup_decode_programs(self):
        """Compile the decode program of every bucket width before
        serving: a first-use compile mid-traffic stalls ALL active
        generations for the whole XLA compile (seconds to tens of
        seconds) and wrecks tail latency. ONE program a width: the number
        of steps is its operand (_decode_impl), so every k _select_block
        can return is a dispatch of it; it is run here at the ceiling
        tier's k, which fills every row of its token buffer. All-trash
        index vectors make the warmup dispatches write only into the
        trash page. The operands are built as the loop builds them (numpy,
        or what a program returned), so traffic finds these very cache
        entries."""
        trash = self.cfg.max_batch_size
        # derive from _bucket_width so the warmed set can never diverge
        # from the widths _step actually dispatches
        widths = sorted({self._bucket_width(n)
                         for n in range(1, self.cfg.max_batch_size + 1)})
        k = np.int32(self._blocks_of(self.cfg.decode_block))
        for w in widths:
            idx = self._slot_index((), w)
            # compile_scope registers the width's signature so the
            # traffic-path scopes see it as already compiled; a warmup
            # compile is by definition not mid-traffic. The key that comes
            # back is dropped (it is not donated): the loop's key, and so
            # a seed's sampled streams, do not depend on what was warmed
            with self._prof.compile_scope("decode", ("decode", w)):
                _all, self._dev_tokens, self.kv, self._sl_dev, \
                    _key, *_touched = self._decode(
                        self.params, self.kv, self._pt_dev,
                        self._sl_dev, self._dev_tokens, self._rng,
                        self._temps_dev, idx, k)
            if self._spec_on:
                # the verify-k program per width too: an uncompiled verify
                # stalls the first speculative round mid-traffic exactly
                # like an uncompiled decode block would
                drafts = np.full((w, self.cfg.spec_draft_len), -1,
                                 np.int32)
                with self._prof.compile_scope(
                        "verify", ("verify", w, self.cfg.spec_draft_len)):
                    _all, self._dev_tokens, self.kv, self._sl_dev, \
                        _key = self._verify(
                            self.params, self.kv, self._pt_dev,
                            self._sl_dev, self._dev_tokens, self._rng,
                            self._temps_dev, idx, drafts)
        # the fixed-shape slot patches (all-trash write of zeros is a no-op)
        didx = self._slot_index((), trash + 1)
        with self._prof.compile_scope("patch", ("patch", "state")):
            self._pt_dev, self._sl_dev, self._temps_dev = self._patch_state(
                self._pt_dev, self._sl_dev, self._temps_dev, didx,
                np.zeros((trash + 1, self._table_width), np.int32),
                np.zeros((trash + 1,), np.int32),
                np.zeros((trash + 1,), np.float32))
        with self._prof.compile_scope("patch", ("patch", "toks")):
            self._dev_tokens = self._patch_toks(
                self._dev_tokens, didx,
                np.zeros(self._dev_tokens.shape, np.int32))
        # the key split of the first prefill (both halves dropped: the
        # loop's key is what it would be without this)
        with self._prof.compile_scope("split_key", ("split_key",)):
            self._split_key(self._rng)
        if self._kv_tier_on:
            # the tier-restore scatter too: its one fixed shape would
            # otherwise compile on the first tier hit, mid-traffic (an
            # all-trash-page write of zeros is a no-op)
            mp = self.max_pages_per_seq
            with self._prof.compile_scope("kv_tier_inject",
                                          ("kv_tier_inject", mp)):
                self._inject_host_pages(
                    [self._kvc.zero_pages(self.kv, mp)], ())
        self._jax.block_until_ready(self._dev_tokens)

    def shutdown(self):
        self._stop.set()
        self._wake.set()
        loop_alive = False
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=10.0)
            loop_alive = self._loop_thread.is_alive()
            self._loop_thread = None
        # surface already-computed completions: the loop may exit with
        # dispatched blocks still unharvested, and their waiters would
        # otherwise time out on results that exist. Skip if the loop thread
        # is wedged past the join timeout — draining concurrently with it
        # would race on _pending.
        if loop_alive:
            return
        try:
            while self._pending:
                self._harvest_one()
        except Exception:  # noqa: BLE001 - device may already be gone
            self._pending.clear()
        # restore streams have their own worker threads; cut them before
        # the tier closes underneath them
        with self._lock:
            restoring = list(self._restoring)
        for req in restoring:
            if req.restore_stream is not None:
                req.restore_stream.abort()
                req.restore_stream = None
        if self._kv_tier is not None:
            # flush captured spills, then drop the tier's blobs and
            # retract our cluster-index entries — a clean shutdown must
            # not leave the index pointing at refs nobody serves
            try:
                self._kv_tier_flush()
            except Exception:  # noqa: BLE001
                self._tier_pending.clear()
            self._kv_tier.close()

    def submit(self, prompt: str | list[int], *,
               max_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               request_id: Optional[str] = None,
               prefix_digests: Optional[list] = None,
               resume_tokens: Optional[list] = None,
               disagg: bool = False) -> str:
        """Enqueue a request; returns its id. Tokens stream via drain().

        ``resume_tokens`` is a mid-stream failover continuation (ISSUE
        14): the token ids a dead replica already generated for this
        request. They extend the admission sequence past the prompt —
        the cache-aware admission path (local prefix match, kv-tier
        restore, suffix-only chunked prefill) then recovers or
        recomputes the dead replica's KV and decode resumes at the
        exact next token; drain() emits ONLY post-resume tokens.
        ``max_tokens`` for a continuation is the REMAINING budget
        (original minus the tokens already emitted)."""
        if self.loop_error is not None:
            raise RuntimeError(self.loop_error)
        if isinstance(prompt, str):
            toks = self.tokenizer.encode(prompt)
        else:
            toks = list(prompt)
        # the prompt cap applies BEFORE the continuation is appended:
        # the original leg was capped the same way, so the digest chain
        # over the prompt pages is identical across legs
        toks = toks[: self.cfg.max_prompt_len]
        resume_len = 0
        if resume_tokens:
            if not self.cfg.failover_enabled:
                raise ValueError(
                    "continuation submit with failover_enabled=False")
            # leave >=1 position of generation room: a continuation that
            # would fill max_seq_len exactly still has to sample the next
            # token to make progress (the tail is truncated, which only
            # loses speculative room, never emitted tokens)
            resume = list(resume_tokens)[: max(
                0, self.cfg.max_seq_len - 1 - len(toks))]
            resume_len = len(resume)
            toks = toks + [int(t) for t in resume]
        req = _Request(
            request_id=request_id or uuid.uuid4().hex[:16],
            prompt_tokens=toks,
            max_tokens=max(1, min(max_tokens or self.cfg.max_tokens,
                                  self.cfg.max_seq_len - len(toks))),
            temperature=(self.cfg.temperature if temperature is None
                         else temperature),
            top_k=self.cfg.top_k if top_k is None else top_k,
            stop_token=getattr(self.tokenizer, "eos_token_id", None),
            ingress_digests=(list(prefix_digests)
                             if prefix_digests else None),
            resume_len=resume_len,
            disagg=bool(disagg))
        from ray_tpu.core import deadline as request_deadline
        from ray_tpu.observability import tracing
        req.trace_ctx = tracing.inject()
        req.deadline = request_deadline.current()
        if req.top_k != self.cfg.top_k:
            # All sampling (prefill first token + fused decode) uses the
            # ENGINE's top_k: k is static to the compiled programs, and a
            # per-request k would compile (and loop-stall on) a new program
            # per distinct value.
            logger.warning(
                "request top_k=%s differs from engine top_k=%s; sampling "
                "uses the engine setting", req.top_k, self.cfg.top_k)
        with self._lock:
            self._requests[req.request_id] = req
            self._waiting.append(req)
            self.stats["requests"] += 1
            if resume_len:
                self.stats["failover_resumed"] += 1
        if resume_len:
            # a failed replica's stream is being spliced onto this one —
            # journal it under the same request id so the postmortem
            # timeline joins it against the chaos fault that caused it
            _fr.emit("failover_resume", "WARNING",
                     request_id=req.request_id,
                     attrs={"resume_len": int(resume_len),
                            "model": str(self.cfg.model_id)})
        self._wake.set()
        return req.request_id

    def cancel(self, request_id: str) -> None:
        """Abandon a request (client disconnected mid-stream): a waiting
        request is dropped immediately; a slotted one finishes at its next
        recorded token (the loop then frees its slot/pages on the normal
        completion path). The entry is removed so nothing leaks when no
        one drains it again."""
        with self._lock:
            req = self._requests.pop(request_id, None)
            if req is None:
                return
            if req in self._waiting:
                self._waiting.remove(req)
                req.done = True
                req.finished_at = time.monotonic()
                # a concurrent result() waiter is parked on this event; a
                # dropped WAITING request must release it immediately, not
                # leave it blocking to its full timeout
                req.done_event.set()
                return
            if req in self._prefilling or req in self._restoring:
                # mid chunked prefill (or mid tier-restore stream): flag
                # it and let the LOOP free the slot/pages
                # (_abort_prefilling) — the loop may be building a chunk
                # dispatch from req.pages on the host right now, so
                # freeing here could hand those pages to a later admission
                # while this one still writes them. Without this branch the
                # request would chunk-prefill its ENTIRE remaining prompt,
                # decode a token, and only then free — the _prefilling
                # cancel leak.
                req.prefill_cancelled = True
                req.abandoned = True
                self._requests[request_id] = req  # loop reaps on abort
                self._wake.set()
                return
            if not req.done:
                # finish at next token; keep a tracking entry so the loop's
                # completion path still finds consistent state, and flag it
                # abandoned so completion also reaps the entry (no drain
                # will ever come to do it)
                req.max_tokens = max(1, len(req.generated))
                req.abandoned = True
                self._requests[request_id] = req
                req.drained_upto = len(req.generated)
        self._wake.set()

    def drain(self, request_id: str) -> dict:
        """New tokens since the last drain + done flag (streaming poll)."""
        with self._lock:
            req = self._requests.get(request_id)
            if req is None:
                return {"tokens": [], "text": "", "done": True,
                        "error": "unknown request"}
            new = req.generated[req.drained_upto:]
            req.drained_upto = len(req.generated)
            done = req.done
            err = req.error
            if done and req.drained_upto >= len(req.generated):
                # fully drained: allow GC
                self._requests.pop(request_id, None)
        out = {"tokens": new, "text": self.tokenizer.decode(new),
               "done": done, "error": err}
        if done:
            # final chunk carries the per-request attribution (queue wait +
            # engine stage timeline) so the streaming path surfaces the
            # same critical-path record as result(). Built OUTSIDE the
            # lock — pure host computation, but no reason to hold it.
            out.update(self._attribution_payload(req))
        return out

    def result(self, request_id: str, timeout: Optional[float] = None) -> dict:
        """Block until the request completes; returns the full completion.

        The wait is bounded by min(timeout, remaining request deadline);
        with neither, the 120 s guard still applies (a hung engine must not
        pin the caller forever). On expiry the request is CANCELLED — its
        slot/pages free at the next recorded token instead of decoding to
        max_tokens for nobody."""
        from ray_tpu.core import deadline as request_deadline
        if timeout is None:
            timeout = 120.0
        timeout = request_deadline.bound(timeout)
        with self._lock:
            req = self._requests.get(request_id)
        if req is None:
            return {"text": "", "tokens": [], "error": "unknown request"}
        if not req.done_event.wait(timeout):
            self.cancel(request_id)
            expired = (req.deadline is not None
                       and time.time() >= req.deadline)
            return {"text": "", "tokens": [],
                    "error": "deadline exceeded" if expired else "timeout"}
        with self._lock:
            self._requests.pop(request_id, None)
        ttft = (req.first_token_at - req.submitted_at
                if req.first_token_at else None)
        gaps = sorted(req.itl_gaps)
        out = {
            "text": self.tokenizer.decode(req.generated),
            "tokens": list(req.generated),
            "num_prompt_tokens": len(req.prompt_tokens),
            "num_generated_tokens": len(req.generated),
            "error": req.error,
            "ttft_s": ttft,
            # median inter-token gap at host record time (None for 0/1
            # token completions); bursty under pipelined harvests — see
            # _Request.itl_gaps
            "itl_s": gaps[len(gaps) // 2] if gaps else None,
            "latency_s": (req.finished_at or time.monotonic())
            - req.submitted_at,
        }
        out.update(self._attribution_payload(req))
        return out

    def _attribution_payload(self, req: _Request) -> dict:
        """Per-request critical-path extras (ISSUE 12): queue wait plus
        the engine-side stage timeline, carried in the response metadata
        back to the proxy (different process — stamps can't ride a
        contextvar across the wire)."""
        from ray_tpu.observability import attribution
        gaps = sorted(req.itl_gaps)
        queue_wait = ((req.admitted_at - req.submitted_at)
                      if req.admitted_at is not None else None)
        return {
            "request_id": req.request_id,
            "queue_wait_s": queue_wait,
            "stages": attribution.engine_stages(
                submitted_wall=req.submitted_wall,
                submitted_at=req.submitted_at,
                admitted_at=req.admitted_at,
                first_token_at=req.first_token_at,
                finished_at=req.finished_at,
                cached_tokens=req.cached_tokens,
                restored_tokens=req.restored_tokens,
                restore_bytes=req.restore_bytes,
                restore_ms=req.restore_ms,
                restore_wire_bytes=req.restore_wire_bytes,
                restore_decode_ms=req.restore_decode_ms,
                restore_overlap_ms=req.restore_overlap_ms,
                restore_partial=req.restore_partial,
                prompt_tokens=len(req.prompt_tokens),
                generated_tokens=len(req.generated),
                itl_s=gaps[len(gaps) // 2] if gaps else None),
        }

    def generate(self, prompt: str, **kw) -> dict:
        """Convenience: submit + wait."""
        rid = self.submit(prompt, **kw)
        return self.result(rid)

    def request_progress(self, request_id: str) -> Optional[dict]:
        """Per-request failover journal (ISSUE 14): the progress a
        resume needs — accepted token ids, how much of a continuation's
        prior work was recovered from cache/tier, and the restore cost
        (stamped into the proxy's ``failover`` attribution stage)."""
        with self._lock:
            req = self._requests.get(request_id)
            if req is None:
                return None
            return {"prompt_tokens": len(req.prompt_tokens),
                    "generated": list(req.generated),
                    "resume_len": req.resume_len,
                    "cached_tokens": req.cached_tokens,
                    "restored_tokens": req.restored_tokens,
                    "restore_bytes": req.restore_bytes,
                    "restore_ms": req.restore_ms,
                    "admitted": req.admitted_at is not None}

    def prefix_summary(self, max_pages: Optional[int] = None):
        """(index_version, resident page-chain digest hex list) for the
        affinity router, or None when prefix caching is off (the caller
        marks this engine unsupported and stops probing)."""
        if not self._prefix_cache_on:
            return None
        cap = (self.cfg.prefix_summary_max_pages if max_pages is None
               else max_pages)
        return self.allocator.prefix_summary(cap)

    def prefetch_hint(self, digests: list[str]) -> dict:
        """Router affinity-miss hint: start pulling the tier-held tail of
        this chain NOW so the restore inside _admit finds the pages in the
        hint buffer instead of paying the remote fetch inline. Locally
        resident pages are skipped; everything is best-effort."""
        if not self._kv_tier_on or not digests:
            return {"accepted": False}
        start = self.allocator.match_digest_chain(list(digests))
        if start >= len(digests):
            return {"accepted": False}
        return {"accepted": self._kv_tier.prefetch(list(digests), start)}

    def engine_stats(self) -> dict:
        """Counters and gauges of this engine, one flat dict (README.md's
        engine-telemetry table documents every key, and a test holds the
        two together): ``self.stats`` (running totals: steps / passes,
        prefills, tokens, prefix / tier / speculation / failover / disagg
        counts, per-kernel dispatches, dry dispatches, routed experts,
        what a block with
        slot state or one that generates by diffusion over blocks is kept
        out of: ``*_stateful`` / ``*_block``, and the latter's
        ``block_passes_total`` (a pass of two blocks counts once),
        ``denoise_passes_total``, ``fused_passes_total`` (passes that kept
        a block and denoised the next), ``commit_passes_total`` (commits
        that ran alone: 0 since the commit is deferred),
        ``slot_passes_total``, ``blocks_committed_total``,
        ``tokens_cut_total``); occupancy
        gauges; the profiler's ``phase_<p>_*``, ``host_stall_*``, ``gc_*``,
        compile and memory keys; ``clock_s``;
        the attention backend, device and tensor-parallel surface; prefix
        cache and tier gauges."""
        with self._lock:
            active = sum(1 for r in self.slot_req if r is not None)
            waiting = len(self._waiting)
            prefilling = len(self._prefilling)
            restoring = len(self._restoring)
        # mid-chunked-prefill and mid-restore-stream requests hold a slot
        # + pages but are not yet in slot_req: load monitoring must see
        # them (as waiting) or autoscaling under-counts
        free = self.allocator.available()
        out = {**self.stats, "active_slots": active,
               "waiting": waiting + prefilling + restoring,
               "prefilling": prefilling, "restoring": restoring,
               "free_pages": free,
               # gauges: the decode-block tier actually dispatched last
               # (1 / pressure_decode_block / the idle tier's — admission
               # pressure made visible) and the live dispatched-but-
               # unharvested block count (vs cfg.pipeline_depth)
               # sequences that hold a state row beside their pages (0
               # for a block without slot state)
               "state_slots_in_use": (active + prefilling + restoring
                                      if self._stateful else 0),
               # the state pool: its rows (a row a page, or slots + 1),
               # its bytes by kind (``taps`` in the activations' dtype,
               # ``recurrent`` float32), what ONE sequence holds of it
               # beside kv_bytes_per_token a token, and the reserved first
               # pages (state rows) no sequence holds
               "state_rows": self._state["rows"],
               "state_pool_bytes": dict(self._state["pool_bytes"]),
               "state_bytes_per_slot": self._state["bytes_per_slot"],
               "first_pages_free": self.allocator.first_pages_free(),
               # pages live requests hold of each kind (a block with
               # window layers: its rings' beside the growing tables')
               "full_pages_in_use": self.cfg.num_pages - 1 - free,
               "window_pages_in_use": (
                   self.window_allocator.num_pages - 1
                   - self.window_allocator.available()
                   if self._windowed else 0),
               "ring_pages": self._ring_pages,
               "decode_block_effective": self._last_block,
               "pending_pipeline_depth": len(self._pending),
               # the idle tier's k now, and how often the rule moved it
               "idle_lead_k": self._lead.k,
               "lead_climbs_total": self._lead.climbs,
               "lead_descents_total": self._lead.descents}
        # introspection (observability/profiling.py): per-phase p50/p95 +
        # itl_s (None until sampled / while profiling_enabled=False),
        # compile-event counters (always live), device-memory gauges.
        out.update(self._prof.phase_stats())
        # stalls of the loop's host: spans of host work whose own time
        # reached profiling.STALL_S, and the process's garbage collector
        out.update(self._prof.stall_stats())
        out["dry_s_total"] = round(out["dry_s_total"], 6)
        # this process's monotonic clock at the read: a reader divides a
        # counter's delta by the delta of THIS between the same two reads
        out["clock_s"] = round(time.monotonic(), 6)
        out["compile_events"] = self._prof.compile_events
        out["mid_traffic_compiles"] = self._prof.mid_traffic_compiles
        out["compile_s"] = round(self._prof.compile_s, 3)
        # the process's start-up ledger: ``startup`` (stages, every first
        # dispatch's record, what compiled under no scope; its lists are
        # the ledger's own, not copied) and the flat ``startup_*`` totals
        out.update(self._startup.stats())
        # paged-attention backend surface (ISSUE 18): which kernel family
        # this replica compiled in (string + a numeric twin exporters can
        # gauge), plus how many attention-bearing programs — decode /
        # verify / chunk tiers — have been compiled so far. The dispatch
        # counters live in self.stats above.
        out["attention_backend"] = self._attn_backend
        out["attn_backend_pallas"] = int(self._attn_backend == "pallas")
        # where the programs ran, as jax reports it: a number from this
        # engine is a device number only if these say tpu and 0
        out["device_platform"] = self._devices[0].platform
        out["device_kind"] = self._devices[0].device_kind
        out["device_count"] = len(self._devices)
        out["attn_interpret"] = self._attn_interpret
        out["attn_walks_live"] = list(self._attn_walks_live)
        # decode programs this engine has dispatched: one a bucket width
        # once start() has warmed them, whatever k traffic then runs
        out["decode_programs"] = self._prof.compile_count(("decode",))
        out["attn_writes_in_kernel"] = list(self._attn_writes_in_kernel)
        out["attn_sink_calls"] = list(self._attn_sink_calls)
        # {pool: [a head's lanes, the lanes stored for it]}: the padding
        out["pool_lanes"] = self._kvc.pool_lanes(self.model_cfg, self.kv)
        # the projections held head-major ({leaf: bytes over the layers};
        # empty: the block serves its weights as the checkpoint lays them)
        out["weights_head_major"] = dict(self._weights_head_major)
        out["attn_kernel_compiles"] = self._prof.compile_count(
            ("decode", "verify", "chunk"))
        # tensor-parallel surface (ISSUE 20), stable-key contract: degree
        # + mesh shape (string — exporters one-hot it like
        # attention_backend) are always emitted ("none"/1 single-chip),
        # and the byte gauges give ONE chip's slice of the pool — page
        # counts everywhere else stay whole-replica logical pages (see
        # PageAllocator), so dashboards sizing a chip's HBM read these
        # two instead of dividing counts themselves.
        out["tp_degree"] = self._tp
        # only live axes: build_mesh materializes every canonical axis at
        # size 1, which is noise in a gauge tag
        out["mesh_shape"] = ("none" if self._mesh is None else ",".join(
            f"{a}={n}" for a, n in dict(self._mesh.shape).items()
            if n > 1))
        pool_bytes = self._kvc.pool_nbytes(self.kv)
        # what a cached token costs the pool, all layers, each pool at its
        # own heads and stored lanes (padding lanes included: a latent
        # cache of 576 numbers a layer in rows of 640; a window layer's
        # row counts like a full layer's)
        out["kv_bytes_per_token"] = self._kvc.token_nbytes(self.kv)
        out["kv_shard_pool_bytes"] = pool_bytes // self._tp
        out["kv_shard_page_occupancy"] = (
            (self.cfg.num_pages - free) * pool_bytes
            // (self.cfg.num_pages * self._tp))
        out.update(self._prof.memory_stats(
            used_pages=self.cfg.num_pages - free,
            total_pages=self.cfg.num_pages, devices=self._devices))
        if self._spec_on:
            d = self.stats["spec_drafted_tokens"]
            out["spec_accept_rate"] = (
                round(self.stats["spec_accepted_tokens"] / d, 4) if d
                else 0.0)
        if self._prefix_cache_on:
            cs = self.allocator.cache_stats()
            out.update({"prefix_cached_pages": cs["cached_pages"],
                        "prefix_evictable_pages": cs["evictable_pages"],
                        "prefix_shared_pages": cs["shared_pages"],
                        "prefix_evictions": cs["evicted"],
                        "prefix_hit_pages": cs["hit_pages"],
                        "prefix_inserted_pages": cs["inserted"]})
        # tier byte gauges are always emitted (0 when the tier is off) so
        # exporters and the README drift guard see a stable key set; the
        # spill/restore counters live in self.stats above
        ts = self._kv_tier.stats() if self._kv_tier is not None else {}
        out["tier_bytes_shm"] = ts.get("shm_bytes", 0)
        out["tier_bytes_disk"] = ts.get("disk_bytes", 0)
        # page codec (ISSUE 15): raw-byte twins of the tier gauges plus
        # the cumulative ratio (= capacity multiplier on both byte caps)
        # and the per-page codec cost medians
        out["tier_bytes_shm_raw"] = ts.get("shm_bytes_raw", 0)
        out["tier_bytes_disk_raw"] = ts.get("disk_bytes_raw", 0)
        out["tier_codec_ratio"] = ts.get("codec_ratio", 0.0)
        out["tier_encode_ms_p50"] = ts.get("encode_ms_p50", 0.0)
        out["tier_decode_ms_p50"] = ts.get("decode_ms_p50", 0.0)
        # affinity-routing surface (ISSUE 10), same stable-key contract:
        # summary export state + hinted-prefetch effectiveness
        out["tier_prefetch_hints"] = ts.get("prefetch_hints", 0)
        out["tier_prefetch_pages"] = ts.get("prefetch_pages", 0)
        out["tier_prefetch_hit_pages"] = ts.get("prefetch_hit_pages", 0)
        if self._prefix_cache_on:
            ver, digs = self.allocator.prefix_summary(
                self.cfg.prefix_summary_max_pages)
            out["prefix_summary_version"] = ver
            out["prefix_summary_pages"] = len(digs)
        else:
            out["prefix_summary_version"] = 0
            out["prefix_summary_pages"] = 0
        return out

    # ---- engine loop ---------------------------------------------------
    def _loop(self):
        """Loop-thread entry. The loop has no other handler: an exception
        out of an iteration (a first-use compile error, a device fault)
        would otherwise end the thread silently while callers wait out
        their timeouts and health checks keep passing."""
        try:
            self._run_loop()
        except Exception as e:  # noqa: BLE001 - thread boundary
            logger.exception("engine loop died; failing in-flight requests")
            self._fail_stranded(f"engine loop failed: {e!r}")

    def _fail_stranded(self, msg: str) -> None:
        """Fail every request the dead loop leaves behind and mark the
        engine unusable. Slots and pages are not reclaimed: nothing will
        run on this engine again."""
        with self._lock:
            self.loop_error = msg
            stranded = [r for r in self._requests.values() if not r.done]
            now = time.monotonic()
            for req in stranded:
                req.error = msg
                req.done = True
                req.finished_at = now
            self._waiting.clear()
            self._prefilling.clear()
            self._restoring.clear()
            self._pending.clear()
        for req in stranded:
            if req.restore_stream is not None:
                req.restore_stream.abort()
                req.restore_stream = None
            req.done_event.set()

    def _run_loop(self):
        while not self._stop.is_set():
            with self._prof.span("loop_pass"):
                self._loop_pass()

    def _loop_pass(self):
        """One pass of the engine loop. Every phase of it is a span
        (observability/profiling.py) nested under `loop_pass`."""
        prof = self._prof
        # admit covers the whole admission pass (including the async
        # prefill dispatches of short prompts, which are ALSO sampled
        # individually as "prefill"), on every pass, idle ones too
        with prof.span("admit") as sp:
            admitted = self._admit()
            sp.set(admitted=admitted, waiting=len(self._waiting))
        # streaming tier restores first: a chunk that landed since
        # the last pass injects before this pass's prefill chunks
        # dispatch, and a stream that just finished routes its
        # request into _prefilling in time for THIS pass
        restored = 0
        if self._kv_tier_on:
            with prof.span("restore"):
                restored = self._restore_steps()
        chunks = self._prefill_chunks()
        if self._spill_req is not None:
            # drain-time eager spill (ISSUE 14): gather + flush on
            # THIS thread, then release the waiter — its return must
            # mean the chains are actually in the tier
            ev, box = self._spill_req
            self._spill_req = None
            try:
                box.append(self._spill_inflight_now())
                self._kv_tier_flush()
            finally:
                ev.set()
        if self._warm_req is not None:
            # cache-warm scale-up (ISSUE 17): restore the fleet's
            # hottest tier chains into the local prefix cache on THIS
            # thread — the replica is pre-routing-table, so the loop
            # has no traffic to stall
            ev, box, w_mb, w_bs = self._warm_req
            self._warm_req = None
            try:
                box.append(self._warm_start_now(w_mb, w_bs))
            finally:
                ev.set()
        # chunk dispatches count as progress: an otherwise-idle engine
        # mid-chunked-prefill must not sleep between chunks. Restore
        # progress counts too; a stream WAITING on fetches does not —
        # the idle wait below parks on _wake, which the stream's
        # on_ready sets the moment new pages land
        dispatched = self._step() or chunks > 0 or restored > 0
        if self._kv_tier_on:
            # spill gathers captured by evictions this pass: their
            # device->host copies were started at dispatch, so this
            # is mostly bookkeeping + an object-store put
            with prof.span("kv_tier_flush"):
                self._kv_tier_flush()
        # Eager harvest: pop every block whose device result already
        # landed (is_ready) — holding computed tokens unharvested just
        # adds their age to TTFT/ITL. The blocking PIPELINE_DEPTH trim
        # in _step still bounds the queue when results are slow.
        while self._pending and self._pending[0][0].is_ready():
            self._harvest_one()
        if not dispatched:
            if self._pending:
                self._harvest_one()  # drain the pipeline tail
                return
            # the one place the loop sleeps: what tells "the host had
            # nothing to do" from "the host was busy"
            self._parked = True
            with prof.span("loop_wait"):
                self._wake.wait(timeout=0.05)
            self._wake.clear()

    def _dry(self) -> int:
        """Called just before every dispatch (decode / block / verify,
        prefill, prefill chunk): 1 when the device has nothing queued
        (the newest program's output is ready) although the loop has not
        parked in ``loop_wait`` since it last dispatched, i.e. slots
        were live and the host did not keep the device fed. Counted in
        ``dry_dispatches_total``; ``dry_s_total`` adds the time since
        the loop last saw that output NOT ready or dispatched. When the
        device finished is unknown to the host, so that is an UPPER
        bound of the idle it stands for. One PJRT call, no sync."""
        now = time.perf_counter()
        dry = 0
        if not self._parked and self._newest is not None \
                and self._newest.is_ready():
            dry = 1
            self.stats["dry_dispatches_total"] += 1
            self.stats["dry_s_total"] += now - self._busy_seen
        self._busy_seen = now
        self._parked = False
        return dry

    def _see_device(self) -> None:
        """At every harvest's entry: is the device still running what was
        dispatched last? Then that is the latest it is known busy."""
        if self._newest is not None and not self._newest.is_ready():
            self._busy_seen = time.perf_counter()

    @staticmethod
    def _start_fetch(dev_arr) -> None:
        """Kick off the device->host copy at DISPATCH time so the later
        harvest finds the bytes already local instead of paying a blocking
        fetch per block."""
        dev_arr.copy_to_host_async()

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.cfg.max_prompt_len)

    def _admissions_blocked(self) -> bool:
        """Requests waiting while slots are free (= page-pool starved), or
        a chunked prefill mid-flight: shrink decode blocks so page
        reclamation isn't a whole block late and prefill chunks interleave
        tightly. Lock held. Subclasses with extra admission queues extend
        this."""
        return (bool(self._waiting) and bool(self.free_slots)) \
            or bool(self._prefilling) or bool(self._restoring)

    def _bucket_width(self, n: int) -> int:
        """Packed decode width: smallest power-of-two ≥ n (floor 4), capped
        at max_batch_size — a handful of compiled widths total."""
        w = 4
        while w < n:
            w *= 2
        return min(w, self.cfg.max_batch_size)

    def _shed_expired_waiting(self) -> None:
        """Drop WAITING requests whose deadline passed: no slot, no pages,
        no prefill — the caller stopped listening ("The Tail at Scale").
        Slotted requests are not preempted; cancel() handles those."""
        now = time.time()
        shed: list[_Request] = []
        with self._lock:
            keep = []
            for req in self._waiting:
                if req.deadline is not None and now >= req.deadline:
                    shed.append(req)
                else:
                    keep.append(req)
            if shed:
                self._waiting = keep
                self.stats["shed_expired"] += len(shed)
                for req in shed:
                    req.error = "deadline exceeded"
                    req.done = True
                    req.finished_at = time.monotonic()
        for req in shed:
            req.done_event.set()

    def _admit(self) -> int:
        """Move waiting requests into free slots (prefill each)."""
        self._shed_expired_waiting()
        admitted = 0
        while True:
            with self._lock:
                if not self._waiting or not self.free_slots:
                    return admitted
                req = self._waiting[0]
                # cache-aware admission: longest indexed full-page prefix
                # (increffed — shared pages go into this slot's page table
                # and only the suffix gets prefilled). match_prefix caps
                # the match so at least one suffix token remains: the
                # suffix pass is what produces the first sampled token.
                matched: list[int] = []
                if self._prefix_cache_on:
                    matched = self.allocator.match_prefix(
                        req.prompt_tokens, self.cfg.page_size)
                # (a block pass writes whole blocks: to the block's edge.
                # The pass of two blocks reaches one block further, with
                # K / V nothing ever reads: into the slot's last page, or
                # the trash page, which table entries past the slot's
                # pages and positions past the table's width name)
                reach = len(req.prompt_tokens) + req.max_tokens
                reach += -reach % self._block_len
                n_pages = -(-max(reach, 1) // self.cfg.page_size)
                n_pages = min(n_pages, self.max_pages_per_seq)
                pages = self.allocator.alloc(n_pages - len(matched))
                if pages is None and self._state_rows \
                        and not req.waited_state_row \
                        and not self.allocator.first_pages_free():
                    # it had a slot and no state row (counted once)
                    req.waited_state_row = True
                    self.stats["admission_waited_state_row"] += 1
                if pages is None:
                    # page pool exhausted; drop the match refs (pages park
                    # back in the cached LRU, still matchable) + retry next
                    # loop
                    if matched:
                        self.allocator.free(matched)
                    return admitted
                if self._windowed:
                    # the slot's ring: every page of it the request can
                    # reach, reserved now like the growing table's
                    req.window_pages = self.window_allocator.alloc(
                        min(n_pages, self._ring_pages))
                    if req.window_pages is None:
                        req.window_pages = []
                        self.allocator.free(pages)
                        return admitted
                self._waiting.pop(0)
                slot = self.free_slots.pop()
                req.slot = slot
                req.admitted_at = time.monotonic()
                req.pages = matched + pages
                req.cached_tokens = len(matched) * self.cfg.page_size
                req.prefill_pos = req.cached_tokens
                if self._prefix_cache_on \
                        and len(req.prompt_tokens) > self.cfg.page_size:
                    key = "prefix_hits" if matched else "prefix_misses"
                    self.stats[key] += 1
                    self.stats["prefix_hit_tokens"] += req.cached_tokens
                if self._stateful:
                    self._count_bypass(req, "stateful")
                if self._block_len > 1:
                    # what the configuration asks for and a block that
                    # carries a pending block does not take part in
                    if self.cfg.spec_decode_enabled:
                        self.stats["spec_bypassed_block"] += 1
                    if self.cfg.kv_tier_enabled \
                            and self.cfg.prefix_cache_enabled \
                            and len(req.prompt_tokens) > self.cfg.page_size:
                        self.stats["kv_tier_bypassed_block"] += 1
                if self._latent and self.cfg.kv_tier_enabled \
                        and self._prefix_cache_on \
                        and len(req.prompt_tokens) > self.cfg.page_size:
                    self.stats["kv_tier_bypassed_latent"] += 1
                if self._windowed:
                    self._count_bypass(req, "window")
            # queue-wait phase sample (submit→admit), recorded OUTSIDE the
            # lock: the profiler observes a metrics histogram, which must
            # never run under the engine lock (graftlint lock-discipline)
            self._prof.record("queue_wait",
                              req.admitted_at - req.submitted_at)
            if self._kv_tier_on and self._kv_tier_begin_restore(
                    req, len(matched)):
                # pipelined streaming restore (ISSUE 15): the stream's
                # worker plans sources (local walk + ONE CP match) and
                # fetches chunk-by-chunk off this thread; the loop's
                # _restore_steps decodes+injects chunks as they land and
                # routes the request on to its suffix prefill when the
                # stream ends. Admission never blocks on tier I/O — a
                # dead peer stalls ONE chunk of ONE request (per-chunk
                # budget), and everything landed before the stall is
                # kept (partial restore), never a whole-chain miss.
                with self._lock:
                    self._restoring.append(req)
                admitted += 1
                continue
            if req.resume_len:
                # tokens of the dead replica's work recovered WITHOUT
                # recompute (local prefix pages; the tier-restore leg
                # accounts its share when its stream finalizes)
                self.stats["failover_restored_tokens"] += req.cached_tokens
            self._route_admitted(req)
            admitted += 1

    def _count_bypass(self, req: _Request, why: str) -> None:
        """What this admission would have taken part in had the block no
        slot state (``why`` "stateful") or no window layers ("window")
        (lock held): the configuration asks for it, the engine does not do
        it, and the count says so."""
        if self.cfg.prefix_cache_enabled \
                and len(req.prompt_tokens) > self.cfg.page_size:
            self.stats[f"prefix_bypassed_{why}"] += 1
            if self.cfg.kv_tier_enabled:
                self.stats[f"kv_tier_bypassed_{why}"] += 1
        if self.cfg.spec_decode_enabled:
            self.stats[f"spec_bypassed_{why}"] += 1

    def refuse_stateful(self, what: str) -> None:
        """Disaggregated handoff moves pages and a first token; a block
        with slot state needs the state too, which no handoff carries, and
        one that generates by diffusion over blocks a pending block and no
        first token."""
        if self._block_len > 1:
            with self._lock:
                self.stats["disagg_refused_block"] += 1
            raise NotImplementedError(
                f"{what}: the block generates by diffusion over blocks; a "
                f"prefill leaves a pending block and no first token, which "
                f"a handoff does not carry")
        if self._stateful:
            with self._lock:
                self.stats["disagg_refused_stateful"] += 1
            raise NotImplementedError(
                f"{what}: the block keeps per-sequence state beside its "
                f"pages, which a handoff does not carry")
        if self._latent:
            with self._lock:
                self.stats["disagg_refused_latent"] += 1
            raise NotImplementedError(
                f"{what}: the block keeps one latent row a token, and a "
                f"handoff's wire format carries pairs of K and V pages")
        if self._windowed:
            with self._lock:
                self.stats["disagg_refused_window"] += 1
            raise NotImplementedError(
                f"{what}: the block has window layers, whose pages are a "
                f"ring of a second pool; a handoff carries pages of one "
                f"pool, each holding its tokens for good")

    def _route_admitted(self, req: _Request) -> None:
        """Send an admitted request (prefix matched, tier restore — if
        any — finished) to its prompt pass."""
        suffix = len(req.prompt_tokens) - req.prefill_pos
        if req.prefill_pos > 0 or (self.cfg.prefill_chunk > 0
                                   and suffix > self.cfg.prefill_chunk):
            # long prompt OR cached prefix: prefill the (remaining)
            # suffix in chunks interleaved with decode blocks (the loop
            # drives _prefill_chunks). A cached prefix MUST go through
            # the chunk program — paged_prefill writes from position 0
            # and would scribble on the shared pages; the chunk pass
            # starts at prefill_pos and reads the cached prefix back
            # through the page table.
            with self._lock:
                self._prefilling.append(req)
        else:
            self._prefill(req)

    # ---- tiered KV cache (kv_tier.py) ---------------------------------
    _SPILL_GATHER_WIDTH = 8  # fixed gather width: one compiled shape

    def _spill_capture(self, evicted) -> None:
        """Allocator spill hook: runs on the loop thread immediately
        after an evicting alloc()/free(), BEFORE the caller can dispatch
        writes that reuse the pages — so the gather dispatched here reads
        the pre-eviction KV on the ordered device stream. Only the
        dispatch happens here; the device->host copy is started async and
        harvested later by _kv_tier_flush, off the admission hot path."""
        ents = [(p, d, pos) for (p, d, pos) in evicted if pos is not None]
        if not ents:
            return
        w = self._SPILL_GATHER_WIDTH
        for i in range(0, len(ents), w):
            batch = ents[i:i + w]
            # pad the gather index to the fixed width with the trash page
            # (sliced off host-side) so spill batches of every size share
            # one compiled gather
            bk, bv = self._kvc.gather_pages(
                self.kv, [p for p, _, _ in batch] + [0] * (w - len(batch)))
            self._start_fetch(bk)
            self._start_fetch(bv)
            self._tier_pending.append((bk, bv, batch))

    def _kv_tier_flush(self) -> None:
        """Harvest captured spill gathers (host copy already in flight)
        and hand them to the tier store. A failed put degrades to a
        plain eviction — the pages are long since back on the free
        list."""
        if not self._tier_pending:
            return
        pend, self._tier_pending = self._tier_pending, []
        for bk, bv, ents in pend:
            try:
                k_np, v_np = self._kvc.fetch_pages(bk, bv, len(ents))
                n = self._kv_tier.put(
                    k_np, v_np,
                    digests=[d.hex() for _, d, _ in ents],
                    tokens=[(pos + 1) * self.cfg.page_size
                            for _, _, pos in ents])
                self.stats["spilled_pages"] += n
            except Exception:  # noqa: BLE001 - spill is best-effort
                logger.warning("kv-tier spill put failed; chain evicted "
                               "without spilling", exc_info=True)

    def spill_inflight(self, timeout_s: float = 5.0) -> int:
        """Eagerly spill the computed full KV pages of every LIVE chain
        into the tier (ISSUE 14 drain/SIGTERM path). Ordinary spill
        waits for pool eviction; a draining or dying replica's in-flight
        requests would take their KV with them — this pushes the chains
        out NOW so a surviving replica can tier-restore a continuation
        instead of recomputing it. Thread-safe: the gather runs on the
        engine loop via a handshake (one driver per device stream), or
        directly when the loop is not running. Returns pages spilled."""
        if not self._kv_tier_on:
            return 0
        loop = self._loop_thread
        if loop is None or not loop.is_alive():
            n = self._spill_inflight_now()
            self._kv_tier_flush()
            return n
        ev = threading.Event()
        box: list = []
        self._spill_req = (ev, box)
        self._wake.set()
        ev.wait(timeout_s)
        return box[0] if box else 0

    def _spill_inflight_now(self) -> int:
        """Capture spill gathers for every live request's full pages
        (slotted or mid chunked prefill). Engine-loop thread only (or
        the caller's, when the loop is down) — the same thread also
        frees pages, so the entries can't go stale under us."""
        if self._kv_tier is None:
            return 0
        ps = self.cfg.page_size
        ents: list = []
        with self._lock:
            live = [r for r in self.slot_req if r is not None and not r.done]
            live += [r for r in self._prefilling
                     if not r.prefill_cancelled and not r.done]
            # mid-restore-stream requests hold pages too; their injected
            # frontier is prefill_pos, same as the chunked-prefill case
            live += [r for r in self._restoring
                     if not r.prefill_cancelled and not r.done]
            for req in live:
                toks = req.prompt_tokens + req.generated
                if req.dispatched > 0:
                    # armed slot: prompt KV fully written; a generated
                    # token's KV is written when it feeds the NEXT step,
                    # so the newest recorded token may not be cached yet
                    covered = len(req.prompt_tokens) + max(
                        0, len(req.generated) - 1)
                else:
                    covered = req.prefill_pos   # mid chunked prefill
                limit = min(covered // ps, len(req.pages))
                digest = b""
                for i in range(limit):
                    digest = self._kvc._chain_digest(
                        digest, toks[i * ps:(i + 1) * ps])
                    ents.append((req.pages[i], digest, i))
        if not ents:
            return 0
        self._spill_capture(ents)
        return len(ents)

    def warm_start(self, max_bytes: Optional[int] = None,
                   budget_s: Optional[float] = None) -> dict:
        """Pre-populate the prefix cache from the cluster tier BEFORE the
        first request (ISSUE 17 cache-warm scale-up): enumerate the
        fleet's restorable chains from the CP ``kv_tier:`` index
        (hottest first), stream them through ChainStream, inject the
        pages and register their digests — so the router's affinity
        scoring sees this replica as a warm holder from its very first
        summary. Bounded by a wire-byte budget AND a time budget; every
        failure degrades to a smaller (or empty) warm set. Thread-safe
        via the same loop handshake as spill_inflight. Returns
        {"supported", "pages", "chains", "wire_bytes", "ms"}."""
        out = {"supported": False, "pages": 0, "chains": 0,
               "wire_bytes": 0, "ms": 0.0}
        if not self._kv_tier_on or not self.cfg.warm_start_enabled:
            return out
        mb = int(max_bytes if max_bytes is not None
                 else self.cfg.warm_start_max_bytes)
        bs = float(budget_s if budget_s is not None
                   else self.cfg.warm_start_budget_s)
        loop = self._loop_thread
        if loop is None or not loop.is_alive():
            return dict(self._warm_start_now(mb, bs), supported=True)
        ev = threading.Event()
        box: list = []
        self._warm_req = (ev, box, mb, bs)
        self._wake.set()
        ev.wait(bs + 10.0)
        res = box[0] if box else {"pages": 0, "chains": 0,
                                  "wire_bytes": 0, "ms": 0.0}
        return dict(res, supported=True)

    def _warm_start_now(self, max_bytes: int, budget_s: float) -> dict:
        """Loop-thread warm-start worker. Plans from the CP index dump,
        restores chain by chain (each through its own ChainStream, chunk
        budgets and all), allocs pages, injects through the ONE fixed-
        shape donated-pool scatter and registers the digests at refcount
        zero (parked in the cached LRU: matchable, evictable, visible to
        prefix_summary). Page budget is capped by pool headroom (one
        request's worth of pages stays free) and the prefix-cache cap,
        so warming can neither starve the first admission nor trigger
        immediate evict-respill churn."""
        t0 = time.perf_counter()
        out = {"pages": 0, "chains": 0, "wire_bytes": 0, "ms": 0.0}
        deadline = t0 + max(0.1, budget_s)
        try:
            chains = self._kv_tier.restorable_chains(
                self.cfg.warm_start_max_chains)
        except Exception:  # noqa: BLE001 — warm start is best-effort
            logger.warning("warm start: chain enumeration failed",
                           exc_info=True)
            chains = []
        mp = self.max_pages_per_seq
        for chain in chains:
            if time.perf_counter() >= deadline \
                    or out["wire_bytes"] >= max_bytes:
                break
            digs = [d for d in chain["digests"] if d]
            start = self.allocator.match_digest_chain(digs)
            if start >= len(digs):
                continue
            cs = self.allocator.cache_stats()
            budget_pages = self.allocator.available() - mp
            cap = self.cfg.prefix_cache_max_pages
            if cap > 0:
                budget_pages = min(budget_pages,
                                   cap - cs["evictable_pages"])
            n_take = min(len(digs) - start, budget_pages)
            if n_take <= 0:
                break
            stream = None
            try:
                stream = self._kv_tier.open_stream(
                    digs, start,
                    chunk_pages=self.cfg.kv_tier_chunk_pages,
                    window_bytes=self.cfg.kv_tier_stream_window_bytes,
                    timeout_s=self.cfg.kv_tier_chunk_timeout_s)
                c = start
                got = 0
                while got < n_take:
                    pairs, wire, _dec = stream.take(
                        max_pages=min(mp, n_take - got))
                    if not pairs:
                        if stream.exhausted \
                                or time.perf_counter() >= deadline:
                            break
                        time.sleep(0.002)
                        continue
                    pgs = self.allocator.alloc(len(pairs))
                    if pgs is None:
                        break
                    t = len(pairs)
                    self._tier_inject(pairs, pgs)
                    self.allocator.insert_digest_chain(
                        digs[c:c + t], pgs, list(range(c, c + t)))
                    # decref to zero: registered pages park in the LRU,
                    # duplicate pages fall back to the free list
                    self.allocator.free(pgs)
                    c += t
                    got += t
                    out["pages"] += t
                    out["wire_bytes"] += wire
                if got:
                    out["chains"] += 1
            except Exception:  # noqa: BLE001 — degrade to a smaller set
                logger.warning("warm start: chain restore failed; "
                               "continuing", exc_info=True)
            finally:
                if stream is not None and not stream.exhausted:
                    stream.abort()
        out["ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        self.stats["warm_start_pages"] += out["pages"]
        self.stats["warm_start_ms"] = round(
            self.stats["warm_start_ms"] + out["ms"], 3)
        return out

    def _chain_digests(self, toks, limit: int,
                       ingress: Optional[list]) -> list[str]:
        """Hex chain digests for the first ``limit`` full pages, always
        recomputed over this engine's own tokens. Ingress digests are
        only cross-checked, never trusted: page-0 equality proves the
        proxy tokenizer agreed on the FIRST page, not on later ones — a
        version skew past page 0 would name different token content and
        restore KV that doesn't match the request. The chaining is
        blake2b over the token ids, microseconds against the cost of a
        wrong restore."""
        ps = self.cfg.page_size
        digest = b""
        digs = []
        for i in range(limit):
            digest = self._kvc._chain_digest(
                digest, toks[i * ps:(i + 1) * ps])
            digs.append(digest.hex())
        if ingress and digs and list(ingress[:limit]) != digs \
                and not self._ingress_skew_warned:
            self._ingress_skew_warned = True
            logger.warning(
                "ingress prefix digests disagree with local recompute "
                "(proxy/replica tokenizer skew?); affinity hints from "
                "this proxy will miss — using local digests")
        return digs

    def _kv_tier_begin_restore(self, req: _Request, m_loc: int) -> bool:
        """Open a pipelined restore stream for the tier-held chain pages
        past the local match. Returns False when there is nothing past
        the local match worth probing (or the stream could not open) —
        the caller then routes straight to prefill. True parks the
        request in _restoring; _restore_steps drives it from there."""
        try:
            ps = self.cfg.page_size
            toks = req.prompt_tokens
            limit = min((len(toks) - 1) // ps, len(req.pages))
            if limit <= m_loc:
                return False
            digs = self._chain_digests(toks, limit, req.ingress_digests)
            # floor the prefetch window at two raw chunks (raw bounds
            # the encoded wire bytes the window counts): a window
            # narrower than one chunk serializes the worker to sub-chunk
            # progress — it parks before every landing
            window = max(
                self.cfg.kv_tier_stream_window_bytes,
                2 * self.cfg.kv_tier_chunk_pages
                * self._kvc.page_raw_nbytes(self.model_cfg, ps))
            req.restore_stream = self._kv_tier.open_stream(
                digs, m_loc,
                chunk_pages=self.cfg.kv_tier_chunk_pages,
                window_bytes=window,
                timeout_s=self.cfg.kv_tier_chunk_timeout_s,
                on_ready=self._wake.set)
        except Exception:  # noqa: BLE001 - restore degrades to a miss
            logger.warning("kv-tier restore stream failed to open; cold "
                           "prefill instead", exc_info=True)
            req.restore_stream = None
            return False
        req.restore_started = time.perf_counter()
        req.restore_page0 = m_loc
        req.restore_pages = 0
        return True

    def _restore_steps(self) -> int:
        """Drive active restore streams (loop thread): take landed
        chunks, decode + scatter them into the request's pages, enforce
        the per-chunk budget, and finalize — full or PARTIAL — routing
        the request on to its suffix prefill. Decode+inject of landed
        chunks runs here while the streams' workers fetch ahead and the
        rest of this loop iteration prefills/decodes other requests:
        that concurrency is the restore latency the old fetch-then-
        inject path spent blocked."""
        with self._lock:
            active = list(self._restoring)
        if not active:
            return 0
        progressed = 0
        now_w = time.time()
        budget_s = max(self.cfg.kv_tier_chunk_timeout_s, 0.1)
        for req in active:
            stream = req.restore_stream
            if req.prefill_cancelled or (req.deadline is not None
                                         and now_w >= req.deadline):
                self._abort_prefilling(req)
                progressed += 1
                continue
            t0 = time.perf_counter()
            injected = 0
            try:
                pairs, wire, dec_ms = stream.take(
                    max_pages=self.max_pages_per_seq)
                if pairs:
                    injected = self._inject_pages(req, pairs)
                    req.restore_wire_bytes += wire
                    req.restore_decode_ms += dec_ms
            except Exception:  # noqa: BLE001 - degrade to partial/miss
                logger.warning("kv-tier chunk inject failed; keeping "
                               "landed pages, prefilling the rest",
                               exc_info=True)
                stream.abort()
            req.restore_blocked_ms += (time.perf_counter() - t0) * 1e3
            progressed += injected
            if stream.exhausted:
                self._finalize_restore(req)
                progressed += 1
            elif (time.monotonic() - stream.last_progress) > budget_s * 1.5:
                # per-chunk budget watchdog: the worker's own gets are
                # timeout-bounded, but a wedged load must not park the
                # request forever — cut the stream, keep what landed
                stream.abort()
        return progressed

    def _inject_host_pages(self, pairs, pages) -> None:
        """Write host page pairs (in order, one or more pages each) into
        pool pages ``pages`` through the ONE fixed-shape donated program:
        the blob zero-padded to max_pages_per_seq, its targets padded with
        the trash page. Loop thread only (one driver per device stream)."""
        mp = self.max_pages_per_seq
        bk, bv = self._kvc.pack_pages(pairs, mp)
        tgt = np.zeros((mp,), np.int32)
        tgt[:len(pages)] = pages
        self.kv = self._inject_kv(self.kv, bk, bv, tgt)

    def _tier_inject(self, pairs, pages) -> None:
        """_inject_host_pages for the tier's restore paths, which count a
        first use after traffic began as a mid-traffic compile."""
        with self._prof.compile_scope(
                "kv_tier_inject",
                ("kv_tier_inject", self.max_pages_per_seq),
                mid_traffic=self.stats["requests"] > 0):
            self._inject_host_pages(pairs, pages)

    def _inject_pages(self, req: _Request, pairs: list) -> int:
        """Scatter decoded chain pages (in chain order, continuing at
        restore_page0 + restore_pages) into this request's pool pages."""
        ps = self.cfg.page_size
        pos0 = req.restore_page0 + req.restore_pages
        t = min(len(pairs), len(req.pages) - pos0)
        if t <= 0:
            return 0
        self._tier_inject(pairs[:t], req.pages[pos0:pos0 + t])
        req.restore_pages += t
        req.cached_tokens = (pos0 + t) * ps
        req.prefill_pos = req.cached_tokens
        req.restored_tokens += t * ps
        req.restore_bytes += t * self._kvc.page_raw_nbytes(
            self.model_cfg, ps)
        self.stats["restored_pages"] += t
        self.stats["tier_hit_tokens"] += t * ps
        return t

    def _finalize_restore(self, req: _Request) -> None:
        """Stream over (fully, partially, or not at all): stamp the
        attribution split, count a partial restore, and send the request
        to its suffix prefill — which starts exactly at the restored
        frontier, so a mid-chain fault costs recompute of the TAIL only,
        never of what already landed."""
        stream = req.restore_stream
        req.restore_stream = None
        req.restore_ms = (time.perf_counter()
                          - req.restore_started) * 1e3
        req.restore_overlap_ms = max(
            0.0, req.restore_ms - req.restore_blocked_ms)
        planned = stream.planned or 0
        if 0 < req.restore_pages < planned:
            self.stats["restore_partial"] += 1
            req.restore_partial = True
            _fr.emit("restore_partial", "WARNING",
                     request_id=req.request_id,
                     attrs={"restored_pages": int(req.restore_pages),
                            "planned_pages": int(planned)})
        if req.disagg:
            # fleet disagg (ISSUE 16): this restore carried a remote
            # prefill's KV — count the handoff and its wire/overlap
            # split regardless of whether the stream ran to plan (a
            # partial handoff still moved bytes and hid latency)
            self.stats["disagg_prefills"] += 1
            self.stats["handoff_bytes_wire"] += req.restore_wire_bytes
            self.stats["handoff_overlap_ms"] += req.restore_overlap_ms
        if req.resume_len:
            # the continuation's recovered-without-recompute accounting,
            # deferred from _admit until the restored frontier is final
            self.stats["failover_restored_tokens"] += req.cached_tokens
        with self._lock:
            if req in self._restoring:
                self._restoring.remove(req)
        self._route_admitted(req)

    def _prefill(self, req: _Request):
        """Dispatch prefill WITHOUT waiting for it: the sampled first token
        stays on device (the program writes it to the slot's row of
        _dev_tokens, where the next decode block reads it) and is
        recorded on the host by the harvest pipeline, in order, like any
        decode block's tokens."""
        plen = len(req.prompt_tokens)
        bucket = self._bucket(plen)
        toks = np.full((1, bucket), 0, np.int32)
        toks[0, :plen] = req.prompt_tokens
        table = self._table_of(req)
        fn = self._prefill_fn(bucket)
        self._rng, sub = self._split_key(self._rng)
        # a first-use prefill bucket compiles HERE, with a live request
        # waiting on it — warmup doesn't cover prompt buckets, so this is
        # always a mid-traffic compile when it fires
        with self._prof.span("prefill", rid=req.request_id, bucket=bucket,
                             tokens=plen, dry=self._dry()), \
                self._prof.compile_scope(
                "prefill", ("prefill", bucket),
                mid_traffic=self.stats["requests"] > 0):
            tok_dev, self._dev_tokens, self.kv = fn(
                self.params, self.kv, self._dev_tokens, table, toks,
                np.int32(plen), sub,
                np.full((1,), req.temperature, np.float32),
                np.int32(req.slot))
            self._newest = tok_dev
        self._arm_slot(req, table, tok_dev, plen)

    def _table_of(self, req: _Request):
        """The slot's page table as the programs take it: its pages of the
        growing table and, of a block with window layers, its ring table
        behind them (kv_cache.py's module docstring)."""
        table = np.zeros((self._table_width,), np.int32)
        table[: len(req.pages)] = req.pages
        mp = self.max_pages_per_seq
        table[mp: mp + len(req.window_pages)] = req.window_pages
        return table

    def _release_pages(self, req: _Request) -> None:
        """Give back what the request holds of both kinds of pages."""
        self.allocator.free(req.pages)
        req.pages = []
        if req.window_pages:
            self.window_allocator.free(req.window_pages)
            req.window_pages = []

    def _count_recycled(self, req: _Request, ctx: int) -> None:
        """A block with window layers: the ring entries of this slot that
        a context of ``ctx`` tokens has written again (lock held or loop
        thread: a counter)."""
        if self._windowed:
            n = max(0, -(-ctx // self.cfg.page_size) - self._ring_pages)
            self.stats["window_pages_recycled_total"] += max(
                0, n - req.ring_recycled)
            req.ring_recycled = max(n, req.ring_recycled)

    def _arm_slot(self, req: _Request, table, tok_dev, plen: int) -> None:
        """Publish a freshly prefilled slot to the decode loop: host/device
        state patch and a harvest entry for the sampled first token
        ``tok_dev`` (which the prefill program has already written to the
        slot's row of _dev_tokens: nothing to place here). With a block
        length above 1 the prefill sampled nothing: the slot starts at the
        prompt's whole blocks with its pending block (not clean: it holds
        a mask) in its row, and its first tokens come with the first block
        dispatch."""
        blocks = self._block_len > 1
        if not blocks:
            self._start_fetch(tok_dev)
        kept = plen - plen % self._block_len
        with self._lock:
            req.dispatched = 0 if blocks else 1
            self.page_tables[req.slot] = table
            self.seq_lens[req.slot] = kept
            self.slot_req[req.slot] = req
            self._dirty_slots[req.slot] = (kept, req.temperature)
            if not blocks:
                self._pending.append(
                    (tok_dev, [(0, req.slot, req)], 1, -1, None))
        if self._prefix_cache_on:
            # Index the prompt's FULL pages now (not at completion): the
            # writes are merely dispatched, but any matcher's reads are
            # dispatched later on the same ordered device stream, so a
            # concurrent same-prefix admission can already share. Partial
            # trailing pages are never indexed — and decode writes land at
            # positions >= plen, past every full prompt page — so a shared
            # page is never written after insertion (the would-be COW case
            # is excluded by construction; a FULL-prefix match instead
            # drops its last page and recomputes it into a private page,
            # copy-on-write by recompute).
            self.allocator.insert_prefix(
                req.prompt_tokens, req.pages, self.cfg.page_size)
        self.stats["prefills"] += 1

    def _prefill_chunks(self) -> int:
        """Dispatch ONE prefill chunk per in-progress chunked admission
        (loop thread). The final chunk's on-device sampled token arms the
        slot exactly like _prefill's; intermediate chunks only extend the
        cached KV (they pass the trash row and ``final`` false, on which
        the program skips its head and sampler: ``chunk_heads_skipped``).
        Chunks are dispatched async — the decode block that follows in this
        loop iteration queues behind them on the device stream, which is
        the interleaving."""
        trash = self.cfg.max_batch_size
        with self._lock:
            active = list(self._prefilling)
        now = time.time()
        for req in active:
            if req.prefill_cancelled or (req.deadline is not None
                                         and now >= req.deadline):
                self._abort_prefilling(req)
                continue
            plen = len(req.prompt_tokens)
            start = req.prefill_pos
            remaining = plen - start
            # prefill_chunk 0 disables chunking, but a cached-prefix
            # admission still rides this path (suffix-only prefill): the
            # whole suffix then goes as one chunk
            chunk = (self.cfg.prefill_chunk if self.cfg.prefill_chunk > 0
                     else remaining)
            final = remaining <= chunk
            clen = self._bucket(remaining) if final else chunk
            toks = np.zeros((1, clen), np.int32)
            seg = req.prompt_tokens[start: start + clen]
            toks[0, : len(seg)] = seg
            table = self._table_of(req)
            fn = self._chunk_fn(clen)
            self._rng, sub = self._split_key(self._rng)
            with self._prof.span(
                    "chunk_prefill", rid=req.request_id, clen=clen,
                    start=start, tokens=len(seg), last=int(final),
                    head=int(final), dry=self._dry()), \
                    self._prof.compile_scope(
                    "chunk", ("chunk", clen),
                    mid_traffic=self.stats["requests"] > 0):
                tok_dev, self._dev_tokens, self.kv = fn(
                    self.params, self.kv, self._dev_tokens, table, toks,
                    np.int32(start), np.int32(plen), sub,
                    np.full((1,), req.temperature, np.float32),
                    np.int32(req.slot if final else trash), np.bool_(final))
                self._newest = tok_dev
            self.stats["attn_chunk_dispatches"] += 1
            self.stats["chunk_heads_skipped"] += not final
            req.prefill_pos = min(start + clen, plen)
            self._count_recycled(req, req.prefill_pos)
            if req.prefill_pos >= plen:
                with self._lock:
                    self._prefilling.remove(req)
                self._arm_slot(req, table, tok_dev, plen)
        return len(active)

    def _abort_prefilling(self, req: _Request) -> None:
        """Release a mid-chunked-prefill request NOW (cancelled, or its
        deadline passed): slot, pages and tracking — not after the
        remaining chunks plus a decode step, which is how the _prefilling
        path used to leak pool capacity under cancel. Loop thread only:
        in-flight chunk dispatches may still write these pages, but the
        device stream is ordered, so any later prefill reusing them is
        dispatched — and therefore executes — after. The slot was never
        armed, so its device page-table row is still the zeros its
        previous occupant left."""
        expired = not getattr(req, "abandoned", False)
        if req.restore_stream is not None:
            # cut the stream first: its worker must stop landing chunks
            # for pages we are about to hand back to the pool
            req.restore_stream.abort()
            req.restore_stream = None
        with self._lock:
            if req in self._prefilling:
                self._prefilling.remove(req)
            if req in self._restoring:
                self._restoring.remove(req)
            if req.slot >= 0:
                self.free_slots.append(req.slot)
                req.slot = -1
            req.done = True
            req.finished_at = time.monotonic()
            if expired:
                req.error = "deadline exceeded"
                self.stats["shed_expired"] += 1
            else:
                self._requests.pop(req.request_id, None)
        self._release_pages(req)
        req.done_event.set()

    def _record_token(self, req: _Request, tok: int) -> None:
        """Append a sampled token; mark done on stop/max. Lock held."""
        if req.done:
            return
        now = time.monotonic()
        if req.first_token_at is None:
            req.first_token_at = now
        elif req.last_token_at is not None:
            gap = now - req.last_token_at
            req.itl_gaps.append(gap)
            self._prof.record_itl(gap)
        req.last_token_at = now
        req.generated.append(tok)
        self.stats["tokens_out"] += 1
        hit_stop = (req.stop_token is not None and tok == req.stop_token)
        if hit_stop or len(req.generated) >= req.max_tokens:
            if hit_stop:
                req.generated.pop()  # don't emit the stop token
            req.done = True
            req.finished_at = time.monotonic()

    def _select_block(self) -> int:
        """Decode-block tier for the next dispatch (lock held). k is an
        OPERAND of the width's one decode program, whose token buffer has
        the ceiling tier's rows: every value returned here is at most
        that, so nothing compiles under traffic (start() warmed the
        program: _warmup_decode_programs). Three tiers, by what the queue
        shows:

        * admissions blocked (requests wait though slots are free, or a
          chunked prefill is mid-flight): ONE step, so page reclamation
          and the next chunk are not a block late;
        * requests queue for slots: ``pressure_decode_block``. A
          finishing request's stop token is seen (and its slot freed for
          the queue) within ~pipeline_depth * k steps, so big blocks at
          saturation hold slots long past completion;
        * nothing waits (the idle tier): what keeps the device fed and no
          more (lead.py). Whatever is in flight stands between an
          arriving prompt's prefill and its first token, so the tier
          starts at ONE step and climbs, through the pressure tier's k,
          towards ``decode_block``, its CEILING, only while the loop sees
          the device run dry with less (_decode_step reports every
          idle-tier dispatch to the rule).

        With speculative decoding on, the idle tier is additionally capped
        at spec_draft_len: a draft can only continue the CURRENT head
        token, and the engine probes for drafts once per loop iteration,
        so long decode blocks would skip almost every draft opportunity
        (the head lands mid-block). Verify rounds are themselves k+1 fused
        steps, and on non-repetitive traffic the cap is the documented
        cost of leaving the flag on.

        With a block length B above 1 the tiers count whole BLOCKS: the
        configuration's token counts over B, at least one (_blocks_of)."""
        if self._admissions_blocked():
            self._last_tier = "admit"
            return self._blocks_of(1)
        if self._waiting:
            self._last_tier = "pressure"
            return self._blocks_of(min(self.cfg.pressure_decode_block,
                                       self.cfg.decode_block))
        self._last_tier = "idle"
        k = self._lead.k
        if self._spec_on:
            k = min(k, max(1, self.cfg.spec_draft_len))
        return k

    def _blocks_of(self, tokens: int) -> int:
        """A decode-block tier given in tokens as what a dispatch runs:
        that many steps, or (block length B above 1) whole blocks."""
        return max(1, tokens // self._block_len)

    def _passes_of(self, k: int) -> int:
        """Passes over the model that a dispatch of ``k`` runs: k steps, or
        (block length above 1) k blocks of ``denoise_passes`` passes each,
        the first of them over two blocks (it keeps the block before):
        ONE pass, one read of the weights, one call of each kernel a
        layer."""
        if self._block_len == 1:
            return k
        return k * self.model_cfg.denoise_passes

    def _slot_index(self, slots, width: int):
        """``slots`` as the index vector of a fixed-shape program: int32
        [width], padded with the trash row. numpy on purpose: what the loop
        thread hands a program is a host array or what a program returned,
        never the result of an eager device op (which would run, and on a
        busy device wait, on this thread)."""
        idx = np.full((width,), self.cfg.max_batch_size, np.int32)
        idx[: len(slots)] = slots
        return idx

    def _flush_slot_patches(self, dirty: dict, overrides: dict):
        """Apply queued slot-state patches at the fixed B+1 shape (trash-
        row padded — see the compile-stall note on _patch_state) and
        return the patched device token vector. A patch carries slot state
        and host-known tokens only: a prefill writes its first token
        itself. Shared by the decode and verify-k dispatch paths; loop
        thread only."""
        with self._prof.span("patch_flush", dirty=len(dirty),
                             overrides=len(overrides)):
            trash_row = self.cfg.max_batch_size
            if dirty:
                # fixed-shape patch: pad to B+1 rows onto the trash row
                # (whose state is all-zeros by invariant), so ONE compiled
                # scatter covers every dirty-count
                order = sorted(dirty)
                ptv = np.zeros((trash_row + 1, self._table_width),
                               np.int32)
                ptv[: len(order)] = self.page_tables[order]
                slv = np.zeros((trash_row + 1,), np.int32)
                slv[: len(order)] = [dirty[i][0] for i in order]
                tv = np.zeros((trash_row + 1,), np.float32)
                tv[: len(order)] = [dirty[i][1] for i in order]
                self._pt_dev, self._sl_dev, self._temps_dev = \
                    self._patch_state(
                        self._pt_dev, self._sl_dev, self._temps_dev,
                        self._slot_index(order, trash_row + 1),
                        ptv, slv, tv)
            toks = self._dev_tokens
            if overrides:
                # host ints (a verify round's rollback, a disaggregated
                # adoption), at the same fixed shape (trash-row writes of
                # 0) as the state patch
                ovals = np.zeros((trash_row + 1,), np.int32)
                ovals[: len(overrides)] = list(overrides.values())
                toks = self._patch_toks(
                    toks, self._slot_index(list(overrides), trash_row + 1),
                    ovals)
            return toks

    def _step(self) -> bool:
        """Dispatch the iteration's device work: a speculative verify-k
        round for slots with drafts (spec_decode_enabled), then one fused
        decode block for the rest; then hold the loop to its lead.

        HOW MANY ENTRIES THE LOOP RUNS AHEAD OF THE DEVICE IS DECIDED
        HERE AND NOWHERE ELSE: at most PIPELINE_DEPTH entries (decode
        blocks, verify rounds, prefills' first tokens) stay in flight,
        and the harvests below block until the device has retired the
        rest. What an entry holds is _select_block's: the two together
        are the device work an arriving prompt's prefill runs behind,
        and the longest the loop sits in one harvest before it admits.
        Every admission queues an entry of its own beside the pass's
        block, so trimming one entry a dispatch let the backlog grow by
        one with each admission, and every stream's tokens reached its
        caller that much later. The other half of the rule is that
        nothing else on this thread waits for the device (_slot_index).
        The excess is counted ONCE: a verify round's harvest can queue
        its successor, and a loop on the length would follow that chain
        for as long as its drafts hit."""
        did_spec = self._spec_on and self._spec_step()
        dispatched = self._decode_step() or did_spec
        for _ in range(len(self._pending) - self.PIPELINE_DEPTH):
            self._harvest_one()
        return dispatched

    def _decode_step(self) -> bool:
        """Dispatch one fused decode block (_select_block's k steps: one
        of the tiers, decode_block at most) without waiting for
        its result; _step harvests PIPELINE_DEPTH entries behind. Device
        execution is a single ordered stream, so an in-flight block that
        still references a freed slot's pages runs BEFORE any later
        prefill that reuses them.

        Steady-state decode is ONE jitted call with all-device arguments
        (page tables, seq lens, temps, last tokens, rng all live on device;
        slot admissions patch them with one small jitted update). A block
        of k steps pays a dispatch and the state gather / scatter once for
        k tokens a slot and is k steps of lead over the device. No
        program re-lays a projection out any more (the served form,
        models/block.py ``serve_params``: before ISSUE 54 a block of k
        also spread the compiler's copy of wq / wk / wv over its k steps,
        and on a v5e, Mistral-7B at depth 16 and widths 4-8, a step cost
        12.49 ms in blocks of 8, 13.35 in blocks of 2 and 13.08 alone:
        PERF.md section 6, PR 42 and PR 54). An
        idle-tier dispatch tells the rule that sizes that tier whether
        the device had run dry (lead.py).

        With a block length B above 1 the dispatch is ``k`` whole BLOCKS
        (_block_impl): k x ``denoise_passes`` passes, k x B tokens a
        slot, of which a slot's first block gives back the ``skip`` tokens
        its prompt left in it (the harvest drops them); the span is
        ``block_dispatch``. The device commits a block with the first
        pass of the NEXT one, so its seq_lens stay one block behind the
        tokens given out from a slot's second block on (``ctx_tokens``
        says what the device holds; ``fused``: the passes of two blocks
        that keep a block for some slot, all but a first iteration whose
        slots are all new)."""
        bl = self._block_len
        with self._lock:
            snapshot = [(i, i, req) for i, req in enumerate(self.slot_req)
                        if req is not None
                        and req.dispatched < req.max_tokens
                        and not req.spec_inflight]
            if not snapshot:
                return False
            # Overshoot past a request's max_tokens is by-design safe:
            # extra writes land in the slot's own tail pages or the trash
            # page, and harvest discards them.
            k = self._select_block()
            self._last_block = k
            tier = self._last_tier
            self.stats[f"dispatch_tier_{tier}_total"] += 1
            dirty, self._dirty_slots = self._dirty_slots, {}
            overrides, self._overrides = self._overrides, {}
            # tokens in the cache of the block's slots as its first step
            # starts (what the device's seq_lens hold): the live context;
            # live_pages: the pages that hold that step's keys (a slot's
            # context and the step's own bl positions)
            ctx_tokens = live_pages = window_tokens = 0
            skips = []
            fused = k - 1
            for _col, _slot, req in snapshot:
                if bl > 1:
                    plen = len(req.prompt_tokens)
                    ctx = (plen + req.dispatched) // bl * bl
                    if req.dispatched:      # a clean block is pending
                        ctx -= bl
                        fused = k
                    skips.append(0 if req.dispatched else plen % bl)
                    req.dispatched += k * bl - skips[-1]
                else:
                    ctx = len(req.prompt_tokens) + req.dispatched - 1
                    req.dispatched += k
                ctx_tokens += ctx
                live_pages += -(-(ctx + bl) // self.cfg.page_size)
                if self._windowed:
                    # what a window layer's read of the slot walks
                    window_tokens += min(ctx, self._cache_spec.window)
                    self._count_recycled(req, ctx + k)
            table_pages = len(snapshot) * self.max_pages_per_seq
            self.stats["attn_live_pages_total"] += live_pages
            self.stats["attn_table_pages_total"] += table_pages
        # bucketed width: pack the active slots, pad with the trash row —
        # a lightly loaded engine runs a narrow program
        active_slots = [slot for _c, slot, _r in snapshot]
        w = self._bucket_width(len(active_slots))
        self._block_seq = seq = self._block_seq + 1
        # decode_dispatch times the HOST cost of getting the block onto
        # the device stream (patch flush + jit dispatch); the result sync
        # is the harvest phase. The pipeline-trim harvests in _step are
        # excluded — they're already sampled inside _harvest_one.
        # inflight: entries pending as this block is dispatched; trimmed:
        # the harvests the bound then forces (_step) — a trace says how
        # often, and how hard, the bound engages. lead: the passes those
        # entries hold (a prefill's first token: none), what this block
        # and any prefill after it run behind. dry: the device had
        # nothing queued (_dry): the dispatch that ends an idle gap.
        inflight = len(self._pending)
        lead = sum(e[2][1] + 1 if isinstance(e[2], tuple)
                   else self._passes_of(e[2])
                   for e in self._pending if e[3] >= 0)
        passes = self._passes_of(k)
        how = {"blocks": k, "passes": passes, "fused": fused} if bl > 1 \
            else {"k": k}
        # draws: a row of the dispatch asks for a temperature, so the
        # program's sampler draws (kv_cache.sample_tokens reads the same
        # in the device's temperatures, which hold each request's)
        draws = any(req.temperature > 0 for _c, _s, req in snapshot)
        dry = self._dry()
        if tier == "idle":
            self._lead.observe(dry, self._collector.pause_n)
        with self._prof.span("block_dispatch" if bl > 1
                             else "decode_dispatch", seq=seq, **how, w=w,
                             active=len(active_slots),
                             ctx_tokens=ctx_tokens, live_pages=live_pages,
                             **({"window_tokens": window_tokens}
                                if self._windowed else {}),
                             table_pages=table_pages, inflight=inflight,
                             lead=lead,
                             trimmed=max(
                                 0, inflight + 1 - self.PIPELINE_DEPTH),
                             draws=int(draws), dry=dry):
            toks = self._flush_slot_patches(dirty, overrides)
            idx = self._slot_index(active_slots, w)
            snapshot = [(col, slot, req, *skips[col:col + 1])
                        for col, (_c, slot, req) in enumerate(snapshot)]
            with self._prof.compile_scope(
                    "decode", ("decode", w),
                    mid_traffic=self.stats["requests"] > 0):
                all_toks, self._dev_tokens, self.kv, self._sl_dev, \
                    self._rng, *touched = self._decode(
                        self.params, self.kv, self._pt_dev, self._sl_dev,
                        toks, self._rng, self._temps_dev, idx, np.int32(k))
            self._newest = all_toks
            self._start_fetch(all_toks)
            dev_touched = touched[0] if touched else None
            # a routed block's count of experts (a block program's counts)
            if dev_touched is not None:
                self._start_fetch(dev_touched)
            self._pending.append((all_toks, snapshot, k, seq, dev_touched))
            self.stats["steps"] += passes
            self.stats["attn_decode_dispatches"] += 1
            self.stats["greedy_dispatches"] += not draws
        return True

    # ---- speculative decoding ------------------------------------------
    def _propose_locked(self, req: _Request) -> list[int]:
        """Draft tokens for one slot (lock held). Greedy slots only — the
        bit-identity guarantee is a greedy property; non-greedy slots ride
        the normal decode path untouched. The draft is capped so a fully
        accepted round cannot emit past max_tokens."""
        if req.temperature != 0.0:
            return []
        remaining = req.max_tokens - len(req.generated)
        if remaining <= 1:
            return []
        if req.spec is None:
            from ray_tpu.serve.llm import spec_decode
            req.spec = spec_decode.NGramProposer(
                self.cfg.spec_ngram_max, self.cfg.spec_draft_len)
        draft = req.spec.propose(req.prompt_tokens + req.generated)
        return draft[: remaining - 1]

    def _dispatch_verify(self, rows) -> None:
        """Dispatch ONE verify-k round for ``rows`` of (slot, req, draft,
        base_len) whose host state is exact (just drained or just
        harvested). Loop thread only; lock NOT held."""
        k = self.cfg.spec_draft_len
        with self._lock:
            for _slot, req, _draft, _base in rows:
                req.spec_inflight = True
                req.dispatched += k + 1
            dirty, self._dirty_slots = self._dirty_slots, {}
            overrides, self._overrides = self._overrides, {}
        spec_slots = [slot for slot, _r, _d, _b in rows]
        w = self._bucket_width(len(spec_slots))
        self._block_seq = seq = self._block_seq + 1
        with self._prof.span("verify_dispatch", seq=seq, k=k, w=w,
                             dry=self._dry()):
            toks = self._flush_slot_patches(dirty, overrides)
            idx = self._slot_index(spec_slots, w)
            draft_mat = np.full((w, k), -1, np.int32)
            entry = []  # (col, slot, req, draft, base_len)
            for col, (slot, req, draft, base_len) in enumerate(rows):
                draft_mat[col, : len(draft)] = draft
                entry.append((col, slot, req, draft, base_len))
            with self._prof.compile_scope(
                    "verify", ("verify", w, k),
                    mid_traffic=self.stats["requests"] > 0):
                all_toks, self._dev_tokens, self.kv, self._sl_dev, \
                    self._rng = self._verify(
                        self.params, self.kv, self._pt_dev, self._sl_dev,
                        toks, self._rng, self._temps_dev, idx, draft_mat)
            self._newest = all_toks
            self._start_fetch(all_toks)
            self._pending.append((all_toks, entry, ("spec", k), seq, None))
            self.stats["steps"] += k + 1
            self.stats["attn_verify_dispatches"] += 1

    def _spec_step(self) -> bool:
        """TRANSITION decode-mode slots with drafts into verify rounds.

        Speculation needs the host's view of a slot to be authoritative
        (drafts continue the slot's true token sequence, and rollback
        needs its true cache length), so entering spec mode drains the
        in-flight pipeline once — every entry's successors are already
        dispatched on the ordered device stream, so those harvests are
        bounded by work the device is retiring anyway. After that the slot
        CHAINS drain-free: each verify harvest leaves its host state
        exact, so _apply_verify re-proposes and dispatches the next round
        directly, and the slot only falls back into decode blocks when a
        draft misses. Slots without a draft are left to _decode_step in
        the same iteration (their blocks never touch a chained slot:
        spec_inflight excludes it from decode snapshots). A cheap
        pre-check on the (possibly pipeline-stale) host context avoids
        paying the drain when nothing would draft."""
        with self._lock:
            # gate on generated (host truth lower bound), NOT dispatched:
            # pipelined decode runs dispatched ahead to max_tokens within a
            # few blocks, which would silence speculation for the rest of
            # the generation. A stale-context false positive just costs the
            # drain (the post-drain re-propose is authoritative).
            if not any(req is not None and not req.done
                       and len(req.generated) < req.max_tokens
                       and not req.spec_inflight
                       and self._propose_locked(req)
                       for req in self.slot_req):
                return False
            n = len(self._pending)
        # drain the entries present NOW: chained verify rounds appended by
        # these harvests belong to already-speculating slots and never
        # reference the transitioning ones
        for _ in range(n):
            self._harvest_one()
        with self._lock:
            rows = []  # (slot, req, draft, base_len)
            for slot, req in enumerate(self.slot_req):
                if req is None or req.spec_inflight \
                        or req.dispatched >= req.max_tokens:
                    continue
                draft = self._propose_locked(req)
                if not draft:
                    continue
                # device cache length for this slot: prompt + every
                # recorded token except the current one (which is the
                # verify round's position-0 input). Exact because the
                # pipeline was just drained.
                base_len = len(req.prompt_tokens) + len(req.generated) - 1
                rows.append((slot, req, draft, base_len))
        if not rows:
            return False
        self._dispatch_verify(rows)
        return True

    def _apply_verify(self, dev_toks, rows, k: int, seq: int) -> None:
        """Record a verify round: per slot, accept the longest draft
        prefix matching the per-position outputs, emit accepted+1 tokens
        through _record_token (stream ordering unchanged), and roll the
        slot's seq_len back past the rejected tail via the dirty-slot
        patch. Rollback is pure length accounting — no allocator calls, so
        shared prefix-cache pages are never decreffed or evicted by a
        rejection; the junk KV past the new length sits in the slot's own
        suffix pages and is overwritten before it can be attended.

        Slots whose fresh context drafts again chain straight into the
        next verify round (their just-harvested host state is exact — no
        pipeline drain needed); the rest drop back to decode blocks."""
        self._see_device()
        with self._prof.span("harvest", seq=seq, k=k + 1):
            dev_toks.block_until_ready()  # device sync (oldest round)
        with self._prof.span("fetch", seq=seq, k=k + 1):
            host = np.asarray(dev_toks).reshape(k + 1, -1)
        with self._prof.span("emit", seq=seq) as sp:
            chain, tokens, finished = self._emit_verified(host, rows, k)
            sp.set(tokens=tokens, finished=finished)
        if chain:
            self._dispatch_verify(chain)

    def _emit_verified(self, host, rows, k: int) -> tuple[list, int, int]:
        """The host half of a verify round (see _apply_verify): returns
        the rows that chain into the next round, the tokens emitted and
        the requests finished."""
        from ray_tpu.serve.llm import spec_decode
        finished: list[_Request] = []
        chain = []  # (slot, req, draft, base_len)
        tokens = 0
        with self._lock:
            self.stats["spec_rounds"] += 1
            for col, slot, req, draft, base_len in rows:
                req.spec_inflight = False
                outs = [int(host[s, col]) for s in range(k + 1)]
                a = spec_decode.accept_length(draft, outs)
                self.stats["spec_drafted_tokens"] += len(draft)
                self.stats["spec_accepted_tokens"] += a
                emitted = 0
                for tok in outs[: a + 1]:
                    if req.done:
                        break  # stop token inside the accepted run
                    self._record_token(req, tok)
                    emitted += 1
                tokens += emitted
                if req.done:
                    finished.append(req)
                    if self.slot_req[slot] is req:
                        self.slot_req[slot] = None
                        self.free_slots.append(slot)
                        self.page_tables[slot] = 0
                        self.seq_lens[slot] = 0
                        self._dirty_slots[slot] = (0, 0.0)
                    continue
                # roll back: device seq_len advanced k+1 during the round;
                # the truth is base_len + emitted (the accepted tokens are
                # in cache, the last emitted token is the new current one)
                new_len = base_len + emitted
                self.seq_lens[slot] = new_len
                self._dirty_slots[slot] = (new_len, req.temperature)
                self._overrides[slot] = outs[emitted - 1]
                req.dispatched = len(req.generated)
                nxt = self._propose_locked(req)
                if nxt:
                    chain.append((slot, req, nxt, new_len))
        self._finish_requests(finished)
        return chain, tokens, len(finished)

    def _harvest_one(self) -> None:
        """Block on the OLDEST in-flight block's tokens and record them.

        Entries are decode blocks (tokens at the PACKED bucket width, of
        which rows [:k] are the dispatch's: the program's buffer has the
        ceiling tier's rows — the column is the request's position in that
        block's packed index vector, NOT its slot id), prefill first-tokens
        (scalar, column 0) with snapshot rows (token_column, slot,
        request), or verify-k rounds (meta ("spec", k), handled by
        _apply_verify)."""
        with self._lock:
            if not self._pending:
                return
            dev_toks, snapshot, k, seq, dev_touched = self._pending.pop(0)
        if isinstance(k, tuple):  # ("spec", draft_len) verify round
            self._apply_verify(dev_toks, snapshot, k[1], seq)
            return
        # a block dispatch (rows of (col, slot, req, skip)): k blocks of B
        # token rows each, run as ``passes`` passes (the first of a block
        # over two blocks: counted once)
        bl = self._block_len
        passes = self._passes_of(k)
        # THE device sync: all device slowness surfaces here, attributed
        # as "harvest" instead of smeared across the loop. The span holds
        # the wait and nothing else (the GIL is released in it): what the
        # host does with the result is its sibling, fetch
        self._see_device()
        with self._prof.span("harvest", seq=seq, k=k):
            dev_toks.block_until_ready()    # sync point: oldest block only
        with self._prof.span("fetch", seq=seq, k=k) as sp:
            # the copy was started at dispatch (_start_fetch)
            host_toks = np.asarray(dev_toks)
            # a routed block: the experts its steps touched, an output of
            # the same program as the tokens (no sync of its own)
            if dev_touched is not None:
                counts = [int(n)
                          for n in np.atleast_1d(np.asarray(dev_touched))]
                routed = self._cache_spec.routed_layers
                # rows a slot: a token a step, or B positions a pass and B
                # more for each block's pass of two blocks
                rows = (passes + (k if bl > 1 else 0)) * bl
                sp.set(experts_touched=counts[0], expert_visits=counts[-1])
                self.stats["experts_touched_total"] += counts[0]
                self.stats["expert_visits_total"] += counts[-1]
                self.stats["routed_layer_steps_total"] += passes * routed
                self.stats["expert_rows_total"] += (
                    routed * len(snapshot) * rows * self._cache_spec.top_k)
        if bl > 1:
            _touched, fused, kept, _visits = counts
            self.stats["block_passes_total"] += passes
            self.stats["denoise_passes_total"] += passes
            self.stats["fused_passes_total"] += fused
            self.stats["slot_passes_total"] += passes * len(snapshot)
            self.stats["blocks_committed_total"] += kept
        # (a prefill's first token is a scalar; a block's rows past its k
        # are the buffer's, never the dispatch's)
        host_toks = np.atleast_2d(host_toks)[: k * bl]
        # emit: what follows the sync on the host — up to k x w
        # _record_token calls under the lock, then the completion tail
        with self._prof.span("emit", seq=seq) as sp:
            finished: list[_Request] = []
            tokens = 0
            with self._lock:
                for step in range(k * bl):
                    for col, slot, req, *skip in snapshot:
                        if skip and step < skip[0]:
                            continue  # the prompt's own, left in its block
                        if req.done:
                            # stop/max lag: discard overshoot
                            self.stats["tokens_cut_total"] += bl > 1
                            continue
                        self._record_token(req, int(host_toks[step, col]))
                        tokens += 1
                        if req.done:
                            finished.append(req)
                            if self.slot_req[slot] is req:
                                self.slot_req[slot] = None
                                self.free_slots.append(slot)
                                self.page_tables[slot] = 0
                                self.seq_lens[slot] = 0
                                # invalidate the DEVICE row too: a stale
                                # device page table keeps scattering this
                                # slot's junk KV into pages after they're
                                # reallocated
                                self._dirty_slots[slot] = (0, 0.0)
            self._finish_requests(finished)
            sp.set(tokens=tokens, finished=len(finished))

    def _finish_requests(self, finished: list[_Request]) -> None:
        """Completion tail shared by decode and verify harvests: free
        pages, release waiters, emit trace spans, reap abandoned."""
        for req in finished:
            self._release_pages(req)
        for req in finished:
            req.done_event.set()
            if req.trace_ctx:
                from ray_tpu.observability import tracing
                tracing.record_span(
                    "llm.generate", req.submitted_wall, time.time(),
                    parent=req.trace_ctx, kind="llm",
                    attrs={"request_id": req.request_id,
                           "prompt_tokens": len(req.prompt_tokens),
                           "generated_tokens": len(req.generated)})
            if getattr(req, "abandoned", False):
                with self._lock:
                    self._requests.pop(req.request_id, None)
