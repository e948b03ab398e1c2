"""Paged KV cache + paged attention steps for continuous batching.

The TPU-native analog of vLLM's PagedAttention (the reference delegates to
it — python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:101):
KV lives in a fixed pool of fixed-size pages in HBM; each decode slot owns a
page table mapping logical sequence positions to pool pages. All shapes are
static (slot count, page count, pages-per-slot), so the decode step compiles
ONCE and every iteration reuses the same XLA program — the crucial property
on TPU, where recompilation would dwarf the step itself.

Design choices:
- attention over the paged pool dispatches through ONE backend switch
  (``LLMConfig.attention_kernel``, resolved once by
  :func:`resolve_attention_backend`): ``"pallas"`` runs the fused kernel
  family in ray_tpu/ops/paged_attention.py — decode, multi-query verify,
  and chunked prefill all read K/V pages directly from the pool via the
  slot page table (scalar-prefetch block index maps; no materialized
  gather per layer per step) and reproduce the gather path's dense-softmax
  numerics bit-exactly; ``"gather"`` materializes the full per-slot view
  + dense softmax. Auto resolution picks pallas on TPU (when the
  kernel's tiling accepts the shapes) and gather elsewhere; an explicit
  "pallas" the kernel cannot tile raises; tests force the pallas backend
  in interpreter mode on CPU. Which kernel BODY a call runs (one that
  walks a slot's live pages, or the grid over its whole table) is that
  file's table ``WALKS_LIVE``, by the pool's kind and the call's kind:
  every call walks but the chunk call on pools of K and V per head of a
  block without window layers;
- the pool is LAYER-INDEXED AND CARRIED IN PLACE: inside every paged
  program the whole pool [n_layers, Hkv, P, page, D] is a carry of the
  scan over layers (and, in the engine's decode block, of the scan over
  steps around it), never one of its ``xs``/``ys``. The layer is a dynamic
  index into the carry — the token write is one scatter of rows at
  ``[l, h, page, offset]``, the kernels pick the layer in their block
  index maps, the gather backend gathers ``pool[l, :, page_tables]`` — so
  no program slices a layer's pool out of the stack, restacks it, or
  copies the pool to update it (tests/test_pool_carry.py holds that);
- the layer of a block is written ONCE: a program that reads the cache
  back states its :class:`_Geometry` (where this call's rows go, how it
  reads them back) and runs :func:`_layer`, the mixer of the layer's kind
  (``_MIXERS``), then its feed-forward; a mixer names no program;
- writes are scatters at (layer, head, page, offset) indices; inactive
  slots write to a reserved trash page (page 0), keeping the step free of
  dynamic shapes and `lax.cond`s. Where the kernel that reads a call's
  pages back walks them (pools of K and V per head, one chip: ops/
  paged_attention.py ``writing_calls``: the decode and verify calls of
  every such block, a block pass, and the chunk call too of a block with
  window layers; NOT the chunk call of a block without them, a latent
  pool's calls, the gather backend or a tensor-parallel mesh), the call's
  rows ride in that kernel and it writes them (:func:`_write_read`): the
  same bytes in the same rows, where the rows go decided here all the
  same;
- full (non-chunked) prefill stays dense within the prompt: it runs at
  B=1 per admission with no cached prefix to read back;
- a block that generates by diffusion over blocks (its cache spec states a
  ``block_length`` above 1, models/block.py) runs the same programs under
  the block mask (:func:`_visible`): prefill and the chunk program commit a
  prompt's whole blocks, :func:`paged_block_step` runs one pending
  block's positions against the cache, committing or not, and
  :func:`paged_block_pair_step` a clean pending block and the one after
  it in one pass (the engine's: the commit rides with the next denoise);
  on the pallas backend their kernel call (``paged_block_attention``)
  runs the body that walks each slot's live pages, ops/paged_attention.py;
- a block whose cache spec states ``latent_dim`` keeps ONE row a token and
  layer (:func:`_latent_mixer`): the pool is one array, a whole prompt is
  attended in the mixer's expanded form (per-head keys and values, nothing
  read back), and every program that reads the cache runs the absorbed
  form against the pool, all heads on the one row, the values a prefix of
  the row's lanes (the pallas backend: the latent body of ops/
  paged_attention.py, whose work follows each slot's live length);
- a block whose cache spec states a ``window`` has WINDOW LAYERS beside
  its full ones (models/block.py): their K and V lie in a pool of their
  own (``kw`` / ``vw``) as a RING of :func:`ring_pages` pages a slot,
  position p in entry ``(p // page) % ring`` of the slot's ring table, which
  is the TAIL of the page table the programs are handed (the full layers'
  growing table first, ``ceil(cfg.max_seq_len / page)`` entries wide).
  :func:`_layer_geometry` gives each layer its table, its place for the
  call's rows and its lower edge; every paged read of such a block is the
  walking body's (``window=``) or, on the gather backend, the ring's mask;
  the window layers may keep another number of KV heads than the full
  ones, the value rows may be narrower than the key rows (which are then
  stored on whole 128-lane vectors, :func:`key_lanes`), and a layer's
  softmax may hold a learned sink (the mixer kind "sink"): the pools'
  shapes, :func:`_write_read`'s padding and :func:`_dense_attention`'s
  extra column carry these, no program does;
- tensor parallelism (ISSUE 20): every step function takes an optional
  ``mesh``. With a live "tensor" axis the pool is sharded per-KV-head
  (axis 1) and the q heads split into exactly the matching kv-head
  groups (GQA head order is kv-major), so per-head attention has ZERO
  cross-shard communication; only the wo/w_down row-parallel psums and
  the vocab-sharded argmax cross chips. The gather backend partitions
  under plain GSPMD/pjit; the Pallas kernels are opaque to GSPMD and run
  under ``shard_map`` — each shard's kernel invocation is shape-wise
  identical to the single-chip call on a pool with Hkv/tp heads.

Page 0 is RESERVED as the trash page; the allocator never hands it out.

This module alone knows the DEVICE pool's format (two arrays, pages on axis
2, KV heads on axis 1, or ONE array of one row a token where the block's
cache spec states a latent cache; beside them whatever else the spec
asks for: ``init_paged_cache``): the engine, disaggregation and the tier
path move pages through the page operations below ``init_paged_cache``.
It names no architecture: the block comes with the model configuration
(models/block.py), a layer definition that the programs below walk. The HOST blobs
that kv_tier.py, kv_codec.py and disagg's wire codec carry (pairs of arrays
with pages on axis 2) keep their own format, which those modules own.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import logging
import threading
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.models.block import block_of, gqa_expand

logger = logging.getLogger(__name__)


def ring_pages(window: int, page_size: int, span: int) -> int:
    """Pages a slot holds for ONE window layer, whatever its context: the
    window, the widest span one call writes before it reads (a prefill
    chunk) and one page more, since neither edge need lie on a page's. A
    call at positions [s, s + span) overwrites the pages ``ring`` before
    the ones it writes, whose last token lies at or below ``s - window``:
    out of every query's sight from s on."""
    return -(-(window + span) // page_size) + 1


def init_paged_cache(cfg, num_pages: int, page_size: int, tp: int = 1,
                     window_pages: int = 0, state_rows: int = 0):
    """What the block's cache spec (models/block.py) asks for, a pytree
    (``tp``: the chips the pool's heads will be split over).

    ``k`` / ``v``: the KV pool [paged_layers, n_kv_heads, num_pages,
    page_size, head_dim], one row a layer that attends. Heads of 64 lie
    two to a 128-lane row, [paged_layers, n_kv_heads / 2, num_pages,
    page_size, 128] (:func:`pool_heads_lanes`): a 64-lane minor dimension
    would be padded to 128 in HBM, or re-laid out by the compiler around
    every token write. The head-major page
    layout is what the Pallas paged-attention kernels (ops/
    paged_attention.py) consume directly — the whole pool plus a layer
    index, per layer [Hkv, P, page, D] — so the paged programs run them with
    no relayout and no per-layer slice; the gather backend indexes the same
    pool. The paged programs carry both arrays through their loops and only
    ever update them in place (see the module docstring).

    A LATENT cache (the spec states ``latent_dim``): ``k`` alone, [paged_
    layers, 1, num_pages, page_size, lanes], one row a token whose first
    ``value_dim`` lanes are also its values; ``lanes`` is ``latent_dim``
    rounded up to whole 128-lane vectors (:func:`latent_lanes`), the
    padding zeros for ever. There is no ``v``.

    ``kw`` / ``vw`` (a block with window layers only): the same rows for
    the layers that keep a window, [window_layers, n_kv_heads,
    window_pages, page_size, head_dim]: a pool of its own, sized by the
    rings (slots x :func:`ring_pages` and the trash page), not by
    ``max_seq_len``; ``k`` / ``v`` then hold the full layers alone. The
    window layers may keep another number of KV heads than the full ones
    (the spec's ``window_kv_heads``).

    VALUE ROWS NARROWER THAN KEY ROWS (the spec states ``value_dim`` and no
    ``latent_dim``): the key pools' rows are ``head_dim`` lanes rounded up
    to whole 128-lane vectors (:func:`key_lanes`: 192 -> 256, the padding
    zeros for ever, as a latent pool's), the value pools' ``value_dim``:
    ``k`` [paged_layers, n_kv_heads, num_pages, page_size, key lanes], ``v``
    [.., value_dim], ``kw`` / ``vw`` alike at their own heads.

    ``state`` (a block with slot state only), an entry a layer that keeps
    state: one array [rows, prod(state_shape)] (flat: a [2, D] row would
    be padded to whole sublane tiles), or, where the spec states
    ``state_arrays``, a tuple of arrays [rows, *shape], each at its own
    dtype (a convolution's columns flat in the activations' dtype beside
    a recurrent state [rows, H, N, P] in float32). A sequence's row is its
    FIRST PAGE, so the state rides the page table: the allocator that
    hands a sequence its pages has handed it its state row, the programs
    need no slot argument, and inactive lanes (a page table of zeros) meet
    in the trash page's row. ``rows`` is ``num_pages``, or ``state_rows``
    where the caller says so: a block whose spec states ``state_per_slot``
    takes its first pages from a reserved range (:class:`PageAllocator`
    ``first_pages``), so the engine holds slots + 1 rows and not a row a
    page (4 MB a row and layer would not fit a row a page). Nothing zeroes
    a row: a program that starts a sequence (a whole prefill, a chunk with
    ``start`` 0) reads zeros instead of the row. Two sequences must never
    share a first page, so a block with slot state takes no prefix reuse
    (:func:`has_slot_state`).

    ``routing`` (a block with routed experts only): int32 [routed_layers,
    max_seq_len, top_k], the experts the LAST program call chose for each
    of its token rows."""
    spec = block_of(cfg).cache_spec(cfg)
    if spec.latent_dim:
        kv = {"k": jnp.zeros((spec.paged_layers, 1, num_pages, page_size,
                              latent_lanes(spec.latent_dim)), cfg.dtype)}
    else:
        heads, *lanes = pool_rows(spec, spec.n_kv_heads, tp)
        kv = {n: jnp.zeros((spec.paged_layers, heads, num_pages, page_size,
                            w), cfg.dtype) for n, w in zip("kv", lanes)}
        if spec.window_layers:
            if window_pages < 2:
                raise ValueError(
                    f"the block has {spec.window_layers} window layers: "
                    f"their pool needs window_pages (a trash page and a "
                    f"ring a slot), got {window_pages}")
            heads, *lanes = pool_rows(
                spec, spec.window_kv_heads or spec.n_kv_heads, tp)
            for n, w in zip(("kw", "vw"), lanes):
                kv[n] = jnp.zeros((spec.window_layers, heads, window_pages,
                                   page_size, w), cfg.dtype)
    if spec.state_layers:
        rows = state_rows or num_pages
        if spec.state_arrays:
            kv["state"] = tuple(
                tuple(jnp.zeros((rows, *shape), dtype or cfg.dtype)
                      for shape, dtype in spec.state_arrays)
                for _ in range(spec.state_layers))
        else:
            kv["state"] = tuple(
                jnp.zeros((rows, int(np.prod(spec.state_shape))), cfg.dtype)
                for _ in range(spec.state_layers))
    if spec.routed_layers:
        kv["routing"] = jnp.zeros(
            (spec.routed_layers, cfg.max_seq_len, spec.top_k), jnp.int32)
    return kv


def pool_heads_lanes(n_kv_heads: int, head_dim: int,
                     tp: int = 1) -> tuple[int, int]:
    """(rows of heads, lanes a row) of the pool's two minor-most head
    dimensions: a head a row, except that heads of 64 lie two to a
    128-lane row (KV head 2i in lanes 0-63, 2i + 1 in lanes 64-127)
    wherever each of the ``tp`` chips the rows are split over then holds
    whole rows: an odd share of heads stays a head a row (and off the
    Pallas kernels: ``paged_attention.can_tile``). The token write and the
    gather read reshape between the two views; the Pallas wrapper
    (ops/paged_attention.py) reads the packed rows."""
    if head_dim == 64 and n_kv_heads % (2 * tp) == 0:
        return n_kv_heads // 2, 128
    return n_kv_heads, head_dim


def pool_rows(spec, n_kv_heads: int, tp: int = 1) -> tuple[int, int, int]:
    """(rows of heads, lanes a key row, lanes a value row) of the pools of
    K and V a head that hold ``n_kv_heads`` heads a layer: one width and
    :func:`pool_heads_lanes`' packing, or, where the cache spec states a
    ``value_dim`` of its own, a head a row, the keys on whole vectors
    (:func:`key_lanes`) and the values as wide as they are."""
    if spec.value_dim and not spec.latent_dim:
        return n_kv_heads, key_lanes(spec.head_dim), spec.value_dim
    heads, lanes = pool_heads_lanes(n_kv_heads, spec.head_dim, tp)
    return heads, lanes, lanes


def key_lanes(head_dim: int) -> int:
    """Lanes of a key row beside a value row of another width: ``head_dim``
    rounded up to whole 128-lane vectors (192 -> 256: a quarter of the key
    pool, a sixth of both pools, is padding), for :func:`latent_lanes`'
    reasons. The padding is written as zeros and meets the zeros the query
    is padded with."""
    return latent_lanes(head_dim)


def latent_lanes(latent_dim: int) -> int:
    """Lanes of a latent pool's row: ``latent_dim`` rounded up to whole
    128-lane vectors (576 -> 640: a ninth of the pool is padding), so that
    a page block is whole vectors (``paged_attention.can_tile``) and the
    compiler has no reason to re-lay the pool out around a token write.
    The padding is written as zeros and meets zeros in the query."""
    return -(-latent_dim // 128) * 128


def has_latent_cache(cfg) -> bool:
    """Whether the block keeps ONE row a token (a pool of one array, no K
    and V): the kv tier's and the hand-off's host blobs are pairs of K and
    V pages, so the engine does neither for such a block (and counts)."""
    return block_of(cfg).cache_spec(cfg).latent_dim > 0


def has_window_layers(cfg) -> bool:
    """Whether some of the block's layers keep a window of their tokens in
    a ring of pages: a page of theirs is written again while its sequence
    lives, so prefix reuse (a shared page must hold its tokens), the kv
    tier, speculative rollback and disaggregated handoff, which all move
    or share pages of ONE kind, do not happen for such a block (the engine
    counts each)."""
    return block_of(cfg).cache_spec(cfg).window_layers > 0


def has_slot_state(cfg) -> bool:
    """Whether the block keeps per-sequence state beside its pages (a
    convolution's last columns, a recurrent state a head: whatever its
    cache spec's ``state_layers`` keep): state that pages, ``seq_len`` and
    a page table do not restore, and that a page could not carry where it
    is megabytes a layer. Prefix reuse,
    the kv tier, speculative rollback and disaggregated handoff move pages
    only, so the engine does none of them for such a block (and counts)."""
    return block_of(cfg).cache_spec(cfg).state_layers > 0


def page_raw_nbytes(cfg, page_size: int) -> int:
    """Pre-codec bytes ONE pool page holds across all layers, k + v —
    the unit the tier spills and the restore stream lands. Derived from
    the pool spec (not a live array) so byte-budget callers (stream
    prefetch window, chunk sizing) can size before any page exists."""
    spec = block_of(cfg).cache_spec(cfg)
    if spec.latent_dim:
        per = spec.paged_layers * page_size * latent_lanes(spec.latent_dim)
        return per * np.dtype(cfg.dtype).itemsize
    per = spec.paged_layers * spec.n_kv_heads * page_size * spec.head_dim
    return 2 * per * np.dtype(cfg.dtype).itemsize


def pool_nbytes(kv) -> int:
    """Bytes the whole pool holds on the device(s): k + v (window layers'
    too), or the one array of a latent cache."""
    return int(sum(kv[n].nbytes for n in ("k", "v", "kw", "vw") if n in kv))


def token_nbytes(kv) -> int:
    """Bytes ONE cached token holds in the pool, all layers, each pool at
    its own heads and stored lanes (padding included; a window layer's row
    counts like a full layer's, whatever its ring recycles)."""
    return int(sum(kv[n].nbytes // (kv[n].shape[2] * kv[n].shape[3])
                   for n in ("k", "v", "kw", "vw") if n in kv))


def state_nbytes(kv) -> dict:
    """{"rows", "pool_bytes": {kind: bytes over the layers}, "bytes_per_
    slot"} of the slot state the cache holds (zeros and an empty dict for
    a block without): ``taps`` the arrays in the activations' dtype (a
    convolution's last columns), ``recurrent`` the float32 ones."""
    arrays = [a for entry in kv.get("state", ())
              for a in (entry if isinstance(entry, tuple) else (entry,))]
    kinds = {}
    for a in arrays:
        kind = "recurrent" if a.dtype == jnp.float32 and a.ndim > 2 \
            else "taps"
        kinds[kind] = kinds.get(kind, 0) + int(a.nbytes)
    return {"rows": int(arrays[0].shape[0]) if arrays else 0,
            "pool_bytes": kinds,
            "bytes_per_slot": int(sum(a.nbytes // a.shape[0]
                                      for a in arrays))}


def pool_lanes(cfg, kv) -> dict:
    """{"k": [lanes a head's key row has, lanes the pool stores for it],
    "v": ...}: where the two differ the pool holds padding (a latent row of
    576 on 640, a key row of 192 on 256) and every page read moves it."""
    spec = block_of(cfg).cache_spec(cfg)
    has = {"k": spec.latent_dim or spec.head_dim,
           "v": spec.value_dim or spec.head_dim}
    return {n: [has[n], kv[n].shape[1] * kv[n].shape[4]
                // (1 if spec.latent_dim else spec.n_kv_heads)]
            for n in ("k", "v") if n in kv}


def pool_dtype(kv):
    """The dtype pages are stored in (and a spilled blob carries)."""
    return kv["k"].dtype


def pool_spec():
    """PartitionSpec of both pool arrays on a "tensor" mesh: split per KV
    head, whole pages on every shard (see the module docstring)."""
    from jax.sharding import PartitionSpec as P
    return P(None, "tensor")


def gather_pages(kv, pages):
    """Pool pages ``pages`` ([n] ints) as a device blob pair (bk, bv), each
    [L, Hkv, n, page, D]; of a latent pool (one array) ``bv`` is None, here
    and in every page operation below."""
    pidx = np.asarray(pages, np.int32)
    return tuple(jnp.take(kv[n], pidx, axis=2) if n in kv else None
                 for n in ("k", "v"))


def scatter_pages(kv, bk, bv, pages):
    """Write blob page i of (bk, bv) into pool page ``pages[i]``. The body of
    the engine's donated inject program: the pool is rewritten in place. A
    blob padded with zero pages targets the trash page with them."""
    if bv is None:
        return {**kv, "k": kv["k"].at[:, :, pages].set(bk)}
    return {**kv, "k": kv["k"].at[:, :, pages].set(bk),
            "v": kv["v"].at[:, :, pages].set(bv)}


def fetch_pages(bk, bv, n: int):
    """Host copy of the first ``n`` pages of a :func:`gather_pages` result
    (a gather at a fixed width is padded with the trash page past them)."""
    return tuple(None if b is None else np.asarray(b)[:, :, :n]
                 for b in (bk, bv))


def zero_pages(kv, n: int):
    """A host blob pair of ``n`` zero pages in the pool's shape and dtype."""
    shape = kv["k"].shape[:2] + (n,) + kv["k"].shape[3:]
    return tuple(np.zeros(shape, kv[m].dtype) if m in kv else None
                 for m in ("k", "v"))


def pack_pages(pairs, width: int):
    """Host page pairs [(k, v), ...] (each holding one or more pages) as ONE
    blob pair of exactly ``width`` pages: laid down in order on the page
    axis, zeros behind them — the fixed shape :func:`scatter_pages` is
    compiled at."""
    first = pairs[0][0]
    shape = first.shape[:2] + (width,) + first.shape[3:]
    bk = np.zeros(shape, first.dtype)
    bv = None if pairs[0][1] is None else np.zeros(shape, first.dtype)
    at = 0
    for k, v in pairs:
        n = k.shape[2]
        bk[:, :, at:at + n] = k
        if bv is not None:
            bv[:, :, at:at + n] = v
        at += n
    return bk, bv


def _chain_digest(parent: bytes, chunk) -> bytes:
    """Hash-chain node key for one FULL page of prompt tokens: digest of
    (parent page's digest, this page's token ids). Chaining makes the key
    encode the entire token prefix, so equal digests mean equal prefixes —
    the flat-dict equivalent of a radix-tree path (SGLang RadixAttention;
    vLLM's hash-based prefix caching uses the same chained-hash trick).
    blake2b-128 so a collision (which would silently serve the wrong KV)
    is cryptographically excluded rather than merely unlikely."""
    return hashlib.blake2b(
        parent + np.asarray(chunk, np.int32).tobytes(),
        digest_size=16).digest()


class PageAllocator:
    """Host-side free list + prefix cache over the page pool (page 0
    reserved as trash).

    Mirrors vLLM's BlockAllocator role; plain Python because allocation
    happens between steps, never inside the compiled program.

    Page counts here are WHOLE-REPLICA logical pages: under tensor
    parallelism (ISSUE 20) each page physically spans every shard
    (1/tp_degree of its bytes per chip), but the allocator, the page
    tables and every occupancy/free gauge derived from them count the
    logical page once. Per-shard byte views (dashboards sizing one
    chip's HBM) divide the replica's pool bytes by tp_degree — the
    engine exports that as ``kv_shard_pool_bytes``.

    Prefix caching: pages are REFCOUNTED, and full pages of prompt tokens
    can be registered in a hash-chained index (one node per full page,
    keyed on the chain digest of every token up to the page's end). A page
    whose refcount drops to zero while indexed is not returned to the free
    list — it parks in an LRU of cached pages, its KV content intact, and
    is either resurrected by a later ``match_prefix`` (refcount 1 again,
    shared) or evicted back to the free list under pool pressure. Because
    only refcount-zero pages are evictable, eviction can never free a page
    a live slot's page table still references.

    ``cache_pages`` caps how many refcount-zero cached pages are retained
    (0 = bounded only by the pool itself).

    ``first_pages`` (a block whose state pool has a row a SLOT, models/
    block.py ``CacheSpec.state_per_slot``): pages 1..first_pages are handed
    out as a sequence's FIRST page and as nothing else, every other page as
    a later page only, so a first page names one of ``first_pages`` state
    rows. ``alloc(n)`` then takes one page of the range and n - 1 others,
    the first page first in its answer, and refuses when either kind has
    run out (``first_pages_free`` tells a caller which). Such a block takes
    no prefix reuse, so a reserved page is never indexed.

    Spilling (serve/llm/kv_tier.py): ``spill_hook``, when set, receives
    every ``(page, digest, chain_pos)`` evicted during one ``alloc()`` /
    ``free()`` call — after the allocator lock is released but BEFORE
    control returns to the caller, i.e. before the caller can dispatch
    device writes that reuse the freed pages (the hook's gather lands
    first on the ordered device stream). A raising hook is swallowed:
    the eviction has already completed, so behavior degrades to a plain
    free — no page leaks, no deadlock, just no spill.
    """

    def __init__(self, num_pages: int, cache_pages: int = 0,
                 first_pages: int = 0):
        if not 0 <= first_pages < num_pages:
            raise ValueError(f"first_pages={first_pages} of {num_pages} "
                             f"pages (page 0 is the trash page)")
        # stacks; never page 0
        self._free = list(range(num_pages - 1, first_pages, -1))
        self._free_first = list(range(first_pages, 0, -1))
        self.first_pages = first_pages
        self._lock = threading.Lock()
        self.num_pages = num_pages
        self._cache_cap = int(cache_pages)
        self._ref: dict[int, int] = {}          # live page -> refcount
        self._index: dict[bytes, int] = {}      # chain digest -> page
        self._page_key: dict[int, bytes] = {}   # indexed page -> digest
        self._page_pos: dict[int, int] = {}     # indexed page -> chain pos
        self._lru: OrderedDict[int, None] = OrderedDict()  # ref-0 cached
        self.spill_hook = None
        self.counters = {"hit_pages": 0, "miss_pages": 0, "evicted": 0,
                         "inserted": 0}
        # monotone index version: bumps whenever the set of indexed
        # digests changes (insert or eviction). Lets prefix_summary()
        # callers skip re-reading an unchanged index — the affinity
        # summary export (ISSUE 10) polls this.
        self._version = 0

    # ---- allocation ----------------------------------------------------
    def _evict_one_locked(self, spilled: list | None = None) -> bool:
        """Drop the least-recently-used refcount-zero cached page back to
        the free list (its index node dies with it). Lock held. When a
        spill hook is installed, the page's (page, digest, chain_pos) is
        appended to ``spilled`` for the post-lock hook call."""
        if not self._lru:
            return False
        page, _ = self._lru.popitem(last=False)
        key = self._page_key.pop(page)
        pos = self._page_pos.pop(page, None)
        if self._index.get(key) == page:
            del self._index[key]
            self._version += 1
        if spilled is not None and self.spill_hook is not None:
            spilled.append((page, key, pos))
        self._free.append(page)
        self.counters["evicted"] += 1
        return True

    def _fire_spill_hook(self, spilled: list) -> None:
        hook = self.spill_hook
        if hook is None or not spilled:
            return
        try:
            hook(spilled)
        except Exception:  # noqa: BLE001 - spill is best-effort by contract
            logger.warning(
                "kv-tier spill hook failed; %d pages evicted without "
                "spilling", len(spilled), exc_info=True)

    def alloc(self, n: int) -> list[int] | None:
        """n fresh pages at refcount 1, evicting cached pages LRU-first
        under pressure; None when free + evictable can't cover n."""
        spilled: list = []
        with self._lock:
            first = bool(self.first_pages and n > 0)
            if first and not self._free_first:
                return None     # no state row left, whatever else is free
            rest = n - first
            if len(self._free) + len(self._lru) < rest:
                return None  # can't be satisfied — don't evict for nothing
            while len(self._free) < rest:
                self._evict_one_locked(spilled)
            out = ([self._free_first.pop()] if first else []) \
                + [self._free.pop() for _ in range(rest)]
            for p in out:
                self._ref[p] = 1
        self._fire_spill_hook(spilled)
        return out

    def free(self, pages: list[int]) -> None:
        """Decref; a page reaching zero parks in the cached LRU if indexed
        (content stays valid for later matches), else rejoins the free
        list. Safe against double-free of already-dead pages."""
        spilled: list = []
        with self._lock:
            for p in pages:
                if p == 0:
                    continue
                cur = self._ref.get(p)
                if cur is None:
                    # already dead: a double free must not re-append the
                    # page (duplicate free-list entries would hand one
                    # page to two requests)
                    continue
                if cur > 1:
                    self._ref[p] = cur - 1
                    continue
                del self._ref[p]
                if p in self._page_key:
                    self._lru[p] = None
                    self._lru.move_to_end(p)
                    while self._cache_cap > 0 \
                            and len(self._lru) > self._cache_cap:
                        self._evict_one_locked(spilled)
                elif p <= self.first_pages:
                    self._free_first.append(p)
                else:
                    self._free.append(p)
        self._fire_spill_hook(spilled)

    def first_pages_free(self) -> int:
        """Reserved first pages (state rows) no sequence holds; 0 without
        a reserved range."""
        with self._lock:
            return len(self._free_first)

    def incref(self, pages: list[int]) -> None:
        with self._lock:
            for p in pages:
                if p != 0:
                    self._ref[p] = self._ref.get(p, 0) + 1

    def available(self) -> int:
        """Pages an alloc() could obtain: strictly-free + evictable
        cached. NOT the same as ``cache_stats()["free_pages"]`` — an
        evictable page still holds restorable KV content (and, with the
        kv tier on, spills on eviction); see cache_stats() for the
        three-way occupancy breakdown. Whole-replica logical pages
        (shard-count-independent; see the class docstring)."""
        with self._lock:
            return len(self._free) + len(self._lru) + len(self._free_first)

    def refcount(self, page: int) -> int:
        """Current refcount of one page (0 = free or parked in the cached
        LRU). Inspection only — used by tests that pin allocator
        invariants, e.g. that a speculative verify-k rollback never
        releases a reference on a shared prefix page (rollback is pure
        seq-len accounting in the engine; no allocator call sites)."""
        with self._lock:
            return self._ref.get(page, 0)

    # ---- prefix index --------------------------------------------------
    def match_prefix(self, tokens, page_size: int) -> list[int]:
        """Longest indexed chain of FULL token pages that prefixes
        ``tokens``, capped so at least one token is left to prefill (the
        suffix pass is what produces the first sampled token). Matched
        pages are increffed (cached ref-0 pages resurrect from the LRU) —
        the caller owns one reference and releases it via free()."""
        limit = (len(tokens) - 1) // page_size
        out: list[int] = []
        if limit <= 0:
            return out
        with self._lock:
            digest = b""
            for i in range(limit):
                digest = _chain_digest(
                    digest, tokens[i * page_size:(i + 1) * page_size])
                page = self._index.get(digest)
                if page is None:
                    self.counters["miss_pages"] += 1
                    break
                out.append(page)
            for p in out:
                if p in self._lru:
                    del self._lru[p]
                self._ref[p] = self._ref.get(p, 0) + 1
            self.counters["hit_pages"] += len(out)
        return out

    def insert_prefix(self, tokens, pages: list[int],
                      page_size: int) -> int:
        """Register a request's FULL prompt pages in the index (pages[i]
        holds tokens [i*page_size, (i+1)*page_size)). First writer wins: a
        chunk whose digest is already indexed keeps the existing page (the
        duplicate page simply stays un-indexed and frees normally).
        Returns how many new nodes were added."""
        added = 0
        with self._lock:
            digest = b""
            for i in range(min(len(tokens) // page_size, len(pages))):
                digest = _chain_digest(
                    digest, tokens[i * page_size:(i + 1) * page_size])
                if digest in self._index:
                    continue
                page = pages[i]
                if page == 0 or page in self._page_key:
                    continue
                self._index[digest] = page
                self._page_key[page] = digest
                # chain position: the spill path needs each evicted
                # page's token length ((pos+1) * page_size) to register
                # it in the cluster index
                self._page_pos[page] = i
                added += 1
            self.counters["inserted"] += added
            if added:
                self._version += 1
        return added

    def insert_digest_chain(self, digests_hex: list[str], pages: list[int],
                            positions: list[int]) -> int:
        """Register pages under pre-computed chain digests — the warm-start
        twin of ``insert_prefix`` for restores that carry digests but no
        token ids (the CP ``kv_tier:`` index stores digests only; the
        tokens that produced them live on whatever replica spilled them).
        A digest uniquely determines the full token prefix it closes
        (``_chain_digest`` chains over every token), so a digest-keyed
        node is exactly as trustworthy as a token-keyed one.

        ``positions[i]`` is the page's chain position (tokens/page_size-1
        from the tier entry) — needed so prefix_summary's low-position-
        wins cut and the re-spill path see the right depth. First writer
        wins, same as insert_prefix; pages the caller alloc'd stay at
        refcount 1 and park in the cached LRU on the caller's free().
        Returns how many new index nodes were added."""
        added = 0
        with self._lock:
            for d_hex, page, pos in zip(digests_hex, pages, positions):
                try:
                    digest = bytes.fromhex(d_hex)
                except (ValueError, TypeError):
                    continue
                if digest in self._index:
                    continue
                if page == 0 or page in self._page_key:
                    continue
                self._index[digest] = page
                self._page_key[page] = digest
                self._page_pos[page] = int(pos)
                added += 1
            self.counters["inserted"] += added
            if added:
                self._version += 1
        return added

    def index_version(self) -> int:
        with self._lock:
            return self._version

    def prefix_summary(self, max_pages: int = 0) -> tuple[int, list[str]]:
        """(version, resident page-chain digests as hex) — the bounded
        summary the affinity router consumes (ISSUE 10). When the index
        exceeds ``max_pages`` (0 = unbounded), LOW chain positions win the
        cut: a leading page is what lets the router match any prefix at
        all, while a deep page is only reachable through the pages before
        it. Every digest here names a page whose KV is resident (live or
        parked in the cached LRU) — both are served by match_prefix."""
        with self._lock:
            ver = self._version
            items = list(self._page_key.items())  # (page, digest)
            if max_pages and len(items) > max_pages:
                items.sort(key=lambda it: self._page_pos.get(it[0], 0))
                items = items[:max_pages]
            return ver, [d.hex() for _, d in items]

    def match_digest_chain(self, digests_hex: list[str]) -> int:
        """Leading run of ``digests_hex`` resident in the index (no
        incref, no LRU touch — pure inspection, used to size a tier
        prefetch so it skips pages already local)."""
        n = 0
        with self._lock:
            for d in digests_hex:
                try:
                    if bytes.fromhex(d) not in self._index:
                        break
                except ValueError:
                    break
                n += 1
        return n

    def cache_stats(self) -> dict:
        """Snapshot for engine stats / metrics export.

        All counts are WHOLE-REPLICA logical pages: a TP engine's page
        spans every shard, but it is one page here — free/evictable/live
        never multiply (or divide) by tp_degree. Dashboards wanting one
        chip's view scale the engine's byte gauges, not these counts.

        Three distinct occupancy numbers — dashboards must not conflate
        them (eviction is non-destructive once spilling is on):

        - ``free_pages``: strictly free — on the free list, content dead,
          allocation costs nothing.
        - ``evictable_pages``: refcount-zero but cached — content is
          live, restorable KV; allocating them evicts (and, with the kv
          tier on, spills) first.
        - live/referenced pages: ``num_pages - 1 - free - evictable``
          (page 0 is the reserved trash page) — pinned by active slots,
          never evictable.

        ``available()`` = free_pages + evictable_pages.
        """
        with self._lock:
            return {**self.counters,
                    "free_pages": len(self._free),
                    "cached_pages": len(self._page_key),
                    "evictable_pages": len(self._lru),
                    "shared_pages": sum(1 for c in self._ref.values()
                                        if c > 1)}


# ---------------------------------------------------------------------------
# compiled steps
# ---------------------------------------------------------------------------


# The paged steps below put a block's pieces together (models/block.py:
# ``serve_qkv``, ``serve_attn_out``, ``serve_conv``, ``serve_ffn``, ...).
# Each piece sits under a jax.named_scope so that a profiler trace says
# which layer an op belongs to (`norm`, `attn`, `conv`, `mlp`, `router`,
# `experts`, `embed`, `lm_head`, `sample`; `kv_write` / `state_write` = the
# page-pool and slot-state updates only; `ssm` a state-space mixer, inside
# it `ssm_in`, `ssm_conv`, `ssm_scan` | `ssm_update`, `state_write`,
# `ssm_out`); the scopes are compile-time metadata and change no
# executable.

def _pad_lanes(a, lanes: int):
    """``a`` with zeros behind its last axis up to ``lanes`` (a key row, or
    the query that meets it, on the whole vectors the pool stores:
    :func:`key_lanes`); ``a`` itself where it is that wide."""
    if a.shape[-1] == lanes:
        return a
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, lanes - a.shape[-1]),))


def _write_token_kv(k_pool, v_pool, layer, k_new, v_new, page_idx, offset):
    """Scatter new tokens' k/v into layer ``layer`` of the page pool.

    k_pool: the whole pool [L, Hkv, P, page, D], a loop carry; layer: int32
    scalar; page_idx/offset: [...] (decode [B], verify [B, T], prefill
    [T]); k_new: [..., Hkv, D]. ONE scatter on the 5-D carry, which XLA
    performs in place (the carry has no other live reader) — never
    ``pool[layer]`` updated and put back. The scatter's rows are single
    [D] vectors at ``[layer, h, page_idx, offset]``: with the head among
    the scattered indices the update window is the pool's minor-most
    dimension, so the compiler keeps the pool in the row-major layout the
    kernels read. (A ``[layer, :, page_idx, offset]`` scatter has [Hkv, D]
    windows, for which the TPU compiler moves Hkv next to D in the carry's
    layout and copies the whole pool back to row-major for every kernel
    call.) Distinct slots write distinct pages and distinct positions
    distinct offsets (or the shared trash page), so the scatter is
    conflict-free for real slots.
    """
    heads = jnp.arange(k_pool.shape[1])
    idx = (layer, heads, page_idx[..., None], offset[..., None])

    def rows(new, pool):
        """The pool's own rows (heads of 64: two heads a row, same bytes;
        key rows beside narrower value rows: zeros up to whole vectors,
        :func:`key_lanes`)."""
        if new.shape[-2] == pool.shape[1]:      # a head a row
            new = _pad_lanes(new, pool.shape[4])
        return new.reshape(new.shape[:-2] + (pool.shape[1], pool.shape[4])
                           ).astype(pool.dtype)

    return (k_pool.at[idx].set(rows(k_new, k_pool)),
            v_pool.at[idx].set(rows(v_new, v_pool)))


def _write_token_rows(pool, layer, new, page_idx, offset):
    """:func:`_write_token_kv` for a latent pool [L, 1, P, page, lanes]:
    ``new`` [..., latent_dim] at page_idx / offset [...], padded with zeros
    to the rows' lanes. The same ONE scatter of single rows, the (one)
    head among the scattered indices, and one array written."""
    new = jnp.pad(new, ((0, 0),) * (new.ndim - 1)
                  + ((0, pool.shape[4] - new.shape[-1]),))
    idx = (layer, jnp.arange(1), page_idx[..., None], offset[..., None])
    return pool.at[idx].set(new[..., None, :].astype(pool.dtype))


def _latent_mixer(x, kv, layer, ld, l, g):
    """A mixer that keeps one latent row a token, in its ABSORBED form
    (models/block.py ``serve_latent``): the rows of x [B, T, D] are written
    to layer ``l`` of the pool where the call's rows go, then q [B, T, H,
    latent_dim] runs against that layer (:func:`_attend`: all heads on the
    one row) and the weighted rows' first ``value_dim`` lanes [B, T, H,
    value_dim] go through the output projection. Returns (x + mixer, kv)."""
    blk = block_of(g.cfg)
    page_idx, offset = _keep(g.page_idx, g.lone), _keep(g.offset, g.lone)
    q, entry = blk.serve_latent(x, layer, g.cos, g.sin, g.cfg)
    with jax.named_scope("kv_latent"):
        pool = _write_token_rows(kv["k"], l, entry, page_idx, offset)
    with jax.named_scope("attn"):
        o = _attend(q, pool, None, l, g, g.value_dim)
    return x + blk.serve_latent_out(o, layer), {**kv, "k": pool}


def _latent_gather_attention(q, pool, layer, page_tables, valid, sm,
                             value_dim: int):
    """The gather backend's absorbed attention: the slots' rows as one
    sequence (padding lanes dropped), all heads on the one row, the values
    its first ``value_dim`` lanes. q [B, T, H, latent_dim]; valid
    broadcastable to [B, H, T, L]."""
    rows = _gather_seq(pool, layer, page_tables)[..., :q.shape[-1]]
    return _dense_attention(q, rows, rows[..., :value_dim], valid, sm)


def _gather_seq(pool, layer, page_tables, head_dim: int | None = None):
    """The gather backend's read: layer ``layer`` of the slots' pages as
    one contiguous sequence. pool: [L, Hkv, P, page, D]; page_tables:
    [..., MP]. ONE gather on the 5-D pool (no ``pool[layer]`` first).
    Returns [..., MP * page, Hkv, D] (``head_dim``: the model's, where the
    pool's rows hold two heads of 64)."""
    pages = pool[layer, :, page_tables]          # [..., MP, Hkv, page, D]
    hkv, page_size, d = pages.shape[-3:]
    head_dim = head_dim or d
    return jnp.swapaxes(pages, -3, -2).reshape(
        page_tables.shape[:-1] + (page_tables.shape[-1] * page_size,
                                  hkv * d // head_dim, head_dim))


def _over_layers(step, x, kv, params, cfg, *operands):
    """Run ``step(x, kv, layer_params, ld, l, *operands)`` -> (x, kv) over
    the block's layers, the cache a carry that is only ever updated in place.
    A block whose layers are all alike (``serve_layers`` None: stacked
    parameters, ``ld`` None, ``l`` traced) is scanned with the pool as a
    CARRY: the only ``xs`` are the layer parameters and the layer index,
    and there are no ``ys``. One whose layers differ in kind is walked in
    order (``ld`` its :class:`LayerDef`, ``l`` its row of the pool), each
    layer's weights read where they lie. ``params`` as a checkpoint lays
    them or in the block's served form (``serve_params``, idempotent: the
    engine's tree is served already and nothing is made here; a caller
    that hands a checkpoint's pays the conversion inside its program)."""
    blk = block_of(cfg)
    params = blk.serve_params(params, cfg)
    layers = blk.serve_layers(cfg)
    if layers is None:
        def body(carry, inputs):
            x, k_pool, v_pool = carry
            layer, l = inputs
            x, out = step(x, {"k": k_pool, "v": v_pool}, layer, None, l,
                          *operands)
            return (x, out["k"], out["v"]), None

        (x, k_pool, v_pool), _ = jax.lax.scan(
            body, (x, kv["k"], kv["v"]),
            (params["layers"],
             jnp.arange(kv["k"].shape[0], dtype=jnp.int32)))
        return x, {"k": k_pool, "v": v_pool}
    for ld, layer in zip(layers, params["layers"]):
        x, kv = step(x, kv, layer, ld, ld.page_layer, *operands)
    return x, kv


def _ffn(x, kv, layer, cfg, ld):
    """The layer's feed-forward; a routed one leaves its rows' choice of
    experts in the cache's ``routing`` record."""
    x, choice = block_of(cfg).serve_ffn(x, layer, cfg, ld)
    if choice is not None:
        kv = {**kv, "routing": kv["routing"].at[
            ld.routed_layer, :choice.shape[0]].set(choice)}
    return x, kv


def _block_len(cfg) -> int:
    """1, or the block length of a block that generates by diffusion over
    blocks (models/block.py)."""
    return block_of(cfg).cache_spec(cfg).block_length


def _visible(kpos, qpos, block_len: int):
    """Whether the key at ``kpos`` is visible to the query at ``qpos``
    (broadcast against each other): causal, or, with blocks of
    ``block_len`` positions from 0, every key up to the end of the
    query's own block."""
    if block_len == 1:
        return kpos <= qpos
    return kpos < (qpos // block_len + 1) * block_len


def _committed(true_len, block_len: int):
    """How many of a prompt's ``true_len`` tokens a prefill keeps K / V
    of: all of them, or its whole blocks (the rest start the pending
    block, which the block pass commits once it is clean)."""
    return true_len if block_len == 1 else true_len - true_len % block_len


def _conv_mixer(x, kv, layer, cfg, ld, rows, fresh=None, n_real=None):
    """A mixer with slot state over x [B, T, D]. ``rows`` [B]: each
    sequence's state row (its first page). ``fresh``: true where the call
    starts its sequence, which then reads zeros and not the row. ``n_real``
    [B]: how many of the T columns are the sequence's own (None = all):
    the row keeps the state as of the last real one."""
    i = ld.state_layer
    state = kv["state"][i]                                 # [P, (K-1)*D]
    prev = state[rows].reshape(x.shape[0], -1, x.shape[-1])
    if fresh is not None:
        prev = jnp.where(fresh, jnp.zeros_like(prev), prev)
    x, ext = block_of(cfg).serve_conv(x, layer, prev, cfg)
    with jax.named_scope("state_write"):
        new = _last_real(ext, prev.shape[1], n_real)
        state = state.at[rows].set(
            new.reshape(new.shape[0], -1).astype(state.dtype))
    return x, {**kv, "state": kv["state"][:i] + (state,)
               + kv["state"][i + 1:]}


def _last_real(ext, keep: int, n_real):
    """The ``keep`` columns of ext [B, keep + T, D] that end at each
    sequence's last REAL column of the call's T (``n_real`` [B]; None =
    all T are real): what a convolution's state keeps."""
    if n_real is None:
        return ext[:, ext.shape[1] - keep:]
    return jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
        e, n, keep, axis=0))(ext, n_real)


def _ssm_half(kv, layer, cfg, ld, z, xbc, dt, rows, fresh, n_real,
              kernel: bool):
    """The state-space half of a "hybrid" mixer over a call's columns (z,
    xbc, dt: ``serve_hybrid_in``'s) on the layer's two state arrays, the
    convolution's last K - 1 columns and the recurrent state a head (ops/
    ssm.py). ``rows`` / ``fresh`` / ``n_real`` as :func:`_conv_mixer`'s;
    columns past ``n_real`` leave both as they were. A call of one column a
    slot with nothing to mask (a decode step) updates the state IN PLACE
    (``kernel``: the Pallas kernel; else its ``jax.numpy`` form); any other
    scans its columns from the carried state. Returns (the branch as the
    residual takes it [B, T, D], kv)."""
    from ray_tpu.ops import ssm as ssm_ops
    blk = block_of(cfg)
    i = ld.state_layer
    taps, pool = kv["state"][i]
    nb, t = xbc.shape[:2]
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm_conv"):
            prev = taps[rows].reshape(nb, -1, xbc.shape[-1])
            if fresh is not None:
                prev = jnp.where(fresh, jnp.zeros_like(prev), prev)
            ext = jnp.concatenate([prev.astype(xbc.dtype), xbc], axis=1)
            xs, b, c = blk.serve_ssm_conv(ext, layer, cfg)
            dt, a = blk.serve_ssm_step(dt, layer)
        if t == 1 and fresh is None and n_real is None:
            with jax.named_scope("ssm_update"):
                update = ssm_ops.decode_update if kernel \
                    else ssm_ops.decode_update_xla
                y, pool = update(pool, rows, xs[:, 0], dt[:, 0], a, b[:, 0],
                                 c[:, 0])
                y = y[:, None]
        else:
            with jax.named_scope("ssm_scan"):
                if n_real is not None:
                    dt = jnp.where(jnp.arange(t)[None, :, None]
                                   < n_real[:, None, None], dt, 0.0)
                state = pool[rows]
                if fresh is not None:
                    state = jnp.where(fresh, jnp.zeros_like(state), state)
                y, state = jax.vmap(
                    lambda *o: ssm_ops.chunk_scan(*o, cfg.ssm_chunk),
                    in_axes=(0, 0, None, 0, 0, 0))(xs, dt, a, b, c, state)
                pool = pool.at[rows].set(state)
        with jax.named_scope("state_write"):
            taps = taps.at[rows].set(_last_real(
                ext, prev.shape[1], n_real).reshape(nb, -1).astype(
                    taps.dtype))
        with jax.named_scope("ssm_out"):
            out = blk.serve_ssm_out(y, xs, z, layer, cfg)
    return out, {**kv, "state": kv["state"][:i] + ((taps, pool),)
                 + kv["state"][i + 1:]}


def _hybrid_mixer(x, kv, layer, ld, l, g):
    """TWO mixers off one norm (``serve_hybrid_in``), both added to the
    residual: the "attn" write and read (:func:`_write_read`) and the
    state-space half (:func:`_ssm_half`)."""
    blk = block_of(g.cfg)
    (q, k, v), (z, xbc, dt) = blk.serve_hybrid_in(x, layer, g.cos, g.sin,
                                                 g.cfg)
    out, kv = _ssm_half(kv, layer, g.cfg, ld, z, xbc, dt, *g.state(),
                        kernel=g.attn_backend == "pallas")
    x, kv = _write_read(x, kv, q, k, v, ld, l, g,
                        lambda read: blk.serve_attn_out(read, layer, g.cfg))
    return x + out, kv


def _use_pallas_decode(cfg=None, page_size: int = 0, tp: int = 1) -> bool:
    """Kernel path gate: TPU backend + shapes the Pallas paged-attention
    kernels tile (``paged_attention.can_tile``). Tiny test models
    (head_dim 16-32) take the gather path on real TPUs; in interpreter
    mode (CPU) every shape runs."""
    if jax.default_backend() != "tpu":
        return False
    if cfg is None:
        return True
    from ray_tpu.ops.paged_attention import can_tile
    latent = getattr(cfg, "latent_dim", 0)
    if latent:        # the kernel reads the pool's padded rows
        return can_tile(latent_lanes(latent), page_size, cfg.dtype)
    value = getattr(cfg, "value_dim", 0)
    if value:         # value rows of their own width: the keys padded too
        return can_tile(key_lanes(cfg.head_dim), page_size, cfg.dtype,
                        value_dim=value)
    return can_tile(cfg.head_dim, page_size,
                    getattr(cfg, "dtype", jnp.bfloat16),
                    max(1, getattr(cfg, "n_kv_heads", 2) // tp))


def resolve_attention_backend(choice, cfg=None, page_size: int = 0,
                              tp: int = 1) -> str:
    """Resolve ``LLMConfig.attention_kernel`` to a concrete backend
    (``tp``: the chips the KV heads are split over).

    ``"auto"`` (default) picks ``"pallas"`` on TPU when the kernel tiling
    accepts the model's shapes and ``"gather"`` everywhere else (the
    interpreter-mode kernels are a correctness vehicle, not a CPU win).
    An explicit ``"pallas"`` is honored off-TPU (interpret mode — how
    tests gate the kernels on CPU) and raises on a TPU whose shapes the
    kernel can't tile: whoever named the kernel must not be served by
    another path under its name."""
    if choice in (None, "", "auto"):
        return "pallas" if _use_pallas_decode(cfg, page_size, tp) \
            else "gather"
    if choice not in ("gather", "pallas"):
        raise ValueError(
            f"attention_kernel must be 'auto', 'gather' or 'pallas', "
            f"got {choice!r}")
    if choice == "pallas" and jax.default_backend() == "tpu" \
            and not _use_pallas_decode(cfg, page_size, tp):
        raise ValueError(
            f"attention_kernel='pallas' cannot tile head_dim="
            f"{getattr(cfg, 'head_dim', '?')} / page_size={page_size} on "
            f"TPU (needs head_dim a multiple of 128, or 64 with an even "
            f"number of KV heads a chip, and whole sublane tiles per "
            f"page); use 'auto' or 'gather'")
    return choice


def tp_degree(mesh) -> int:
    """Live tensor-parallel degree of a serving mesh (1 = no TP: no mesh,
    or a mesh whose "tensor" axis is size 1 — both compile the exact
    single-chip program)."""
    if mesh is None or "tensor" not in mesh.axis_names:
        return 1
    return int(mesh.shape["tensor"])


def _dense_attention(q, k, v, mask, sm, sink=None):
    """Dense-softmax attention, the numerics every backend reproduces:
    float32 logits scaled by ``sm``, masked with -1e30, full-row float32
    softmax, probabilities cast back to q.dtype. q: [B, T, H, D]; k:
    [B, L, Hkv, D]; v: [B, L, Hkv, Dv] (Dv need not be D: a latent
    mixer's values are narrower than its keys in either form); mask:
    broadcastable to [B, H, T, L]; ``sink`` float32 [H]: a learned logit
    a head, one more column of the softmax that weighs no value (a row's
    weights then sum to less than 1). Returns [B, T, H, Dv]."""
    n_rep = q.shape[2] // k.shape[2]
    k_full = gqa_expand(k, n_rep)
    v_full = gqa_expand(v, n_rep)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_full).astype(
        jnp.float32) * sm
    logits = jnp.where(mask, logits, -1e30)
    if sink is not None:
        logits = jnp.concatenate([logits, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            logits.shape[:3] + (1,))], axis=-1)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if sink is not None:
        p = p[..., :-1]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_full)


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """What ONE call of a paged program fixes for every layer it runs:
    where the call's rows go and how it reads the cache back. A program
    builds it once a trace; the mixers read it and nothing of the program.

    ``page_idx`` / ``offset`` are in the form of the call's token grid: [B,
    T], or without the axis the call has ONE of. ``lone`` names that axis:
    1, one token a slot (decode: [B]; its per-head q, k, v and read have no
    token axis either); 0, one slot (a chunk: [T]; its tables and mask have
    no slot axis); None, a span. ``state``: () -> (rows, fresh, n_real),
    :func:`_conv_mixer`'s operands, made at each layer that keeps state
    (not once a trace: the recorded programs hold them a layer).

    The read, on the pallas backend: ``kernel``, the name of a wrapper of
    ops/paged_attention.py, its ``static`` keywords, and ``operands``, its
    replicated operands between the pools and the layer index ((tables,
    pos), (tables, seq_lens) or (table, start, kept)); ``writes``, whether
    that kernel also writes the call's rows (:func:`_geometry`). On the gather
    backend: ``operands[0]``, the page tables, and ``valid``, the mask in
    the grid's form with the keys last (decode has none: its read builds
    the causal mask from ``operands[1]``). ``cfg``, ``value_dim`` (of its
    cache spec), ``attn_backend`` and ``mesh`` are the engine's, the same
    for every call. A call that reads nothing back (a whole prefill) has no
    use for a geometry."""
    cfg: object = None
    cos: object = None
    sin: object = None
    page_idx: object = None
    offset: object = None
    lone: int | None = None
    state: object = None
    attn_backend: str = "gather"
    kernel: str = ""
    writes: bool = False
    static: dict = dataclasses.field(default_factory=dict)
    operands: tuple = ()
    valid: object = None
    value_dim: int = 0
    mesh: object = None


def _drop(a, lone):
    """a [B, T, ...] without the axis its call has one of."""
    return a if lone is None else a[(slice(None),) * lone + (0,)]


def _keep(a, lone):
    """:func:`_drop` undone: the axis back, of length 1."""
    return a if lone is None else a[(slice(None),) * lone + (None,)]


def _attend(q, k_pool, v_pool, l, g, value_lanes: int = 0, write=None,
            sink=None):
    """Layer ``l`` of the pool read back for q [B, T, H, D]: THE backend
    switch (the module docstring's first design choice, and on a TP mesh
    its last). ``v_pool`` None and ``value_lanes``: a latent pool, all
    heads on the one row, the values its first ``value_lanes`` lanes.
    Returns [B, T, H, Dv]; on per-head pools a call of one token a slot
    (``g.lone`` 1) returns [B, H, D], the form its read works in: on the
    gather backend three-axis einsums over the full [B, max_len] view (the
    reference tests/test_paged_kernels.py holds the kernel to; one slot's
    view, a chunk's, is small, a batch's is why the kernels exist).
    ``write`` (the pallas backend, a call whose ``g.writes`` is set):
    the call's rows in its grid's form and the pages they go to, which the
    kernel writes before it reads; returns (that, k_pool, v_pool).
    ``sink``: float32 [H], a learned logit a head in the softmax's
    denominator (:func:`_dense_attention`; the walking body's ``sink=``);
    the gather backend's read of one token takes it under a ring's mask
    alone (no block has a sink in a layer that has no window)."""
    head_dim = g.cfg.head_dim
    sm = head_dim ** -0.5
    one = g.lone == 1
    # a head's lanes in a row of each pool: the model's (a row may hold two
    # heads of 64), or the pools' own where value rows are narrower
    kd = vd = head_dim
    if v_pool is not None and v_pool.shape[4] != k_pool.shape[4]:
        kd, vd = k_pool.shape[4], v_pool.shape[4]
    extra = {} if sink is None else {"sink": sink}
    if g.attn_backend == "pallas":
        from ray_tpu.ops import paged_attention as paged_ops
        static = {**g.static, "value_lanes": value_lanes} if value_lanes \
            else g.static
        call = functools.partial(getattr(paged_ops, g.kernel), sm_scale=sm,
                                 **static)
        q = q[:, 0] if one else q
        if tp_degree(g.mesh) > 1:
            # check_vma=False: the kernel writes nothing replicated, and
            # rep inference can't see through pallas anyway
            in_specs, out_spec = paged_ops.tp_shard_specs(
                q_rank=q.ndim, n_replicated=len(g.operands) + 1)
            call = jax.shard_map(call, mesh=g.mesh, in_specs=in_specs,
                                 out_specs=out_spec, check_vma=False)
        if write is not None:
            return call(q, k_pool, v_pool, *g.operands, l, write=write,
                        **extra)
        out = call(q, k_pool, v_pool, *g.operands, l, **extra)
        return out[:, None] if one and value_lanes else out
    tables = g.operands[0]
    if one and g.valid is not None:     # a ring's mask, by its geometry
        return _dense_attention(
            q, _gather_seq(k_pool, l, tables, kd),
            _gather_seq(v_pool, l, tables, vd),
            g.valid[:, None, None], sm, sink)[:, 0]
    if one:
        def causal():       # [B, L], made where the recorded programs have it
            return jnp.arange(tables.shape[1] * k_pool.shape[3])[None, :] \
                <= g.operands[1][:, None]

        if value_lanes:
            return _latent_gather_attention(
                q, k_pool, l, tables, causal()[:, None, None], sm,
                value_lanes)
        q = q[:, 0]                                               # [B,H,D]
        # query heads a KV head (the pool's rows may hold two heads of 64)
        n_rep = q.shape[1] * kd // (k_pool.shape[1] * k_pool.shape[4])
        k_full = gqa_expand(_gather_seq(k_pool, l, tables, kd), n_rep)
        v_full = gqa_expand(_gather_seq(v_pool, l, tables, vd), n_rep)
        valid = causal()
        logits = jnp.einsum("bhd,bkhd->bhk", q, k_full).astype(
            jnp.float32) * sm
        logits = jnp.where(valid[:, None, :], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhk,bkhd->bhd", p, v_full)
    heads = (None, None) if g.lone == 0 else (slice(None), None)
    if value_lanes:
        return _latent_gather_attention(
            q, k_pool, l, _keep(tables, g.lone), g.valid[heads], sm,
            value_lanes)
    return _dense_attention(
        q, _keep(_gather_seq(k_pool, l, tables, kd), g.lone),
        _keep(_gather_seq(v_pool, l, tables, vd), g.lone),
        g.valid[heads], sm, sink)


def _positions(g):
    """The call's positions in the form of its token grid, from its
    operands (the second is where the call's first row lies)."""
    first = g.operands[1]
    if g.lone == 1:
        return first
    return jnp.asarray(first)[..., None] + jnp.arange(g.offset.shape[-1])


def _layer_geometry(g, ld, page_size: int):
    """(the names of the layer's pools, its geometry, the scope of its
    read). A block without window layers: the call's own, untouched. One
    with them: the page table the call was handed is the full layers'
    table and then the ring table (the module docstring); a full layer
    reads the first with a lower edge of 0, a window layer gets the ring
    table, the ring entries of the call's rows and, on the gather backend,
    the ring's mask: entry ``c // page`` offset ``c % page`` holds the
    LATEST position p <= hi with ``p % cap == c`` (hi: the last position
    the call wrote; write-then-read), seen by query i iff ``0 <= i - p <
    window``."""
    if not has_window_layers(g.cfg):
        return ("k", "v"), g, None
    if g.lone is None or _block_len(g.cfg) > 1:
        raise NotImplementedError(
            "a span of positions a slot (speculative verify, a block pass) "
            "for a block with window layers: a rejected position's row "
            "would have overwritten a live one of the ring")
    full_w = -(-g.cfg.max_seq_len // page_size)
    tables = g.operands[0]
    if tables.shape[-1] <= full_w:
        raise ValueError(
            f"the page table is {tables.shape[-1]} entries wide: a block "
            f"with window layers takes the full table "
            f"(ceil(cfg.max_seq_len / page) = {full_w}) followed by the "
            f"ring table")
    window = ld.window if ld is not None else 0
    if not window:
        valid = g.valid if g.valid is None \
            else g.valid[..., :full_w * page_size]
        return ("k", "v"), dataclasses.replace(
            g, operands=(tables[..., :full_w], *g.operands[1:]),
            static={**g.static, "window": 0}, valid=valid), "attn_full"
    ring = tables[..., full_w:]
    r = ring.shape[-1]
    pos = _positions(g)
    entry = (pos // page_size) % r
    page_idx = jnp.where(g.page_idx != 0, jnp.take_along_axis(
        ring, entry.reshape(ring.shape[:-1] + (-1,)), axis=-1).reshape(
            entry.shape), 0)
    valid = None
    if g.attn_backend != "pallas":
        cap = r * page_size
        # the last position written: a decode's own, a chunk's last real
        hi = pos if g.lone == 1 else jnp.minimum(
            g.operands[1] + g.offset.shape[0], g.operands[2]) - 1
        hi = jnp.asarray(hi)[..., None]
        kpos = hi - (hi - jnp.arange(cap)) % cap         # [B, cap] | [cap]
        valid = (kpos >= 0) if g.lone == 1 \
            else (kpos >= 0) & (kpos < g.operands[2])
        seen = (kpos <= pos[..., None]) & (kpos > pos[..., None] - window)
        valid = seen & valid
    return ("kw", "vw"), dataclasses.replace(
        g, operands=(ring, *g.operands[1:]), page_idx=page_idx,
        static={**g.static, "window": window}, valid=valid), "attn_window"


def _write_read(x, kv, q, k, v, ld, l, g, project, sink=None):
    """What the mixers that keep K and V a head share: the call's rows
    written to layer ``l`` of the layer's pool first, then read back with
    all that is cached (write-then-read: a call sees earlier calls AND
    itself; a window layer through its ring and no further back than its
    window), then ``project``, the output projection. The rows are
    scattered by :func:`_write_token_kv`, or ride in the kernel that reads
    them back (``g.writes``; the scope ``kv_write`` then holds no
    operation). Key rows that the pool stores on more lanes than the
    values' (:func:`key_lanes`) are padded with zeros here, q with them;
    ``sink``: the layer's learned logits in the softmax's denominator
    (:func:`_attend`). Returns (x + mixer, kv)."""
    (nk, nv), g, scope = _layer_geometry(g, ld, kv["k"].shape[3])
    k_pool, v_pool, write = kv[nk], kv[nv], None
    if v_pool.shape[4] != k_pool.shape[4]:
        q, k = _pad_lanes(q, k_pool.shape[4]), _pad_lanes(k, k_pool.shape[4])
    if g.writes:
        write = (_drop(k, g.lone), _drop(v, g.lone), g.page_idx)
    else:
        with jax.named_scope("kv_write"):
            k_pool, v_pool = _write_token_kv(
                k_pool, v_pool, l, _drop(k, g.lone), _drop(v, g.lone),
                g.page_idx, g.offset)
    with jax.named_scope("attn"):
        # a trace tells a window layer's read from a full one's
        with jax.named_scope(scope) if scope else contextlib.nullcontext():
            read = _attend(q, k_pool, v_pool, l, g, write=write, sink=sink)
        if write is not None:
            read, k_pool, v_pool = read
        out = project(read)
        x = x + (out if out.ndim == x.ndim else out[:, None])
    return x, {**kv, nk: k_pool, nv: v_pool}


def _attn_mixer(x, kv, layer, ld, l, g):
    """A mixer that keeps K and V a head (``serve_qkv``,
    :func:`_write_read`)."""
    blk = block_of(g.cfg)
    q, k, v = blk.serve_qkv(x, layer, g.cos, g.sin, g.cfg)
    return _write_read(x, kv, q, k, v, ld, l, g,
                       lambda read: blk.serve_attn_out(read, layer))


def _gated_mixer(x, kv, layer, ld, l, g):
    """The same under a gate (``serve_gated_qkv``): what was read is
    multiplied by the gate of its own row, lane by lane, before the output
    projection."""
    blk = block_of(g.cfg)
    q, k, v, gate = blk.serve_gated_qkv(x, layer, g.cos, g.sin, g.cfg, ld)
    return _write_read(
        x, kv, q, k, v, ld, l, g, lambda read: blk.serve_gated_out(
            read, gate if read.ndim == gate.ndim else gate[:, 0], layer,
            g.cfg))


def _sink_mixer(x, kv, layer, ld, l, g):
    """"attn" whose projections read the layer's definition (a rotation and
    a KV-head count a layer kind) and whose softmax may hold a learned sink
    (``serve_sink_qkv``: the sink float32 [H], or None for a layer that
    has none)."""
    blk = block_of(g.cfg)
    q, k, v, sink = blk.serve_sink_qkv(x, layer, g.cos, g.sin, g.cfg, ld)
    return _write_read(x, kv, q, k, v, ld, l, g,
                       lambda read: blk.serve_attn_out(read, layer), sink)


# A MIXER KIND (models/block.py ``LayerDef.mixer``) is one entry here:
# ``mixer(x, kv, layer, ld, l, g) -> (x + mixer, kv)``, the cache updated
# in place, the call's :class:`_Geometry` all it knows of the program that
# runs it.
_MIXERS = {
    "attn": _attn_mixer,
    "gated": _gated_mixer,
    "sink": _sink_mixer,
    "latent": _latent_mixer,
    "conv": lambda x, kv, layer, ld, l, g: _conv_mixer(
        x, kv, layer, g.cfg, ld, *g.state()),
    "hybrid": _hybrid_mixer,
}


def _mixer_kind(ld) -> str:
    """The layer's mixer kind; the layers of a scanned block attend."""
    return "attn" if ld is None else ld.mixer


def _layer(x, kv, layer, ld, l, g):
    """THE layer of every program that reads the cache back: its mixer,
    then its feed-forward. Returns (x, kv)."""
    x, kv = _MIXERS[_mixer_kind(ld)](x, kv, layer, ld, l, g)
    return _ffn(x, kv, layer, g.cfg, ld)


def _geometry(cfg, attn_backend, mesh, kind: str, **call) -> _Geometry:
    """The :class:`_Geometry` of a call of kind ``kind`` ("decode",
    "verify", "block" or "chunk": the names ops/paged_attention.py's tables
    of calls use), what is the same for every call filled in: the wrapper
    that reads the call's pages back, and whether its kernel also WRITES
    the call's rows (``writing_calls``: the calls whose body walks a slot's
    pages, on pools of K and V per head, on one chip), so that nothing is
    scattered before it."""
    spec = block_of(cfg).cache_spec(cfg)
    writes = False
    if attn_backend == "pallas":
        from ray_tpu.ops import paged_attention as paged_ops
        writes = kind in paged_ops.writing_calls(
            bool(spec.latent_dim), _block_len(cfg), has_window_layers(cfg),
            tp_degree(mesh))
    return _Geometry(cfg=cfg, attn_backend=attn_backend, mesh=mesh,
                     value_dim=spec.value_dim,
                     kernel=f"paged_{kind}_attention", writes=writes, **call)


def paged_decode_step(params, kv, page_tables, seq_lens, tokens,
                      cfg, page_size: int,
                      attn_backend: str = "gather", mesh=None):
    """One fused decode step for all slots.

    tokens: [B] current token ids; seq_lens: [B] tokens already in cache
    (the new token lands at position seq_lens[b]); page_tables:
    [B, max_pages] pool page ids (trash page 0 for unused entries).
    Returns (logits [B, vocab], new_kv, new_seq_lens). Inactive slots should
    carry seq_lens pointing at trash-page positions; their logits are junk
    and the engine ignores them.
    """
    blk = block_of(cfg)
    with jax.named_scope("embed"):
        x = blk.serve_embed(params, tokens[:, None], cfg)        # [B,1,D]
        cos, sin = blk.rope_freqs(cfg, seq_lens[:, None])      # position = len
    pos = seq_lens
    page_idx = jnp.take_along_axis(
        page_tables, (pos // page_size)[:, None], axis=1)[:, 0]  # [B]
    offset = pos % page_size

    g = _geometry(cfg, attn_backend, mesh, cos=cos, sin=sin,
                  page_idx=page_idx, offset=offset, lone=1,
                  state=lambda: (page_tables[:, 0], None, None),
                  kind="decode", operands=(page_tables, pos))
    x, kv = _over_layers(_layer, x, kv, params, cfg, g)
    x = blk.serve_final_norm(x, params, cfg)
    return blk.serve_lm_head(x[:, 0], params, cfg), kv, seq_lens + 1


@jax.named_scope("verify")
def paged_verify_step(params, kv, page_tables, seq_lens, tokens,
                      cfg, page_size: int,
                      attn_backend: str = "gather", mesh=None):
    """Speculative verify: T tokens per slot in ONE fused pass.

    tokens: [B, T] — slot b's current token followed by its T-1 drafted
    tokens; tokens[b, t] lands at position seq_lens[b] + t. All T
    positions are computed together (causal within the span, full
    attention over the paged cache), so the per-layer cache read happens
    ONCE per round instead of once per token — the decode pass is
    memory-bound, which is where verifying k drafts gets cheaper than k
    decode steps. logits[b, t] equals what paged_decode_step would
    produce after consuming tokens[b, :t+1] sequentially, which is what
    makes greedy speculative acceptance bit-identical to baseline decode.

    The pallas backend runs the fused MULTI-QUERY paged kernel — all k+1
    query positions per slot in one kernel launch, causal within the
    span, pages read through the page table (the TPU follow-up the
    single-query stock kernel deferred since PR 5). The gather backend
    materializes the [B, T, L] view — T times the decode fallback's
    traffic, bounded by small T (draft_len+1).
    Returns (logits [B, T, vocab], new_kv, seq_lens + T); what becomes of
    the K / V of a rejected draft: :func:`_span_step`.

    A block with slot state has no verify program: a rejected draft's
    columns would have to be taken out of the state again, which pages and
    ``seq_len`` do not record (the engine turns speculation off for it).
    """
    if has_slot_state(cfg):
        raise NotImplementedError(
            "speculative verify for a block with slot state: the state "
            "after a rejected draft cannot be rolled back")
    if has_window_layers(cfg):
        raise NotImplementedError(
            "speculative verify for a block with window layers: a "
            "rejected draft's row has overwritten a live one of the ring")
    x, kv = _span_step(params, kv, page_tables, seq_lens, tokens, cfg,
                       page_size, attn_backend, mesh, block_len=1)
    blk = block_of(cfg)
    logits = blk.serve_lm_head(blk.serve_final_norm(x, params, cfg), params,
                               cfg)                               # [B,T,V]
    return logits, kv, seq_lens + tokens.shape[1]


def paged_block_step(params, kv, page_tables, seq_lens, tokens,
                     cfg, page_size: int,
                     attn_backend: str = "gather", mesh=None, *,
                     commit: bool):
    """One pass over every slot's pending block (generation by diffusion
    over blocks; ``block_length`` B of the block's cache spec).

    tokens: [W, B], the block as it stands: known tokens, and the mask
    token where none is revealed yet; seq_lens: [W] the positions
    committed, a block edge. The B positions are computed together against
    the cached blocks and the block itself (every position sees all B).
    ``commit`` (static) false, a DENOISE pass: returns (logits [W, B,
    vocab], new_kv, seq_lens); the logits at a masked position are the
    distribution of the token that belongs there, and the K / V the pass
    wrote is junk under :func:`_span_step`'s rule. True, the COMMIT pass
    of a clean block: its K / V stay, no logits are computed, and it
    returns (None, new_kv, seq_lens + B).

    The engine's block program runs neither alone since it defers the
    commit (:func:`paged_block_pair_step`: the pass that keeps block g is
    the first denoise pass of block g + 1); the harness's check 1 drives
    these two, one known token a call, and the CPU tests hold the pass of
    two blocks to them position by position."""
    b = _block_len(cfg)
    if tokens.shape[1] != b:
        raise ValueError(f"a block pass takes blocks of {b} positions, got "
                         f"{tokens.shape[1]}")
    x, kv = _span_step(params, kv, page_tables, seq_lens, tokens, cfg,
                       page_size, attn_backend, mesh, block_len=b)
    if commit:
        return None, kv, seq_lens + b
    blk = block_of(cfg)
    return blk.serve_lm_head(blk.serve_final_norm(x, params, cfg), params,
                             cfg), kv, seq_lens


def paged_block_pair_step(params, kv, page_tables, seq_lens, tokens,
                          cfg, page_size: int,
                          attn_backend: str = "gather", mesh=None):
    """One pass over TWO consecutive blocks a slot: the deferred commit of
    generation by diffusion over blocks.

    tokens: [W, 2B] at ``seq_lens`` (a block edge): the slot's pending
    block, then the block after it, all masked. The block mask cuts the
    span into its two blocks (a position of the first sees the cache and
    the first, one of the second sees both), so the pass is what a commit
    pass of the first block FOLLOWED BY a denoise pass of the second would
    compute, in one read of the weights. Whether it is that is read from
    the data, per slot:

    * the first block is CLEAN (no position holds the mask token): the
      pass KEEPS it (its K / V stay: ``seq_lens + B``) and is the first
      denoise pass of the second, whose K / V are junk under
      :func:`_span_step`'s rule; the logits are the second block's.
    * it is not (what a prompt left: known tokens, then masks): a denoise
      pass of the first block, nothing kept; the second half is filler
      that no position of the first can see, its K / V junk and its
      hidden states dropped; the logits are the first block's.

    The final norm and the head run over the B positions a slot that the
    flag selects, so they cost what a pass of one block costs. Returns
    (logits [W, B, vocab], new_kv, new seq_lens, kept [W] bool)."""
    blk = block_of(cfg)
    spec = blk.cache_spec(cfg)
    b = spec.block_length
    if tokens.shape[1] != 2 * b:
        raise ValueError(f"a pass of two blocks takes {2 * b} positions, "
                         f"got {tokens.shape[1]}")
    x, kv = _span_step(params, kv, page_tables, seq_lens, tokens, cfg,
                       page_size, attn_backend, mesh, block_len=b)
    kept = jnp.all(tokens[:, :b] != spec.mask_token, axis=1)      # [W]
    x = jnp.where(kept[:, None, None], x[:, b:], x[:, :b])        # [W,B,D]
    return blk.serve_lm_head(blk.serve_final_norm(x, params, cfg), params,
                             cfg), kv, seq_lens + b * kept, kept


def _span_step(params, kv, page_tables, seq_lens, tokens, cfg, page_size,
               attn_backend, mesh, *, block_len: int):
    """What speculative verify and the block passes share: T positions a
    slot (tokens [B, T] at ``seq_lens[b] + t``) written to the slot's
    pages and attended in one pass, causal inside the span
    (``block_len`` 1) or every position seeing its whole block (T a whole
    number of blocks from a block edge). Returns (hidden states [B, T, D]
    before the final norm, new_kv).

    THE JUNK-WRITE RULE. All T positions' K / V are written whether or not
    the caller keeps them (a verify round's rejected drafts, a denoise
    pass's masked positions, the second block of a pass of two): what is
    not kept lies at or past the length the caller goes on with, in pages
    the slot owns alone (positions at or past the prompt's: never a
    shared prefix page) or in the trash page (table entries past the
    slot's pages are 0; under a block mask so is every position past the
    table's width, which the second block of a pass at the last block
    edge reaches), and is overwritten before anything can attend to it:
    by the next step that writes those positions (the decode that follows
    a rollback, the pass that keeps the block), every one of which writes
    before it reads."""
    blk = block_of(cfg)
    t = tokens.shape[1]
    max_len = page_tables.shape[1] * page_size

    pos = seq_lens[:, None] + jnp.arange(t)[None, :]              # [B,T]
    with jax.named_scope("embed"):
        x = blk.serve_embed(params, tokens, cfg)                  # [B,T,D]
        cos, sin = blk.rope_freqs(cfg, pos)
    page_idx = jnp.take_along_axis(page_tables, pos // page_size,
                                   axis=1)                        # [B,T]
    if block_len > 1:       # past the table's width: the trash page
        page_idx = jnp.where(pos < max_len, page_idx, 0)
    offset = pos % page_size
    kpos = jnp.arange(max_len)                                    # [L]
    # position t sees cache + the span's tokens 0..t (its own write), or
    # to the end of its block
    valid = _visible(kpos[None, None, :], pos[:, :, None], block_len)
    g = _geometry(
        cfg, attn_backend, mesh, cos=cos, sin=sin, page_idx=page_idx,
        offset=offset, operands=(page_tables, seq_lens), valid=valid,
        kind="verify" if block_len == 1 else "block",
        static={} if block_len == 1 else {"block_len": block_len})
    return _over_layers(_layer, x, kv, params, cfg, g)


@jax.named_scope("prefill")
def paged_prefill(params, kv, page_table, tokens, true_len,
                  cfg, page_size: int):
    """Prefill ONE slot's prompt into its pages.

    tokens: [1, T] (bucket-padded); page_table: [max_pages] for this slot;
    true_len: scalar actual prompt length. Returns (last-token logits
    [vocab], new_kv). Padding positions (>= true_len) write to the trash
    page via index clamping, so junk never lands in real pages. A block
    that generates by diffusion over blocks attends under the block mask
    and keeps the K / V of the prompt's whole blocks only
    (:func:`_committed`); its logits mean nothing (models/block.py).

    It reads nothing back, so it keeps a layer of its own and not
    :func:`_layer`'s: the prompt's own rows are attended (a latent mixer in
    its EXPANDED form) and written AFTER the feed-forward, for which a
    layer that is "the mixer, then the feed-forward" has no place.
    """
    blk = block_of(cfg)
    b = _block_len(cfg)
    t = tokens.shape[1]
    with jax.named_scope("embed"):
        x = blk.serve_embed(params, tokens, cfg)                  # [1,T,D]
        cos, sin = blk.rope_freqs(cfg, jnp.arange(t)[None, :])
    pos = jnp.arange(t)
    in_range = pos < _committed(true_len, b)
    page_idx = jnp.where(in_range, jnp.take(page_table, pos // page_size), 0)
    offset = pos % page_size
    # causal (or block) mask for the in-prompt attention (at 1 spelled as
    # it was, so that the blocks served before lower to the same program)
    causal = pos[:, None] >= pos[None, :] if b == 1 \
        else _visible(pos[None, :], pos[:, None], b)
    sm = cfg.head_dim ** -0.5

    def step(x, kv, layer, ld, l):
        kind = _mixer_kind(ld)
        if kind == "conv":
            x, kv = _conv_mixer(
                x, kv, layer, cfg, ld, page_table[:1], fresh=True,
                n_real=jnp.reshape(true_len, (1,)).astype(jnp.int32))
            return _ffn(x, kv, layer, cfg, ld)
        if kind == "latent":
            # the mixer's EXPANDED form: keys and values per head from the
            # prompt's own rows, nothing read back; the rows go to the pool
            q, k, v, entry = blk.serve_latent_expanded(x, layer, cos, sin,
                                                       cfg)
            with jax.named_scope("attn"):
                attn = _dense_attention(q, k, v, causal[None, None], sm)
                x = x + blk.serve_attn_out(attn, layer)
            x, kv = _ffn(x, kv, layer, cfg, ld)
            with jax.named_scope("kv_latent"):
                pool = _write_token_rows(kv["k"], l, entry, page_idx[None],
                                         offset[None])
            return x, {**kv, "k": pool}
        sink = ssm = None
        if kind == "hybrid":
            (q, k, v), parts = blk.serve_hybrid_in(x, layer, cos, sin, cfg)
            ssm, kv = _ssm_half(
                kv, layer, cfg, ld, *parts, page_table[:1], True,
                jnp.reshape(true_len, (1,)).astype(jnp.int32), kernel=False)
        elif kind == "gated":
            q, k, v, gate = blk.serve_gated_qkv(x, layer, cos, sin, cfg, ld)
        elif kind == "sink":
            q, k, v, sink = blk.serve_sink_qkv(x, layer, cos, sin, cfg, ld)
        else:
            q, k, v = blk.serve_qkv(x, layer, cos, sin, cfg)
        window = ld.window if ld is not None else 0
        (nk, nv), mask, rows = ("k", "v"), causal, page_idx
        if window:
            # a window layer: no further back than its window, and into
            # its ring (of a prompt longer than the ring, the rows the
            # ring still holds at its end: a row is written once)
            ring = page_table[-(-cfg.max_seq_len // page_size):]
            r = ring.shape[0]
            (nk, nv) = ("kw", "vw")
            mask = causal & (pos[:, None] - pos[None, :] < window)
            rows = jnp.where(
                in_range & (pos >= true_len - r * page_size),
                jnp.take(ring, (pos // page_size) % r), 0)
        with jax.named_scope("attn"):
            # dense causal attention within the prompt (prefill is
            # compute-bound and contiguous — no need to read back through
            # pages)
            attn = _dense_attention(q, k, v, mask[None, None], sm, sink)
            if kind == "hybrid":
                x = x + blk.serve_attn_out(attn, layer, cfg) + ssm
            else:
                x = x + (blk.serve_gated_out(attn, gate, layer, cfg)
                         if kind == "gated"
                         else blk.serve_attn_out(attn, layer))
        x, kv = _ffn(x, kv, layer, cfg, ld)
        with jax.named_scope("kv_write"):
            # scatter the prompt's k/v into this slot's pages
            k_pool, v_pool = _write_token_kv(
                kv[nk], kv[nv], l, k[0], v[0], rows, offset)
        return x, {**kv, nk: k_pool, nv: v_pool}

    x, kv = _over_layers(step, x, kv, params, cfg)
    x = blk.serve_final_norm(x, params, cfg)
    last = jnp.take_along_axis(
        x, jnp.maximum(true_len - 1, 0)[None, None, None], axis=1)[:, 0]
    return blk.serve_lm_head(last, params, cfg)[0], kv


@jax.named_scope("prefill_chunk")
def paged_chunk_walk(params, kv, page_table, tokens, start, true_len,
                     cfg, page_size: int,
                     attn_backend: str = "gather", mesh=None):
    """The layers of one CHUNK of a long prompt's prefill (chunked prefill:
    the engine interleaves prompt chunks with decode blocks so a long
    admission never stalls active generations for the whole prompt pass —
    the scheduling intent the reference delegates to vLLM's
    chunked-prefill/priority scheduler, vllm_engine.py:101).

    tokens: [1, C] the chunk (bucket-padded); start: scalar position of the
    chunk's first token; true_len: scalar total prompt length. The chunk's
    queries attend to every cached position < start (earlier chunks, read
    back through the page pool) plus causally within the chunk. Under the
    pallas backend the cached prefix is read page-by-page inside the fused
    chunk kernel instead of gathering the full paged view every chunk —
    the long-prompt suffix-prefill-after-tier-restore hot path. Returns
    (the chunk's hidden states [1, C, D] before the final norm, new_kv).
    Under a block mask as :func:`paged_prefill`: ``start`` is a block edge,
    and only the prompt's whole blocks are kept and attended to.
    """
    blk = block_of(cfg)
    b = _block_len(cfg)
    kept = _committed(true_len, b)
    c = tokens.shape[1]
    max_len = page_table.shape[0] * page_size

    pos = start + jnp.arange(c)                                   # [C]
    with jax.named_scope("embed"):
        x = blk.serve_embed(params, tokens, cfg)                  # [1,C,D]
        cos, sin = blk.rope_freqs(cfg, pos[None, :])
    in_range = pos < kept
    page_idx = jnp.where(in_range, jnp.take(page_table, pos // page_size), 0)
    offset = pos % page_size
    # keys: the whole paged view (earlier chunks + this one after write)
    kpos = jnp.arange(max_len)                                    # [L]
    valid = _visible(kpos[None, :], pos[:, None], b) \
        & (kpos[None, :] < kept)
    g = _geometry(
        cfg, attn_backend, mesh, cos=cos, sin=sin, page_idx=page_idx,
        offset=offset, lone=0, operands=(page_table, start, kept),
        valid=valid, kind="chunk", static={"block_len": b},
        state=lambda: (page_table[:1], start == 0, jnp.reshape(
            jnp.clip(true_len - start, 0, c), (1,)).astype(jnp.int32)))
    return _over_layers(_layer, x, kv, params, cfg, g)


@jax.named_scope("prefill_chunk")
def chunk_head(params, x, start, true_len, cfg):
    """The tail of a chunk: logits [vocab] of the prompt's last REAL token,
    picked out of ``x`` [1, C, D] (paged_chunk_walk's) by its position
    relative to the chunk's ``start``."""
    blk = block_of(cfg)
    x = blk.serve_final_norm(x, params, cfg)
    rel = jnp.clip(true_len - 1 - start, 0, x.shape[1] - 1)
    last = jnp.take_along_axis(x, rel[None, None, None], axis=1)[:, 0]
    return blk.serve_lm_head(last, params, cfg)[0]


def paged_prefill_chunk(params, kv, page_table, tokens, start, true_len,
                        cfg, page_size: int,
                        attn_backend: str = "gather", mesh=None):
    """One CHUNK of a long prompt's prefill: :func:`paged_chunk_walk`, then
    :func:`chunk_head` whatever the chunk (the engine's chunk program puts
    the head under a ``cond``: only a prompt's last chunk needs it).
    Returns (last-token logits [vocab] — meaningful only on the final
    chunk, new_kv)."""
    x, kv = paged_chunk_walk(params, kv, page_table, tokens, start,
                             true_len, cfg, page_size, attn_backend, mesh)
    return chunk_head(params, x, start, true_len, cfg), kv


@jax.named_scope("sample")
def sample_tokens(logits, rng, temperature, top_k: int = 0):
    """Greedy/temperature/top-k sampling on device. logits: [..., V];
    temperature: [...], a row's (0 → greedy). The draw (random bits over
    every row's logits, or its top k) runs under a ``cond`` on whether any
    row samples: with no such row the token is the argmax alone, over the
    logits as they lie (rows flattened only inside the draw: a block
    program's [W, B, V] lies B-major on the chip, and flattening it is a
    copy of the whole array); with one, the same expression on the same
    key as without the ``cond``."""
    greedy = jnp.argmax(logits, axis=-1)

    def drawn():
        rows = logits.reshape(-1, logits.shape[-1])
        temp = temperature.reshape(-1)
        if top_k and top_k > 0:
            vals, idx = jax.lax.top_k(rows, top_k)
            scaled = vals / jnp.maximum(temp[:, None], 1e-6)
            choice = jax.random.categorical(rng, scaled, axis=-1)
            sampled = jnp.take_along_axis(
                idx, choice[:, None], axis=-1)[:, 0]
        else:
            scaled = rows / jnp.maximum(temp[:, None], 1e-6)
            sampled = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(temperature > 0, sampled.reshape(greedy.shape),
                         greedy)

    return jax.lax.cond(jnp.any(temperature > 0), drawn, lambda: greedy)
