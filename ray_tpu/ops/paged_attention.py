"""Fused paged-attention kernel family (Pallas TPU) for the serving path.

The serving engine's gather attention materializes each slot's full
[max_len, Hkv, D] K/V view from the page pool every layer of every step.
These kernels read the pool pages DIRECTLY via the layer index and the
slot page table (scalar-prefetch block index maps, or copies the body
issues itself: the canonical TPU paged-attention patterns): the per-slot
view is assembled page by page in VMEM scratch, never in HBM. They take
the WHOLE pool [L, Hkv, P, page, D] and pick the layer themselves, so the
serving programs (serve/llm/kv_cache.py) can carry the pool through
their loops in place and never slice a layer out of it.

THREE bodies that share no logic. Which one a call runs is decided by
the pool's kind (what the cache spec states) and the call's kind (the
wrapper), from ONE table, :data:`WALKS_LIVE`; never by a model's name, an
option or a rule on shapes:

- pools of K and V per head, the GRID body (:func:`paged_attention`,
  ``_paged_attn_kernel``): decode (T=1), multi-query speculative verify
  (T=k+1 causal within the span), chunked prefill (B=1, extra ``true_len``
  bound) and the block pass of generation by diffusion over blocks (T =
  one block, every position of which sees the whole block) are the same
  computation with different query spans and masks, dispatched through
  thin wrappers. The mask is ``col < (pos // block_len + 1) * block_len``:
  key j is visible to query i iff j's block is not after i's; at
  ``block_len`` 1 (static) that is the causal ``col <= pos``. Grid (slot,
  KV head, table page): every page of a slot's table is read, one a grid
  step, and the scores run over the table's whole span, ``max_len``
  columns: its work follows ``max_seq_len``, never the context. It has
  ONE caller kind left (ISSUE 61): ``paged_chunk_attention`` for a block
  WITHOUT window layers, the one entry :data:`WALKS_LIVE` ``["heads"]``
  lacks. When that call moves too (PERF.md section 7 has both bodies
  timed at the cells' chunk shapes), this body, the ``walk`` flag and the
  table go (ROADMAP D4).

- pools of K and V per head, the WALKING body (``paged_attention(...,
  walk=True)``, ``_gqa_walk_kernel``; ISSUE 48): the same operands, mask
  and values, but its work follows each slot's LIVE length, the end of
  the block that holds the span's last position (bounded by ``limit`` and
  the table), which it reads off its scalar operands: one grid step a
  slot, the slot's live pages of K and of V copied from the pools where
  they lie (one strided copy a page and pool carries every KV head), the
  next slot's under this slot's products, and scores, sums and weighted
  values over the live chunks of columns only, every KV head of the call
  at once. Called by ``paged_block_attention``, by
  ``paged_decode_attention`` and ``paged_verify_attention`` on every such
  pool (ISSUE 61: a decode step read every page of a 2,048-token table
  for slots that held 400, at a twelfth of the bytes' time), and by the
  chunk call too of a block that has WINDOW LAYERS (``window=``, models/
  block.py), for it alone takes a LOWER EDGE: a window layer's walk starts
  at the page of the first query's oldest visible key, reads the slot's
  ring table (entry ``page % ring``) and masks ``col > pos - window``; a
  full layer of such a block walks from 0. The scratch holds a whole walk
  (a table, or a window and the span) of the KV heads of one grid step, so
  a call walks as many KV heads a step as :data:`_WALK_KV_BYTES` holds of,
  by the table's width it sees: every head at a table of 2,048 positions,
  two of eight at 16 k, one at 32 k; a walk whose ONE head passes the
  body's VMEM (some 96 k positions of 128 lanes in bf16) is refused when the
  program is traced, by a ValueError that says so. A call that walks also
  WRITES (``write=``, ISSUE 53;
  :func:`writing_calls`): it holds the pages the call's own rows of K and
  V go to, so the rows ride in, are laid over the scratch a tile of
  positions at a time and copied back to their pages, and the row scatter
  that every other call's program runs before its read
  (serve/llm/kv_cache.py ``_write_token_kv``: one row of a packed tile an
  index, 50 ns a row) is not run; the pools are then outputs aliased to
  the inputs. The pools of such a block need not be of one width nor
  its layers of one KV-head count (ISSUE 55): the key pool's rows may be
  wider than the value pool's (keys of 192 lanes stored on 256, values of
  128: the body's shapes follow each pool's own), a layer's KV heads are
  its pool's, and a layer's softmax may hold a learned SINK a query head
  (``sink=``: the running maximum starts at it, the row sum at ``exp(sink
  - maximum)``, and its column weighs no value). The body takes every
  call shape of the family (tests/test_paged_kernels.py drives them): a
  wrapper moves over by an entry in the table, as the block call did
  (ISSUE 48) and the decode and verify calls (ISSUE 61); the one left is
  the chunk call of a block without window layers.

- a LATENT pool (a cache spec with ``latent_dim``, models/block.py;
  :func:`paged_latent_attention`, ``_latent_attn_kernel``): one array of
  one row a token, [L, 1, P, page, lanes], whose first ``value_lanes``
  lanes are also the row's values, read by every query head. The
  wrappers take ``value_lanes`` (and no value pool) and run the latent
  body under their own kernel names. Its work follows each slot's LIVE
  length ``min(limit, base + T)``, which it reads off its scalar
  operands: one grid step a slot, the slot's live pages copied from the
  pool where it lies into one scratch (key and value at once), the next
  slot's under this slot's products, and scores, sums and weighted
  values over the live chunks of columns only. A page past the live
  ones is never read, a slot with nothing live writes zeros. The rows
  are padded to whole 128-lane vectors with zeros, which meet whatever
  the query holds there and add nothing.

Identity contract: greedy TOKENS under the pallas backend must equal the
gather backend exactly (hard-asserted in tests and the serve bench), so
every body computes the SAME dense-softmax numerics as the gather path —
fp32 logits scaled by ``sm_scale``, masked with -1e30, fp32 softmax over
the whole row (maximum, exponentials, their sum, one division),
probabilities cast back to q.dtype, same contractions — instead of a
flash-style streaming softmax (whose rescaling visibly changes float
results). The two walking bodies walk the row in chunks of columns and three
times (scores and maximum; exponentials and sum; probabilities times
values, summed in fp32), over the LIVE columns only: a column it leaves
out is a masked one, whose exponential is an exact zero, so the row's
maximum and probabilities are the full row's. Raw attention outputs
agree with gather to the last ULPs (the fused [R, L] dot, the chunks'
partial sums and the batched einsum may order partial sums differently);
the win is memory traffic, not math: pages stream HBM->VMEM once per
(slot, kv-head) with no materialized gather intermediate, and under a
walking body the live pages only.

Off-TPU the kernels run in interpreter mode (pl.pallas_call
(interpret=True)), which is how tier-1 gates them on CPU — same story as
ops/attention.py.

Tensor parallelism (ISSUE 20): a pallas_call is opaque to GSPMD, so on a
TP mesh the serving engine runs these kernels under ``shard_map`` with
the canonical per-KV-head partitioning from :func:`tp_shard_specs` —
pool axis 1 (Hkv of [L, Hkv, P, page, D]) and q's H axis split by the
"tensor" mesh axis. The kv-major GQA head order is what makes that
split clean: each shard's kernel invocation is exactly a single-chip call
over Hkv/tp kv heads with their n_rep q heads, no kernel-internal changes
and no in-kernel collectives.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30
# one [rows, L] float32 score tile the kernel may hold; the mask, exp and
# probability temporaries are each about this size again
_SCORE_TILE_BYTES = 2 * 1024 * 1024
_MAX_ROW_TILE = 256
# query rows of one grid step of the chunk call on a latent pool
_MAX_SPAN_ROWS = 2048
# pages of one chunk of key columns of the latent body: its score tile's
# width, and the pages one semaphore counts
_LATENT_CHUNK_PAGES = 4
# the same of the walking body for pools of K and V per head
_GQA_CHUNK_PAGES = 4
# THE table of which call kinds run a body that walks a slot's live pages,
# by the pool's kind: the wrappers route by it and the engine reports its
# own programs' share of it (``attn_walks_live``). Every other call, which
# is the chunk call on "heads" and no other, runs the grid body, whose work
# follows the table's width.
# ("windowed": pools of K and V per head of a block that has window layers,
# whose reads need the lower edge, which only a walking body takes.)
WALKS_LIVE = {"latent": ("decode", "verify", "chunk"),
              "heads": ("decode", "verify", "block"),
              "windowed": ("decode", "chunk")}
# what the walking body's scratch may hold: both pools' pages of one step's
# KV heads (both halves), and one row tile's scores of a block with window
# layers (every other call's: _SCORE_TILE_BYTES). A call whose KV heads do
# not fit, by its table's width or its window's, walks them a GROUP a grid
# step; one whose single head passes the limit is refused.
_WALK_KV_BYTES = 36 * 1024 * 1024
_WALK_SCORE_BYTES = 16 * 1024 * 1024
_WALK_VMEM_LIMIT = 100 * 1024 * 1024


def walking_calls(latent: bool, block_len: int = 1,
                  windowed: bool = False) -> list[str]:
    """The call kinds of ONE engine's programs whose body walks live pages
    (the engine's ``attn_walks_live``): what its programs call, by the
    cache spec's block length (a block program and the chunk program, or
    decode, verify and chunk), that :data:`WALKS_LIVE` names for its
    pool's kind."""
    called = ("decode", "verify", "chunk") if block_len == 1 \
        else ("block", "chunk")
    kind = "latent" if latent else "windowed" if windowed else "heads"
    return [call for call in called if call in WALKS_LIVE[kind]]


def writing_calls(latent: bool, block_len: int = 1, windowed: bool = False,
                  tp: int = 1) -> list[str]:
    """The call kinds of ONE engine's programs whose kernel also WRITES the
    call's own rows of K and V (the engine's ``attn_writes_in_kernel``;
    serve/llm/kv_cache.py ``_geometry`` asks it whether to scatter): a
    body that walks a slot's pages holds the pages the rows go to, so on
    pools of K and V per head every call that walks writes (``write=``).
    Every other call's rows are scattered before it reads: the grid body's,
    a latent pool's, and any call on a tensor-parallel mesh, whose
    ``shard_map`` returns no pool."""
    if latent or tp > 1:
        return []
    return walking_calls(False, block_len, windowed)


def interpret_default() -> bool:
    """Whether these kernels run interpreted in this process: true
    everywhere but on a TPU backend (the CPU tests' only way to run
    them). The engine reports it as ``attn_interpret``."""
    return jax.default_backend() != "tpu"


def sublane_tile(dtype) -> int:
    """Rows of one native TPU tile for ``dtype``: (8, 128) at 4 bytes,
    (16, 128) at 2, (32, 128) at 1."""
    return 32 // jnp.dtype(dtype).itemsize


def _write_tile(dtype, page_size: int) -> int:
    """Rows of one write of the walking body: a native tile of the pool's
    type (a page the TPU tiles is whole tiles, :func:`can_tile`; the
    interpreter takes any page, and then a divisor of it)."""
    return math.gcd(sublane_tile(dtype), page_size)


def can_tile(head_dim: int, page_size: int, dtype,
             n_kv_heads: int = 2, value_dim: int = 0) -> bool:
    """Shapes Mosaic compiles these kernels for, of the rows THE POOL
    STORES: ``head_dim`` lanes fill whole 128-lane vectors (a key row of
    192 lanes is stored on 256, the padding zeros: serve/llm/kv_cache.py
    ``key_lanes``, and is asked about at 256), or are the half vector of
    64 lanes with an even number of KV heads (``n_kv_heads``: a chip's
    share of them), which the pool then stores two to a 128-lane row, so
    that a page block's last dimension is whole vectors (an odd count
    cannot be packed, and a 64-lane pool is re-laid out by the compiler
    around every token write); a value pool of its own width
    (``value_dim`` above 0) whole vectors too; and a page is whole sublane
    tiles, so the page copy into scratch and both matmuls stay
    tile-aligned."""
    return (head_dim % 128 == 0
            or (head_dim == 64 and n_kv_heads % 2 == 0
                and not value_dim)) \
        and value_dim % 128 == 0 \
        and page_size % sublane_tile(dtype) == 0


def tp_shard_specs(q_rank: int, n_replicated: int, axis: str = "tensor"):
    """Canonical ``shard_map`` partition specs for this kernel family on a
    tensor-parallel mesh.

    Operand order is the family's wrapper signature: ``(q, k_pages,
    v_pages, <n_replicated trailing operands>)`` — page tables, scalar
    position/length operands and the layer index are replicated. q of
    rank ``q_rank`` is split on its H axis (second-to-last); the
    layer-indexed pools [L, Hkv, P, page, D] on axis 1 (Hkv). Because
    ``paged_attention`` derives ``hkv``/``n_rep`` from operand shapes and
    splits heads kv-major, each shard's launch is a self-consistent
    single-chip call over its Hkv/tp kv-head groups.

    Returns ``(in_specs, out_spec)``; the output follows q's split.
    """
    q_spec = P(*([None] * (q_rank - 2) + [axis, None]))
    in_specs = (q_spec, P(None, axis), P(None, axis)) \
        + (P(),) * n_replicated
    return in_specs, q_spec


def _row_tiling(r: int, max_len: int, dtype,
                tile_bytes: int = _SCORE_TILE_BYTES) -> tuple[int, int]:
    """(padded row count, row tile). Rows pad to whole sublane tiles —
    Mosaic rejects a 2-row bf16 matmul operand — and are processed in
    tiles small enough that one [tile, L] float32 score block plus its
    softmax temporaries fits scoped VMEM at any sequence limit."""
    sub = sublane_tile(dtype)
    cap = min(_MAX_ROW_TILE, tile_bytes // (4 * max_len))
    cap = max(sub, cap // sub * sub)
    r_sub = -(-r // sub) * sub
    if r_sub <= cap:
        return r_sub, r_sub
    return -(-r // cap) * cap, cap


def _paged_attn_kernel(pt_ref, base_ref, limit_ref, layer_ref,  # prefetch
                       q_ref, k_ref, v_ref, o_ref, k_scr, v_scr, *,
                       sm_scale: float, page_size: int, num_pages: int,
                       t_span: int, row_tile: int, block_len: int = 1):
    """Grid (B, Hkv, num_pages); one (slot, kv-head) pair accumulates its
    pages into VMEM scratch and computes dense attention on the last page.

    q_ref: [1, 1, R, D] where R >= n_rep * t_span, row r = rep * t_span + t
    (GQA heads grouped per kv head, query positions innermost — matches
    ``gqa_expand``'s kv-major head order); rows past n_rep * t_span are
    zero padding the wrapper slices off. k_ref/v_ref: this grid step's
    pool page [page, D] (layer, kv-head and page block dimensions squeezed),
    selected by the block index map through the scalar-prefetched layer
    index and page table — the read IS the gather. ``layer_ref`` is read
    by the index maps only.
    """
    b = pl.program_id(0)
    p = pl.program_id(2)

    off = pl.multiple_of(p * page_size, page_size)
    k_scr[pl.ds(off, page_size)] = k_ref[...]
    v_scr[pl.ds(off, page_size)] = v_ref[...]

    @pl.when(p == num_pages - 1)
    def _compute():
        base = base_ref[b]
        limit = limit_ref[b]

        def rows(i, carry):
            r0 = pl.multiple_of(i * row_tile, row_tile)
            q = q_ref[0, 0, pl.ds(r0, row_tile), :]           # [TR, D]
            # fp32 MXU accumulation rounded to q.dtype, then the fp32
            # scale — what the gather path's einsum(...).astype(f32) * sm
            # computes (Mosaic only accepts a 32-bit accumulator)
            s = jax.lax.dot_general(
                q, k_scr[:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(q.dtype)
            s = s.astype(jnp.float32) * sm_scale              # [TR, L]
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            pos = base + row % t_span
            if block_len == 1:
                valid = (col <= pos) & (col < limit)
            else:   # a position sees its whole block and every earlier one
                valid = (col < (pos // block_len + 1) * block_len) \
                    & (col < limit)
            s = jnp.where(valid, s, _NEG_INF)
            w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            o_ref[0, 0, pl.ds(r0, row_tile), :] = jax.lax.dot_general(
                w, v_scr[:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, q_ref.shape[2] // row_tile, rows, None)


def paged_attention(q, k_pages, v_pages, page_tables, base, limit=None,
                    layer=None, *, sm_scale: float | None = None,
                    interpret: bool | None = None,
                    name: str = "paged_attention", block_len: int = 1,
                    walk: bool = False, window: int | None = None,
                    write=None, sink=None):
    """Fused paged attention over the whole query span.

    q: [B, T, H, D] — query position of q[:, t] is ``base + t`` (causal
    within the span, full attention over the paged cache below it; with a
    static ``block_len`` above 1, positions are cut into blocks of that
    length from 0 and a query sees every key up to the end of its own
    block).
    k_pages/v_pages: the whole pool [L, Hkv, P, page, D], of which the
    kernel reads layer ``layer`` (int32 scalar, traced or not): the index
    rides in as a scalar-prefetch operand and the K/V block index maps
    pick ``(layer, head, page)``, so the caller never slices ``pool[l]``
    out (a Pallas operand is a materialised buffer: that slice would be a
    copy of the layer's pool). A pool with no layer axis [Hkv, P, page, D]
    (``layer`` None) is read as a one-layer pool. page_tables:
    [B, max_pages]. base: [B] int32 first-query positions. limit: [B]
    int32 exclusive key bound (None = the whole table span) — chunked
    prefill passes ``true_len`` so padded tail pages stay masked. name:
    the kernel's name in the compiled program and in a profiler trace
    (each of the callers below passes its own). walk (static): the body
    that walks each slot's live pages (:func:`_gqa_walk_kernel`) instead of
    the grid over the table's pages; the same values to the last ULPs.
    window (static; implies ``walk``): the LOWER EDGE, of a block that has
    window layers. None: no such block. 0: one of its full layers, every
    key from 0 on. W above 0: a window layer, query i sees key j iff ``0 <=
    i - j < W``, and ``page_tables`` is the slots' RING tables [B, ring]:
    position p lies in entry ``(p // page) % ring`` (models/block.py), the
    walk starts at the page that holds the first query's oldest visible
    key, and a page below it is never read.
    write (the walking body only; :func:`writing_calls`): (k_new, v_new
    [B, T, Hkv, D], page_idx [B, T]), the span's own K and V and the page
    each row goes to (its offset is its position's). The call then WRITES
    the rows to layer ``layer`` of the pools where a scatter before it
    would have (:func:`_gqa_walk_kernel`; a row whose page is not its
    position's in the table, which the caller's rule sent to the trash
    page, is dropped) and reads them back with the rest.
    A VALUE POOL NARROWER THAN THE KEY POOL (the walking body only; keys of
    192 lanes stored on 256 beside values of 128, models/block.py
    ``CacheSpec.value_dim``): q and ``k_new`` come with the key pool's
    lanes (the padding zeros, which meet zeros), ``v_new`` with the value
    pool's, and the result has the value pool's lanes.
    sink (the walking body only): float32 [H], a learned logit a query head
    that joins the softmax's denominator and weighs no value
    (:func:`_gqa_walk_kernel`); None: the plain softmax.
    Returns [B, T, H, Dv] in q.dtype; with ``write``, (that, k_pages,
    v_pages), the pools updated in place.
    """
    b, t, h, d = q.shape
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    if write is not None:       # the pool's own rows (heads of 64: two a row)
        write = tuple(new.reshape(b, t, pool.shape[1], pool.shape[4])
                      for new, pool in zip(write, (k_pages, v_pages))) \
            + (write[2],)
    if k_pages.shape[4] != d:
        return _packed_heads(
            q, k_pages, v_pages, page_tables, base, limit, layer,
            sm_scale=d ** -0.5 if sm_scale is None else sm_scale,
            interpret=interpret, name=name, block_len=block_len, walk=walk,
            window=window, write=write, sink=sink)
    if (write is not None or sink is not None
            or v_pages.shape[4] != k_pages.shape[4]) \
            and not (walk or window is not None):
        raise ValueError("only the walking body writes the call's rows, "
                         "takes a sink or reads pools of two widths")
    if walk or window is not None:
        if limit is None:   # the table's span; a ring's positions pass it
            limit = jnp.full((b,), 2 ** 30 if window else
                             page_tables.shape[1] * k_pages.shape[3],
                             jnp.int32)
        return _gqa_walk_call(
            q, k_pages, v_pages, page_tables.astype(jnp.int32),
            base.astype(jnp.int32), limit.astype(jnp.int32),
            jnp.reshape(layer, (1,)).astype(jnp.int32), write, sink,
            sm_scale=float(d ** -0.5 if sm_scale is None else sm_scale),
            interpret=interpret_default() if interpret is None
            else interpret, name=name, block_len=block_len, window=window)
    hkv = k_pages.shape[1]
    n_rep = h // hkv
    page_size = k_pages.shape[3]
    max_pages = page_tables.shape[1]
    max_len = max_pages * page_size
    if sm_scale is None:
        sm_scale = d ** -0.5
    if interpret is None:
        interpret = interpret_default()
    if limit is None:
        limit = jnp.full((b,), max_len, jnp.int32)
    r = n_rep * t
    r_pad, row_tile = _row_tiling(r, max_len, q.dtype)
    # [B, T, H, D] -> [B, Hkv, n_rep*T, D]: kv-major head split (matches
    # gqa_expand), query positions innermost so the kernel recovers t as
    # row % t_span
    qg = q.reshape(b, t, hkv, n_rep, d).transpose(0, 2, 3, 1, 4).reshape(
        b, hkv, r, d)
    if r_pad != r:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))

    isz = jnp.dtype(q.dtype).itemsize
    # scratch + double-buffered q/o/page blocks + the row tile's score,
    # mask, exp and probability temporaries, with headroom: the default
    # scoped limit (16 MiB on v5e) is below the chunked-prefill call's need
    vmem = (2 * max_len * d * jnp.dtype(k_pages.dtype).itemsize
            + 4 * r_pad * d * isz + 4 * page_size * d * isz
            + 8 * row_tile * max_len * 4)
    kernel = functools.partial(
        _paged_attn_kernel, sm_scale=sm_scale, page_size=page_size,
        num_pages=max_pages, t_span=t, row_tile=row_tile,
        block_len=block_len)
    # the paged read: block index (lyr[0], hi, pt[bi, pi]) picks the
    # layer's pool page straight off the scalar-prefetched layer index
    # and table
    pools = (k_pages, v_pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, hkv, max_pages),
            in_specs=[
                pl.BlockSpec((1, 1, r_pad, d),
                             lambda bi, hi, pi, pt, bs, lim, lyr:
                             (bi, hi, 0, 0)),
            ] + [
                pl.BlockSpec((None, None, None, page_size, d),
                             lambda bi, hi, pi, pt, bs, lim, lyr:
                             (lyr[0], hi, pt[bi, pi], 0, 0))
                for _ in pools],
            out_specs=pl.BlockSpec(
                (1, 1, r_pad, d),
                lambda bi, hi, pi, pt, bs, lim, lyr: (bi, hi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((max_len, d), pool.dtype)
                            for pool in pools]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 1024 * 1024, 2 * vmem)),
        interpret=interpret,
        name=name,
    )(page_tables.astype(jnp.int32), base.astype(jnp.int32),
      limit.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qg, *pools)
    return out[:, :, :r].reshape(b, hkv, n_rep, t, d).transpose(
        0, 3, 1, 2, 4).reshape(b, t, h, d)


def _latent_attn_kernel(pt_ref, base_ref, limit_ref, layer_ref,  # prefetch
                        q_ref, pool_ref, o_ref, rows_scr, s_scr, acc_scr,
                        sems, *, sm_scale: float, page_size: int,
                        max_pages: int, chunk_pages: int, t_span: int,
                        row_tile: int, value_lanes: int):
    """Grid (B,): one grid step a slot, whose work follows the slot's LIVE
    length ``min(limit, base + t_span)`` (a decode call: ``pos + 1``).

    pool_ref is the whole latent pool [L, 1, P, page, lanes], left where
    it lies (no block of it is pipelined): the body walks the live entries
    of a slot's table row and copies each page into its place in the
    slot's half of rows_scr [2, chunks * chunk_pages * page, lanes], every
    copy in flight at once, one semaphore a CHUNK of ``chunk_pages``
    pages. Step b starts the copies of slot b + 1 into the other half
    before it computes (step 0 its own too), so a slot's pages arrive
    under the products of the slot before. A page past the live ones is
    never read. The rows are key and value at once (the values their
    first ``value_lanes`` lanes).

    q_ref [R, lanes], row r = head * t_span + t. A tile of ``row_tile``
    rows meets the live chunks only, in three walks: scores (the first
    tile's walk waits for each chunk's pages as it reaches them), masked
    and kept in s_scr with the running row maximum; exponentials and
    their row sums; probabilities (cast to q's type) times the chunk's
    values, summed in acc_scr. These are the dense softmax's float32
    values: a column the walk leaves out is a masked one, whose
    exponential is an exact zero. The dead pages of the last live chunk
    are zeroed first (a weight of zero times whatever VMEM held could be
    a NaN); a slot with nothing live walks nothing and writes zeros.
    """
    b = pl.program_id(0)
    half = b % 2
    base = base_ref[b]
    limit = limit_ref[b]
    chunk = chunk_pages * page_size

    def pages_of(slot):
        live = jnp.clip(jnp.minimum(limit_ref[slot],
                                    base_ref[slot] + t_span),
                        0, max_pages * page_size)
        return (live + page_size - 1) // page_size

    def page_rows(j):
        return pl.ds(pl.multiple_of(j * page_size, page_size), page_size)

    def page_copy(slot, j):
        return pltpu.make_async_copy(
            pool_ref.at[layer_ref[0], 0, pt_ref[slot, j]],
            rows_scr.at[slot % 2, page_rows(j)],
            sems.at[slot % 2, j // chunk_pages])

    def fetch(slot):
        def start(j, carry):
            page_copy(slot, j).start()
            return carry
        jax.lax.fori_loop(0, pages_of(slot), start, None)

    @pl.when(b == 0)
    def _own():
        fetch(b)

    @pl.when(b + 1 < pl.num_programs(0))
    def _ahead():
        fetch(b + 1)

    live_pages = pages_of(b)
    live_chunks = (live_pages + chunk_pages - 1) // chunk_pages

    def zero(j, carry):
        rows_scr[half, page_rows(j), :] = jnp.zeros(
            (page_size, rows_scr.shape[2]), rows_scr.dtype)
        return carry

    jax.lax.fori_loop(live_pages, live_chunks * chunk_pages, zero, None)

    def rows(i, carry):
        r0 = pl.multiple_of(i * row_tile, row_tile)
        q = q_ref[pl.ds(r0, row_tile), :]                      # [TR, lanes]
        row = r0 + jax.lax.broadcasted_iota(
            jnp.int32, (row_tile, chunk), 0)
        pos = base + row % t_span

        def chunk_rows(c):
            return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

        def scores(c, m):
            @pl.when(i == 0)
            def _arrive():
                for p in range(chunk_pages):
                    j = c * chunk_pages + p

                    @pl.when(j < live_pages)
                    def _wait():
                        page_copy(b, j).wait()

            # fp32 MXU accumulation rounded to q.dtype, then the fp32
            # scale: the gather path's einsum(...).astype(f32) * sm
            s = jax.lax.dot_general(
                q, rows_scr[half, chunk_rows(c), :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(q.dtype)
            s = s.astype(jnp.float32) * sm_scale               # [TR, chunk]
            col = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where((col <= pos) & (col < limit), s, _NEG_INF)
            s_scr[c] = s
            return jnp.maximum(m, s.max(axis=-1, keepdims=True))

        m = jax.lax.fori_loop(
            0, live_chunks, scores,
            jnp.full((row_tile, 1), _NEG_INF, jnp.float32))

        def sums(c, total):
            e = jnp.exp(s_scr[c] - m)
            s_scr[c] = e
            return total + e.sum(axis=-1, keepdims=True)

        total = jax.lax.fori_loop(
            0, live_chunks, sums, jnp.zeros((row_tile, 1), jnp.float32))

        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

        def weigh(c, carry):
            w = (s_scr[c] / total).astype(q.dtype)
            acc_scr[...] += jax.lax.dot_general(
                w, rows_scr[half, chunk_rows(c), :value_lanes],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, live_chunks, weigh, None)
        o_ref[pl.ds(r0, row_tile), :] = acc_scr[...].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // row_tile, rows, None)


def paged_latent_attention(q, pool, page_tables, base, limit=None,
                           layer=None, *, value_lanes: int,
                           sm_scale: float | None = None,
                           interpret: bool | None = None,
                           name: str = "paged_latent_attention"):
    """:func:`paged_attention` on a LATENT pool [L, 1, P, page, lanes] (or
    [1, P, page, lanes] with ``layer`` None): one row a token, key and
    value at once, read by every one of the H query heads. q
    [B, T, H, <= lanes] is padded with zeros to the rows' lanes; base,
    limit, page_tables, layer, name as there (causal: ``block_len`` 1).
    The work of slot b follows ``min(limit[b], base[b] + T)``, its live
    length (:func:`_latent_attn_kernel`). Returns [B, T, H, value_lanes]
    in q.dtype: the weighted rows' first ``value_lanes`` (static) lanes.
    """
    if pool.ndim == 4:
        pool, layer = pool[None], 0
    if limit is None:
        limit = jnp.full((q.shape[0],),
                         page_tables.shape[1] * pool.shape[3], jnp.int32)
    return _latent_call(
        q, pool, page_tables.astype(jnp.int32), base.astype(jnp.int32),
        limit.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        value_lanes=value_lanes,
        sm_scale=float(pool.shape[4] ** -0.5 if sm_scale is None
                       else sm_scale),
        interpret=interpret_default() if interpret is None else interpret,
        name=name)


@functools.partial(jax.jit, static_argnames=(
    "value_lanes", "sm_scale", "interpret", "name"))
def _latent_call(q, pool, page_tables, base, limit, layer, *, value_lanes,
                 sm_scale, interpret, name):
    """The latent body's call. Jitted with the layer an OPERAND: a program
    that walks its layers calls it once a layer with the same shapes, and
    the body (several times the other body's equations) is then traced
    once a process and lowered once a program, not once a layer."""
    lanes = pool.shape[4]
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - q.shape[3]),))
    b, t, h, _ = q.shape
    page_size = pool.shape[3]
    max_pages = page_tables.shape[1]
    chunk_pages = min(_LATENT_CHUNK_PAGES, max_pages)
    n_chunks = -(-max_pages // chunk_pages)
    chunk = chunk_pages * page_size
    r = h * t
    r_pad, row_tile = _row_tiling(r, n_chunks * chunk, q.dtype)
    # [B, T, H, lanes] -> [B, H*T, lanes]: query positions innermost, so
    # the kernel recovers t as row % t_span
    qg = q.transpose(0, 2, 1, 3).reshape(b, r, lanes)
    if r_pad != r:
        qg = jnp.pad(qg, ((0, 0), (0, r_pad - r), (0, 0)))

    isz = jnp.dtype(q.dtype).itemsize
    # both halves of the rows' scratch + double-buffered q/o blocks + the
    # row tile's scores, accumulator and their temporaries, with headroom
    vmem = (2 * n_chunks * chunk * lanes * jnp.dtype(pool.dtype).itemsize
            + 2 * r_pad * (lanes + value_lanes) * isz
            + 4 * row_tile * n_chunks * chunk * 4
            + 4 * row_tile * value_lanes * 4)
    kernel = functools.partial(
        _latent_attn_kernel, sm_scale=sm_scale, page_size=page_size,
        max_pages=max_pages, chunk_pages=chunk_pages, t_span=t,
        row_tile=row_tile, value_lanes=value_lanes)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, r_pad, lanes),
                             lambda bi, pt, bs, lim, lyr: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (None, r_pad, value_lanes),
                lambda bi, pt, bs, lim, lyr: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n_chunks * chunk, lanes), pool.dtype),
                pltpu.VMEM((n_chunks, row_tile, chunk), jnp.float32),
                pltpu.VMEM((row_tile, value_lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((2, n_chunks)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, r_pad, value_lanes), q.dtype),
        # in order: a step starts the copies the next one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 * 1024 * 1024, 2 * vmem)),
        interpret=interpret,
        name=name,
    )(page_tables, base, limit, layer, qg, pool)
    return out[:, :r].reshape(b, h, t, value_lanes).transpose(0, 2, 1, 3)


def _div(a, b: int):
    """``a // b`` for an index that is NEVER NEGATIVE, in a kernel body: one
    equation. (``//`` and ``%`` of a traced integer also correct a negative
    operand's quotient, a dozen equations and two nested calls each; a
    walking body has some forty of them, all on positions, pages and grid
    steps, and they were half of what a program paid to trace and lower
    it.)"""
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _rem(a, b: int):
    """``a % b`` for an index that is never negative (:func:`_div`)."""
    return a % b if isinstance(a, int) else jax.lax.rem(a, b)


def _gqa_walk_kernel(pt_ref, base_ref, limit_ref, layer_ref,  # prefetch
                     *refs, sm_scale: float, page_size: int,
                     max_pages: int, chunk_pages: int, t_span: int,
                     row_tile: int, block_len: int, window: int,
                     groups: int, writes: bool = False, sink: int = 0):
    """Grid (B,): one grid step a slot, every KV head of the call walked
    inside it, whose work follows the slot's LIVE length: the end of the
    block that holds its last query position, ``min(limit, ((base + t_span
    - 1) // block_len + 1) * block_len)`` (causal, ``block_len`` 1:
    ``min(limit, base + t_span)``), clipped to the table's span.

    k_pool / v_pool are the whole pools [L, Hkv, P, page, D], left where
    they lie: the body walks the live entries of a slot's table row and
    copies each page of EVERY KV head, one strided copy a pool, into its
    place in the slot's half of k_scr / v_scr [2, Hkv, chunks *
    chunk_pages * page, D], every copy in flight at once, one semaphore a
    CHUNK of ``chunk_pages`` pages. Step b starts the copies of slot b + 1
    into the other half before it computes (step 0 its own too), so a
    slot's pages arrive under the products of the slot before. A page past
    the live ones is never read.

    q_ref [Hkv, R, D], row r = rep * t_span + t of its KV head (the grid
    body's layout). A tile of ``row_tile`` rows of EVERY KV head at once
    (one batched product: a head at a time, the chain of products and
    reductions of one head waited for the head before's: 0.27 against 0.15
    ms a call at the SDAR cell's shapes, chip runs of PR 48) meets the live chunks only, in
    three walks: scores (the first tile's walk waits for each chunk's
    pages as it reaches them), masked as the grid body masks and kept in
    s_scr with the running row maximum;
    exponentials and their row sums; probabilities (cast to q's type)
    times the chunk's values, summed in acc_scr. These are the dense
    softmax's float32 values: a column the walk leaves out is a masked
    one, whose exponential is an exact zero. The dead value pages of the
    last live chunk are zeroed first (a weight of zero times whatever VMEM
    held could be a NaN; a masked score is replaced whatever it was); a
    slot with nothing live walks nothing and writes zeros.

    THE LOWER EDGE (``window`` W above 0, static; a window layer): a row
    sees key j iff ``pos - W < j`` beside the rule above, the walk STARTS
    at the page that holds the first query's oldest visible key, ``max(0,
    base - W + 1) // page`` (a page below it is never copied), scratch page
    0 is that page, and the table row is a RING of ``max_pages`` entries:
    logical page j lies in entry ``j % max_pages``. ``groups`` G above 1
    (static; tables too long for every KV head's pages to fit the
    scratch): the grid is (B x G,), step s walks KV heads ``(s % G) * Hkv /
    G ...`` of slot ``s // G``, and the copies it starts ahead are the next
    STEP's.

    THE CALL'S OWN ROWS (``writes``, static; ISSUE 53): the refs are then
    ``pidx_ref`` (one more scalar operand: the page each row goes to, [B *
    t_span], as the caller's junk-write rule decided), q_ref, ``kn_ref`` /
    ``vn_ref`` (the step's new rows [Hkv, one tile of zeros + t_span rows +
    zeros, D], in the pool's row form and type), the pools as they came in
    (not touched: they ARE the outputs), o_ref, and the pools as OUTPUTS,
    which every copy reads and writes. A row's place is its position's:
    position p lies in scratch page ``p // page - first`` as it lies in the
    table's page. The rows are handled a TILE of positions at a time (the
    rows of one native tile of the pool's type: a copy that starts inside
    one would split a packed word): when the first row tile's walk has
    waited for a chunk's pages, the tiles of the span inside it are laid
    over their columns of k_scr / v_scr (the new rows moved to their
    sublanes by a roll in float32, which bf16 passes through unchanged;
    the tile's other rows keep the bytes just read) and copied back to the
    pool page they were read from, and every such copy is waited for
    before the step ends. So the products see the bytes a scatter followed
    by a read would have given them. A row whose ``pidx`` is not the page
    its position has in the table was sent to the trash page by the
    caller's rule (a chunk's padding, a position past the table): it is
    neither laid nor written. The copies a step starts ahead are another
    slot's pages or other KV heads' rows, so no read meets a write but on
    the trash page, whose values are finite and whose readers' outputs
    nobody uses.

    KEY ROWS WIDER THAN VALUE ROWS (the pools' own lanes: q_ref, k_scr and
    ``kn_ref`` have the key pool's, v_scr, ``vn_ref``, acc_scr and o_ref
    the value pool's): nothing but the shapes differs.

    A SINK (``sink`` R above 0, static: the query heads a KV head; one more
    ref behind the call's rows, ``sink_ref`` [Hkv, >= R, 128] float32, row
    rep of KV head g the learned logit of query head ``g * R + rep`` on
    every lane): the softmax's denominator holds ``exp(sink)`` beside the
    visible keys' exponentials, and the sink's column weighs no value. The
    running maximum starts at the sink and the row sum at ``exp(sink -
    maximum)``; the weights of a row then sum to less than 1.
    """
    refs = list(refs)
    pidx_ref = refs.pop(0) if writes else None
    q_ref = refs.pop(0)
    kn_ref, vn_ref = (refs.pop(0), refs.pop(0)) if writes else (None, None)
    sink_ref = refs.pop(0) if sink else None
    if writes:      # the pools as they came in: they ARE the outputs
        del refs[:2]
        o_ref, k_pool, v_pool, k_scr, v_scr, s_scr, acc_scr, sems, wsems = refs
    else:
        k_pool, v_pool, o_ref, k_scr, v_scr, s_scr, acc_scr, sems = refs
    step = pl.program_id(0)
    half = _rem(step, 2)
    b = step if groups == 1 else _div(step, groups)
    base = base_ref[b]
    limit = limit_ref[b]
    chunk = chunk_pages * page_size
    hkv, r_pad, d = q_ref.shape        # the KV heads of one step
    dv = v_scr.shape[-1]               # a value row's lanes (a key row's: d)

    def pages_of(slot):
        end = base_ref[slot] + t_span
        if block_len > 1:       # the end of the last position's block
            end = (_div(end - 1, block_len) + 1) * block_len
        if window:      # a ring's positions run past its table's span
            live = jnp.maximum(jnp.minimum(limit_ref[slot], end), 0)
        else:
            live = jnp.clip(jnp.minimum(limit_ref[slot], end),
                            0, max_pages * page_size)
        return _div(live + page_size - 1, page_size)

    def first_of(slot):
        """The page of the first query's oldest visible key."""
        return _div(jnp.maximum(base_ref[slot] - (window - 1), 0), page_size)

    def page_rows(j):
        return pl.ds(pl.multiple_of(j * page_size, page_size), page_size)

    def heads_of(s):
        """The KV heads step s walks: every one, or its group's."""
        return slice(None) if groups == 1 \
            else pl.ds(_rem(s, groups) * hkv, hkv)

    def page_copies(s, j, at):
        """Logical page j of step s's slot, the step's KV heads of it, K
        and V, into scratch page ``at``."""
        slot = s if groups == 1 else _div(s, groups)
        return [pltpu.make_async_copy(
            pool.at[layer_ref[0], heads_of(s),
                    pt_ref[slot, _rem(j, max_pages) if window else j]],
            scr.at[_rem(s, 2), :, page_rows(at)],
            sems.at[_rem(s, 2), _div(at, chunk_pages)])
            for pool, scr in ((k_pool, k_scr), (v_pool, v_scr))]

    def fetch(s):
        slot = s if groups == 1 else _div(s, groups)
        first = first_of(slot) if window else 0

        def start(j, carry):
            for copy in page_copies(s, j, j - first if window else j):
                copy.start()
            return carry
        jax.lax.fori_loop(first, pages_of(slot), start, None)

    @pl.when(step == 0)
    def _own():
        fetch(step)

    @pl.when(step + 1 < pl.num_programs(0))
    def _ahead():
        fetch(step + 1)

    first = first_of(b) if window else 0
    live_pages = jnp.maximum(pages_of(b) - first, 0) if window \
        else pages_of(b)
    live_chunks = _div(live_pages + chunk_pages - 1, chunk_pages)

    def zero(j, carry):
        v_scr[half, :, page_rows(j), :] = jnp.zeros(
            (hkv, page_size, dv), v_scr.dtype)
        return carry

    jax.lax.fori_loop(live_pages, live_chunks * chunk_pages, zero, None)

    # the call's own rows, a tile of positions at a time
    if writes:
        sub = _write_tile(k_scr.dtype, page_size)
        tile0 = _div(base, sub)             # the tile of the span's first row
        origin = first * page_size          # the position of scratch column 0

        def tile_at(tile):
            """(the tile's first position, the pool page the table has for
            it, its columns of the scratch)."""
            p0 = tile * sub
            page = _div(p0, page_size)
            return (p0, pt_ref[b, _rem(page, max_pages) if window else page],
                    pl.ds(pl.multiple_of(p0 - origin, sub), sub))

        def tile_copies(tile):
            """Tile ``tile`` of positions, the step's KV heads of it, K and V,
            from the scratch back to the page it was read from."""
            p0, page, cols = tile_at(tile)
            return [pltpu.make_async_copy(
                scr.at[half, :, cols],
                pool.at[layer_ref[0], heads_of(step), page,
                        pl.ds(pl.multiple_of(_rem(p0, page_size), sub), sub)],
                wsems.at[n])
                for n, (pool, scr) in enumerate(((k_pool, k_scr),
                                                 (v_pool, v_scr)))]

        def span_tiles(lo, hi):
            """The tiles of the call's span that lie in scratch columns lo ..
            hi of the live pages."""
            hi = jnp.minimum(hi, live_pages * page_size)
            return (jnp.maximum(tile0, _div(origin + lo, sub)),
                    jnp.minimum(_div(base + t_span - 1, sub) + 1,
                                _div(origin + hi, sub)))

        def lay(tile, carry):
            p0, page, cols = tile_at(tile)

            def kept_rows(lanes):
                """(the sublane of each element of a [sub, lanes] tile, 1
                where the caller's rule kept that row here). The tile holds
                at most ``min(t_span, sub)`` rows of the call, from row
                ``max(p0 - base, 0)`` on: a decode call's one."""
                at = jax.lax.broadcasted_iota(jnp.int32, (sub, lanes), 0)
                keep = jnp.zeros((sub, lanes), jnp.int32)
                t0 = jnp.maximum(p0 - base, 0)
                for j in range(min(t_span, sub)):
                    t = t0 + j              # a row of the call, which lies at
                    r = base + t - p0       # sublane r (past the tile: none)
                    kept = (t < t_span) & (pidx_ref[
                        b * t_span + jnp.minimum(t, t_span - 1)] == page)
                    keep = jnp.where(at == r, kept.astype(jnp.int32), keep)
                return at, keep

            masks = {d: kept_rows(d)}
            if dv != d:
                masks[dv] = kept_rows(dv)
            shift = base - tile0 * sub      # the sublane of the span's first row
            m = tile - tile0

            def rows_tile(ref, n):
                return ref[:, pl.ds(pl.multiple_of((m + n) * sub, sub), sub),
                           :].astype(jnp.float32)

            for ref, scr in ((kn_ref, k_scr), (vn_ref, v_scr)):
                at, keep = masks[scr.shape[-1]]
                # row r of the tile is row ``sub * m - shift + r`` of the call:
                # sublane r - shift of the rows' tile m (which the leading
                # tile of zeros makes tile m + 1), or, below ``shift``,
                # sublane sub + r - shift of tile m - 1: one roll of both
                new = pltpu.roll(jnp.where((at < sub - shift)[None],
                                           rows_tile(ref, 1),
                                           rows_tile(ref, 0)), shift, 1)
                old = scr[half, :, cols, :].astype(jnp.float32)
                scr[half, :, cols, :] = jnp.where(
                    (keep != 0)[None], new, old).astype(scr.dtype)
            for copy in tile_copies(tile):
                copy.start()
            return carry

    def rows(i, carry):
        r0 = pl.multiple_of(i * row_tile, row_tile)
        q = q_ref[:, pl.ds(r0, row_tile), :]                   # [Hkv, TR, D]
        row = r0 + jax.lax.broadcasted_iota(
            jnp.int32, (row_tile, chunk), 0)
        pos = base + _rem(row, t_span)
        # the last key a row sees: itself, or the end of its block
        seen = pos + 1 if block_len == 1 \
            else (_div(pos, block_len) + 1) * block_len

        def chunk_rows(c):
            return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

        def scores(c, m):
            @pl.when(i == 0)
            def _arrive():
                for p in range(chunk_pages):
                    j = c * chunk_pages + p

                    @pl.when(j < live_pages)
                    def _wait():
                        for copy in page_copies(
                                step, first + j if window else j, j):
                            copy.wait()

                if writes:      # the span's tiles inside the chunk
                    jax.lax.fori_loop(
                        *span_tiles(c * chunk, (c + 1) * chunk), lay, None)

            # fp32 MXU accumulation rounded to q.dtype, then the fp32
            # scale: the gather path's einsum(...).astype(f32) * sm
            s = jax.lax.dot_general(
                q, k_scr[half, :, chunk_rows(c), :],
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32).astype(q.dtype)
            s = s.astype(jnp.float32) * sm_scale          # [Hkv, TR, chunk]
            col = c * chunk + jax.lax.broadcasted_iota(
                jnp.int32, (row_tile, chunk), 1)
            if window:      # scratch page 0 is the walk's first page
                col = first * page_size + col
            valid = (col < seen) & (col < limit)
            if window:
                valid = valid & (col > pos - window)
            s = jnp.where(valid[None], s, _NEG_INF)
            s_scr[c] = s
            return jnp.maximum(m, s.max(axis=-1, keepdims=True))

        lowest = jnp.full((hkv, row_tile, 1), _NEG_INF, jnp.float32)
        if sink:    # each row's own head's sink: row r = rep * t_span + t
            rep = _div(r0 + jax.lax.broadcasted_iota(
                jnp.int32, (row_tile, 1), 0), t_span)
            for j in range(sink):
                lowest = jnp.where((rep == j)[None],
                                   sink_ref[:, j:j + 1, :][:, :, :1], lowest)
        m = jax.lax.fori_loop(0, live_chunks, scores, lowest)

        def sums(c, total):
            e = jnp.exp(s_scr[c] - m)
            s_scr[c] = e
            return total + e.sum(axis=-1, keepdims=True)

        total = jax.lax.fori_loop(
            0, live_chunks, sums,
            jnp.exp(lowest - m) if sink
            else jnp.zeros((hkv, row_tile, 1), jnp.float32))

        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

        def weigh(c, carry):
            w = (s_scr[c] / total).astype(q.dtype)
            acc_scr[...] += jax.lax.dot_general(
                w, v_scr[half, :, chunk_rows(c), :],
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, live_chunks, weigh, None)
        o_ref[:, pl.ds(r0, row_tile), :] = acc_scr[...].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, r_pad // row_tile, rows, None)

    if writes:
        def written(tile, carry):
            for copy in tile_copies(tile):
                copy.wait()
            return carry

        jax.lax.fori_loop(*span_tiles(0, live_chunks * chunk), written, None)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "name", "block_len", "window"))
def _gqa_walk_call(q, k_pages, v_pages, page_tables, base, limit, layer,
                   write=None, sink=None, *, sm_scale, interpret, name,
                   block_len, window=None):
    """The walking body's call on pools of K and V per head, every operand
    as :func:`paged_attention` has prepared it. Jitted with the layer an
    OPERAND, as the latent body's call is and for its reason: a block
    program calls it twice a walked layer, and the body is then traced
    once a process and lowered once a program.

    The scratch holds the widest walk (``window`` None or 0: a whole
    table; a window layer: its window and span) of as many KV heads a step
    as :data:`_WALK_KV_BYTES` allows: every head and one grid step a slot
    at the tables the cells serve, fewer under a wider table, one at least;
    if that one does not fit :data:`_WALK_VMEM_LIMIT` the call raises.

    ``write`` (k_new, v_new [B, T, Hkv, D] in the pool's row form, page_idx
    [B, T]): the call's own rows ride in and the body writes them (its
    docstring); the new operands join this ``jit``, the pools are outputs
    aliased to the inputs, and the call returns (read, k_pages, v_pages).

    The pools' rows need not be of one width: q and ``k_new`` have the key
    pool's lanes, ``v_new`` and the read the value pool's. ``sink`` (float32
    [H], a learned logit a query head): the body's "a sink"."""
    b, t, h, d = q.shape
    dv = v_pages.shape[4]
    hkv = k_pages.shape[1]
    n_rep = h // hkv
    page_size = k_pages.shape[3]
    max_pages = page_tables.shape[1]
    # the pages one walk can hold: the table, or a window, the span and
    # the page the walk's first position lies in
    span_pages = max_pages if not window \
        else -(-(window + t - 1) // page_size) + 1
    if span_pages > max_pages:
        raise ValueError(
            f"a span of {t} positions under a window of {window} walks "
            f"{span_pages} pages; the ring table holds {max_pages}")
    chunk_pages = min(_GQA_CHUNK_PAGES, span_pages)
    n_chunks = -(-span_pages // chunk_pages)
    chunk = chunk_pages * page_size
    r = n_rep * t
    isz = jnp.dtype(q.dtype).itemsize
    # the KV heads a grid step walks: as many as the scratch may hold of
    # (both halves of both pools' pages of one walk), by what the call can
    # see, the table's width or the window's: every head at the cells'
    # tables of 2,048 positions, two at 16 k, one at 32 k and beyond
    head_bytes = 2 * n_chunks * chunk * (d + dv) \
        * jnp.dtype(k_pages.dtype).itemsize
    groups = next(g for g in range(1, hkv + 1) if hkv % g == 0
                  and (hkv // g * head_bytes <= _WALK_KV_BYTES or g == hkv))
    # a score tile holds the rows of every KV head of a step
    r_pad, row_tile = _row_tiling(
        r, hkv // groups * n_chunks * chunk, q.dtype,
        _SCORE_TILE_BYTES if window is None else _WALK_SCORE_BYTES)
    hs = hkv // groups       # the KV heads of one grid step
    # [B, T, H, D] -> [B, Hkv, n_rep*T, D]: the grid body's layout
    qg = q.reshape(b, t, hkv, n_rep, d).transpose(0, 2, 3, 1, 4).reshape(
        b, hkv, r, d)
    if r_pad != r:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, r_pad - r), (0, 0)))

    # what the call declares: both halves of both scratches, the
    # double-buffered q/o blocks, the row tile's scores and accumulator;
    # and, with headroom, their temporaries beside them
    held = (hs * head_bytes + 2 * hs * r_pad * (d + dv) * isz
            + hs * row_tile * (n_chunks * chunk + dv) * 4)
    if held > _WALK_VMEM_LIMIT and not interpret:
        raise ValueError(
            f"a walk of {n_chunks * chunk} positions holds {held >> 20} MB "
            f"in VMEM a grid step of {hs} KV head(s) ({hs * head_bytes >> 20} "
            f"MB of it the pages of K and V, both halves) and the body may "
            f"take {_WALK_VMEM_LIMIT >> 20} MB: use a shorter max_seq_len "
            f"or attention_kernel='gather'")
    vmem = held + 3 * hs * row_tile * (n_chunks * chunk + dv) * 4
    kernel = functools.partial(
        _gqa_walk_kernel, sm_scale=sm_scale, page_size=page_size,
        max_pages=max_pages, chunk_pages=chunk_pages, t_span=t,
        row_tile=row_tile, block_len=block_len, window=window or 0,
        groups=groups, writes=write is not None,
        sink=0 if sink is None else n_rep)
    pools = (k_pages, v_pages)

    def rows_of(bi, *_):
        """A step's block of q, o and the new rows: its slot's rows, its
        group's heads."""
        return (bi, 0, 0, 0) if groups == 1 \
            else (bi // groups, bi % groups, 0, 0)

    scalars, blocked = (page_tables, base, limit, layer), [qg]
    if write is not None:
        k_new, v_new, page_idx = write
        sub = _write_tile(k_pages.dtype, page_size)
        # the tiles a span of t positions can touch, one tile of zeros
        # before its rows (the body's roll reads a tile below) and zeros
        # behind them up to whole tiles
        t_pad = ((t + sub - 2) // sub + 2) * sub
        scalars += (page_idx.reshape(-1).astype(jnp.int32),)
        blocked += [jnp.pad(new.astype(pool.dtype).transpose(0, 2, 1, 3),
                            ((0, 0), (0, 0), (sub, t_pad - sub - t), (0, 0)))
                    for new, pool in ((k_new, k_pages), (v_new, v_pages))]
        vmem += 2 * hs * t_pad * (d + dv) * jnp.dtype(k_pages.dtype).itemsize
    in_specs = [pl.BlockSpec((None, hs) + a.shape[2:], rows_of)
                for a in blocked]
    if sink is not None:
        # [H] -> [1, Hkv, query heads a KV head (whole sublane tiles), 128]:
        # the same for every slot, a step's group of KV heads of it
        rows = -(-n_rep // 8) * 8
        blocked.append(jnp.broadcast_to(jnp.pad(
            sink.astype(jnp.float32).reshape(hkv, n_rep),
            ((0, 0), (0, rows - n_rep)))[None, :, :, None],
            (1, hkv, rows, 128)))
        in_specs.append(pl.BlockSpec(
            (None, hs, rows, 128),
            lambda bi, *_: (0, 0 if groups == 1 else bi % groups, 0, 0)))
    n_in = len(scalars) + len(blocked)
    written = pools if write is not None else ()    # the outputs beside o
    out, *written = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b * groups,),
            in_specs=in_specs
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            out_specs=[pl.BlockSpec((None, hs, r_pad, dv), rows_of)]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in written],
            scratch_shapes=[
                pltpu.VMEM((2, hs, n_chunks * chunk, pool.shape[4]),
                           pool.dtype)
                for pool in pools] + [
                pltpu.VMEM((n_chunks, hs, row_tile, chunk), jnp.float32),
                pltpu.VMEM((hs, row_tile, dv), jnp.float32),
                pltpu.SemaphoreType.DMA((2, n_chunks)),
            ] + ([pltpu.SemaphoreType.DMA((2,))] if written else [])),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, r_pad, dv), q.dtype)]
        + [jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in written],
        # the pools are written where they lie
        input_output_aliases={n_in + n: 1 + n for n in range(len(written))},
        # in order: a step starts the copies the next one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(max(32 * 1024 * 1024, 2 * vmem),
                                 _WALK_VMEM_LIMIT)),
        interpret=interpret,
        name=name,
    )(*scalars, *blocked, *pools)
    out = out[:, :, :r].reshape(b, hkv, n_rep, t, dv).transpose(
        0, 3, 1, 2, 4).reshape(b, t, h, dv)
    return (out, *written) if write is not None else out


def _packed_heads(q, k_pages, v_pages, page_tables, base, limit, layer, *,
                  sm_scale, interpret, name, block_len=1, walk=False,
                  window=None, write=None, sink=None):
    """Heads narrower than a pool row: the pool holds ``pack`` KV heads
    side by side in one row of lanes ([L, Hkv / pack, P, page, pack * D]:
    heads of 64 two to a 128-lane row, so HBM holds no padding and a page
    block is whole vectors). The kernel runs as it is, on rows of
    ``pack * D`` lanes: a query row of KV head j is laid into lanes
    j * D .. (j + 1) * D of a zero row, so its scores are its own head's
    (the other head's lanes meet zeros, which add nothing: the same
    float32 sums), and of the output row, which is over both heads'
    values, the same lanes are kept. ``write``: the span's new rows are
    pool rows already, and pass through; so does ``sink``, a number a
    query head in the same order."""
    b, t, h, d = q.shape
    rows = k_pages.shape[1]
    pack = k_pages.shape[4] // d
    n_rep = h // (rows * pack)
    # kv-major heads: h = ((row * pack) + j) * n_rep + rep
    q6 = q.reshape(b, t, rows, pack, n_rep, d)
    lane = jnp.eye(pack, dtype=q.dtype)                     # [j, j']
    spread = (q6[..., None, :] * lane[:, None, :, None]).reshape(
        b, t, h, pack * d)
    out = paged_attention(spread, k_pages, v_pages, page_tables, base, limit,
                          layer, sm_scale=sm_scale, interpret=interpret,
                          name=name, block_len=block_len, walk=walk,
                          window=window, write=write,       # [B, T, H, pack*D]
                          sink=sink)
    out, *written = out if write is not None else (out,)
    out = out.reshape(b, t, rows, pack, n_rep, pack, d)
    out = jnp.stack([out[:, :, :, j, :, j] for j in range(pack)],
                    axis=3).reshape(b, t, h, d)
    return (out, *written) if write is not None else out


def _with_axis(write, axis: int):
    """A call's new rows and their pages with the axis its grid has one of
    (a decode's span, a chunk's slot) put back."""
    return write and tuple(jnp.expand_dims(a, axis) for a in write)


def paged_decode_attention(q, k_pages, v_pages, page_tables, pos,
                           layer=None, *, sm_scale: float | None = None,
                           interpret: bool | None = None,
                           value_lanes: int = 0, window: int | None = None,
                           write=None, sink=None):
    """Single-token decode attention: q [B, H, D], new token at position
    ``pos[b]`` (attends 0..pos inclusive — its own k/v is already written
    to the pool, or rides in as ``write``: (k_new, v_new [B, Hkv, D],
    page_idx [B])). Pool, ``layer``, ``window`` (the lower edge of a block
    that has window layers: it walks), ``write`` and ``sink`` as in
    :func:`paged_attention`. Returns [B, H, Dv]; with ``write``, (that,
    k_pages, v_pages)."""
    if value_lanes:
        return paged_latent_attention(
            q[:, None], k_pages, page_tables, pos, layer=layer,
            value_lanes=value_lanes, sm_scale=sm_scale, interpret=interpret,
            name="paged_decode_attention")[:, 0]
    out = paged_attention(q[:, None], k_pages, v_pages, page_tables, pos,
                          layer=layer, sm_scale=sm_scale, interpret=interpret,
                          name="paged_decode_attention",
                          walk="decode" in WALKS_LIVE["heads"], window=window,
                          write=_with_axis(write, 1), sink=sink)
    return out[:, 0] if write is None else (out[0][:, 0], *out[1:])


def paged_verify_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=None, *, sm_scale: float | None = None,
                           interpret: bool | None = None,
                           value_lanes: int = 0, write=None):
    """Multi-query speculative verify: q [B, T, H, D], T = k+1 draft span
    per slot, q[b, t] at position ``seq_lens[b] + t`` — causal within the
    span, full attention over the slot's cached pages (all T spans' k/v
    are pre-written, or ride in as ``write``, :func:`paged_attention`).
    Returns [B, T, H, D]."""
    if value_lanes:
        return paged_latent_attention(
            q, k_pages, page_tables, seq_lens, layer=layer,
            value_lanes=value_lanes, sm_scale=sm_scale, interpret=interpret,
            name="paged_verify_attention")
    return paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=layer, sm_scale=sm_scale,
                           interpret=interpret,
                           name="paged_verify_attention",
                           walk="verify" in WALKS_LIVE["heads"], write=write)


def paged_block_attention(q, k_pages, v_pages, page_tables, seq_lens,
                          layer=None, *, block_len: int,
                          sm_scale: float | None = None,
                          interpret: bool | None = None, write=None):
    """The block pass of generation by diffusion over blocks: q [B, T, H, D]
    with T a whole number of blocks of ``block_len`` (one, or two for the
    pass that keeps a block and denoises the next), q[b, t] at position
    ``seq_lens[b] + t`` (a block edge), every position seeing the slot's
    cached pages and the span up to the end of its own block (whose k/v
    are pre-written, or ride in as ``write``, :func:`paged_attention`).
    Returns [B, T, H, D]; with ``write``, (that, k_pages, v_pages)."""
    return paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=layer, sm_scale=sm_scale,
                           interpret=interpret,
                           name="paged_block_attention", block_len=block_len,
                           walk="block" in WALKS_LIVE["heads"], write=write)


def paged_chunk_attention(q, k_pages, v_pages, page_table, start, true_len,
                          layer=None, *, sm_scale: float | None = None,
                          interpret: bool | None = None,
                          block_len: int = 1, value_lanes: int = 0,
                          window: int | None = None, write=None, sink=None):
    """Chunked-prefill attention for ONE slot: q [1, C, H, D] chunk whose
    first token sits at position ``start``; keys are the slot's whole
    paged view (earlier chunks + this one, pre-written) bounded by
    ``true_len``; causal, or by blocks of ``block_len``; ``window``: the
    lower edge of a block that has window layers (it walks, and under a
    window ``page_table`` is the slot's ring); ``write``: the chunk's own
    rows ride in ((k_new, v_new [C, Hkv, D], page_idx [C]);
    :func:`paged_attention`, as ``sink``). Returns [1, C, H, Dv]; with
    ``write``, (that, k_pages, v_pages).

    On a latent pool every head's rows lie on the ONE KV head, C x H of
    them: more than a query block should hold in VMEM (512 x 32 rows of 640
    lanes: 21 MB, twice for the pipeline). The chunk is then cut into spans
    of positions, each a slot of its own on the same page table (its base
    the span's first position, so it walks the pages up to its OWN end),
    at most ``_MAX_SPAN_ROWS`` rows a span."""
    base = jnp.reshape(start, (1,)).astype(jnp.int32)
    limit = jnp.reshape(true_len, (1,)).astype(jnp.int32)
    if not value_lanes:
        return paged_attention(
            q, k_pages, v_pages, page_table[None], base, limit, layer,
            sm_scale=sm_scale, interpret=interpret,
            name="paged_chunk_attention", block_len=block_len,
            walk="chunk" in WALKS_LIVE["heads"], window=window,
            write=_with_axis(write, 0), sink=sink)
    _, c, h, _ = q.shape
    span = max(1, _MAX_SPAN_ROWS // h)
    n = c // span if c > span and c % span == 0 else 1
    out = paged_latent_attention(
        q.reshape(n, c // n, h, q.shape[3]), k_pages,
        jnp.broadcast_to(page_table[None], (n,) + page_table.shape),
        base + c // n * jnp.arange(n, dtype=jnp.int32),
        jnp.broadcast_to(limit, (n,)), layer, value_lanes=value_lanes,
        sm_scale=sm_scale, interpret=interpret,
        name="paged_chunk_attention")
    return out.reshape(1, c, h, value_lanes)
