"""Fused paged-attention kernel family (Pallas TPU) for the serving path.

The serving engine's gather attention materializes each slot's full
[max_len, Hkv, D] K/V view from the page pool every layer of every step.
These kernels read the pool pages DIRECTLY via the layer index and the
slot page table (scalar-prefetch block index maps, the canonical TPU
paged-attention pattern): the per-slot view is assembled page by page in
VMEM scratch, never in HBM. They take the WHOLE pool
[L, Hkv, P, page, D] and pick the layer inside the index map, so the
serving programs (serve/llm/kv_cache.py) can carry the pool through
their loops in place and never slice a layer out of it.

One core kernel covers the whole family — decode (T=1), multi-query
speculative verify (T=k+1 causal within the span), chunked prefill
(B=1, extra ``true_len`` bound) and the block pass of generation by
diffusion over blocks (T = one block, every position of which sees the
whole block) are the same computation with different query spans and
masks, dispatched through thin wrappers. The mask is ``col < (pos //
block_len + 1) * block_len``: key j is visible to query i iff j's block is
not after i's; at ``block_len`` 1 (static) that is the causal ``col <=
pos``.

A LATENT pool (a cache spec with ``latent_dim``, models/block.py) is one
array of one row a token, [L, 1, P, page, lanes], whose first
``value_lanes`` lanes are also the row's values: every wrapper takes
``value_lanes`` (and no value pool) and the kernel then reads each page
ONCE, into one scratch of which the value operand is a static prefix of
lanes. The rows are padded to whole 128-lane vectors with zeros, which
meet whatever the query holds there and add nothing.

Identity contract: greedy TOKENS under the pallas backend must equal the
gather backend exactly (hard-asserted in tests and the serve bench), so
the kernel computes the SAME dense-softmax numerics as the gather path —
fp32 logits scaled by ``sm_scale``, masked with -1e30, full-row fp32
softmax, probabilities cast back to q.dtype, same contractions — instead
of a flash-style streaming softmax (whose rescaling visibly changes
float results). Raw attention outputs agree with gather to the last ULPs
(the fused [R, L] dot and the batched einsum may order partial sums
differently); the win is memory traffic, not math: pages stream
HBM->VMEM once per (slot, kv-head) with no materialized gather
intermediate.

Off-TPU the kernels run in interpreter mode (pl.pallas_call
(interpret=True)), which is how tier-1 gates them on CPU — same story as
ops/attention.py.

Tensor parallelism (ISSUE 20): a pallas_call is opaque to GSPMD, so on a
TP mesh the serving engine runs these kernels under ``shard_map`` with
the canonical per-KV-head partitioning from :func:`tp_shard_specs` —
pool axis 1 (Hkv of [L, Hkv, P, page, D]) and q's H axis split by the
"tensor" mesh axis. The kv-major GQA head order above is what makes that
split clean: each
shard's kernel invocation is exactly a single-chip call over Hkv/tp
kv heads with their n_rep q heads, no kernel-internal changes and no
in-kernel collectives.
"""

from __future__ import annotations

import functools

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


def interpret_default() -> bool:
    """Whether these kernels run interpreted in this process: true
    everywhere but on a TPU backend (the CPU tests' only way to run
    them). The engine reports it as ``attn_interpret``."""
    return jax.default_backend() != "tpu"


def sublane_tile(dtype) -> int:
    """Rows of one native TPU tile for ``dtype``: (8, 128) at 4 bytes,
    (16, 128) at 2, (32, 128) at 1."""
    return 32 // jnp.dtype(dtype).itemsize


def can_tile(head_dim: int, page_size: int, dtype,
             n_kv_heads: int = 2) -> bool:
    """Shapes Mosaic compiles these kernels for: head_dim fills whole
    128-lane vectors, or is the half vector of 64 lanes with an even
    number of KV heads (``n_kv_heads``: a chip's share of them), which the
    pool then stores two to a 128-lane row, so that a page block's last
    dimension is whole vectors and HBM holds no padding (an odd count
    cannot be packed, and a 64-lane pool is re-laid out by the compiler
    around every token write); and a page is whole sublane tiles, so the
    page copy into scratch and both matmuls stay tile-aligned."""
    return (head_dim % 128 == 0
            or (head_dim == 64 and n_kv_heads % 2 == 0)) \
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


def _row_tiling(r: int, max_len: int, dtype) -> tuple[int, int]:
    """(padded row count, row tile). Rows pad to whole sublane tiles —
    Mosaic rejects a 2-row bf16 matmul operand — and are processed in
    tiles small enough that one [tile, L] float32 score block plus its
    softmax temporaries fits scoped VMEM at any sequence limit."""
    sub = sublane_tile(dtype)
    cap = min(_MAX_ROW_TILE, _SCORE_TILE_BYTES // (4 * max_len))
    cap = max(sub, cap // sub * sub)
    r_sub = -(-r // sub) * sub
    if r_sub <= cap:
        return r_sub, r_sub
    return -(-r // cap) * cap, cap


def _paged_attn_kernel(pt_ref, base_ref, limit_ref, layer_ref,  # prefetch
                       q_ref, k_ref, *rest,
                       sm_scale: float, page_size: int, num_pages: int,
                       t_span: int, row_tile: int, block_len: int = 1,
                       value_lanes: int = 0):
    """Grid (B, Hkv, num_pages); one (slot, kv-head) pair accumulates its
    pages into VMEM scratch and computes dense attention on the last page.

    q_ref: [1, 1, R, D] where R >= n_rep * t_span, row r = rep * t_span + t
    (GQA heads grouped per kv head, query positions innermost — matches
    ``gqa_expand``'s kv-major head order); rows past n_rep * t_span are
    zero padding the wrapper slices off. k_ref/v_ref: this grid step's
    pool page [page, D] (layer, kv-head and page block dimensions squeezed),
    selected by the block index map through the scalar-prefetched layer
    index and page table — the read IS the gather. ``layer_ref`` is read
    by the index maps only. ``rest``: v_ref, o_ref, k_scr, v_scr; or, with
    ``value_lanes`` (a latent pool), o_ref and k_scr alone: the values are
    the first ``value_lanes`` lanes of the key rows in the one scratch.
    """
    if value_lanes:
        o_ref, k_scr = rest
    else:
        v_ref, o_ref, k_scr, v_scr = rest
    b = pl.program_id(0)
    p = pl.program_id(2)

    off = pl.multiple_of(p * page_size, page_size)
    k_scr[pl.ds(off, page_size)] = k_ref[...]
    if not value_lanes:
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
            values = k_scr[:, :value_lanes] if value_lanes else v_scr[:]
            o_ref[0, 0, pl.ds(r0, row_tile), :] = jax.lax.dot_general(
                w, values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, q_ref.shape[2] // row_tile, rows, None)


def paged_attention(q, k_pages, v_pages, page_tables, base, limit=None,
                    layer=None, *, sm_scale: float | None = None,
                    interpret: bool | None = None,
                    name: str = "paged_attention", block_len: int = 1,
                    value_lanes: int = 0):
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
    (each of the three callers below passes its own).
    ``value_lanes`` (static) above 0: ``k_pages`` is a latent pool
    [L, 1, P, page, lanes] and ``v_pages`` None; the H query heads all
    read the one row a token, q [B, T, H, <= lanes] is padded with zeros
    to the rows' lanes, and the result is [B, T, H, value_lanes].
    Returns [B, T, H, D] in q.dtype.
    """
    if value_lanes:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, k_pages.shape[4] - q.shape[3]),))
    b, t, h, d = q.shape
    if k_pages.ndim == 4:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    if k_pages.shape[4] != d:
        return _packed_heads(
            q, k_pages, v_pages, page_tables, base, limit, layer,
            sm_scale=d ** -0.5 if sm_scale is None else sm_scale,
            interpret=interpret, name=name, block_len=block_len)
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
        block_len=block_len, value_lanes=value_lanes)
    d_out = value_lanes or d
    # the paged read: block index (lyr[0], hi, pt[bi, pi]) picks the
    # layer's pool page straight off the scalar-prefetched layer index
    # and table; a latent pool is read once (no value pool, one scratch)
    pools = (k_pages,) if value_lanes else (k_pages, v_pages)
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
                (1, 1, r_pad, d_out),
                lambda bi, hi, pi, pt, bs, lim, lyr: (bi, hi, 0, 0)),
            scratch_shapes=[pltpu.VMEM((max_len, d), pool.dtype)
                            for pool in pools]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, r_pad, d_out), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 * 1024 * 1024, 2 * vmem)),
        interpret=interpret,
        name=name,
    )(page_tables.astype(jnp.int32), base.astype(jnp.int32),
      limit.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      qg, *pools)
    return out[:, :, :r].reshape(b, hkv, n_rep, t, d_out).transpose(
        0, 3, 1, 2, 4).reshape(b, t, h, d_out)


def _packed_heads(q, k_pages, v_pages, page_tables, base, limit, layer, *,
                  sm_scale, interpret, name, block_len=1):
    """Heads narrower than a pool row: the pool holds ``pack`` KV heads
    side by side in one row of lanes ([L, Hkv / pack, P, page, pack * D]:
    heads of 64 two to a 128-lane row, so HBM holds no padding and a page
    block is whole vectors). The kernel runs as it is, on rows of
    ``pack * D`` lanes: a query row of KV head j is laid into lanes
    j * D .. (j + 1) * D of a zero row, so its scores are its own head's
    (the other head's lanes meet zeros, which add nothing: the same
    float32 sums), and of the output row, which is over both heads'
    values, the same lanes are kept."""
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
                          name=name, block_len=block_len)   # [B, T, H, pack*D]
    out = out.reshape(b, t, rows, pack, n_rep, pack, d)
    return jnp.stack([out[:, :, :, j, :, j] for j in range(pack)],
                     axis=3).reshape(b, t, h, d)


def paged_decode_attention(q, k_pages, v_pages, page_tables, pos,
                           layer=None, *, sm_scale: float | None = None,
                           interpret: bool | None = None,
                           value_lanes: int = 0):
    """Single-token decode attention: q [B, H, D], new token at position
    ``pos[b]`` (attends 0..pos inclusive — its own k/v is already written
    to the pool). Pool and ``layer`` as in :func:`paged_attention`.
    Returns [B, H, D]."""
    out = paged_attention(q[:, None], k_pages, v_pages, page_tables, pos,
                          layer=layer, sm_scale=sm_scale, interpret=interpret,
                          name="paged_decode_attention",
                          value_lanes=value_lanes)
    return out[:, 0]


def paged_verify_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=None, *, sm_scale: float | None = None,
                           interpret: bool | None = None,
                           value_lanes: int = 0):
    """Multi-query speculative verify: q [B, T, H, D], T = k+1 draft span
    per slot, q[b, t] at position ``seq_lens[b] + t`` — causal within the
    span, full attention over the slot's cached pages (all T spans' k/v
    are pre-written). Returns [B, T, H, D]."""
    return paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=layer, sm_scale=sm_scale,
                           interpret=interpret,
                           name="paged_verify_attention",
                           value_lanes=value_lanes)


def paged_block_attention(q, k_pages, v_pages, page_tables, seq_lens,
                          layer=None, *, block_len: int,
                          sm_scale: float | None = None,
                          interpret: bool | None = None):
    """The block pass of generation by diffusion over blocks: q [B, T, H, D]
    with T a whole number of blocks of ``block_len`` (one, or two for the
    pass that keeps a block and denoises the next), q[b, t] at position
    ``seq_lens[b] + t`` (a block edge), every position seeing the slot's
    cached pages and the span up to the end of its own block (whose k/v
    are pre-written). Returns [B, T, H, D]."""
    return paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           layer=layer, sm_scale=sm_scale,
                           interpret=interpret,
                           name="paged_block_attention", block_len=block_len)


def paged_chunk_attention(q, k_pages, v_pages, page_table, start, true_len,
                          layer=None, *, sm_scale: float | None = None,
                          interpret: bool | None = None,
                          block_len: int = 1, value_lanes: int = 0):
    """Chunked-prefill attention for ONE slot: q [1, C, H, D] chunk whose
    first token sits at position ``start``; keys are the slot's whole
    paged view (earlier chunks + this one, pre-written) bounded by
    ``true_len``; causal, or by blocks of ``block_len``. Returns
    [1, C, H, D].

    On a latent pool every head's rows lie on the ONE KV head, C x H of
    them: more than a query block should hold in VMEM (512 x 32 rows of 640
    lanes: 21 MB, twice for the pipeline). The chunk is then cut into spans
    of positions, each a slot of its own on the same page table (its base
    the span's first position), at most ``_MAX_SPAN_ROWS`` rows a span."""
    base = jnp.reshape(start, (1,)).astype(jnp.int32)
    limit = jnp.reshape(true_len, (1,)).astype(jnp.int32)
    _, c, h, _ = q.shape
    span = max(1, _MAX_SPAN_ROWS // h)
    if not value_lanes or c <= span or c % span:
        return paged_attention(
            q, k_pages, v_pages, page_table[None], base, limit, layer,
            sm_scale=sm_scale, interpret=interpret,
            name="paged_chunk_attention", block_len=block_len,
            value_lanes=value_lanes)
    n = c // span
    out = paged_attention(
        q.reshape(n, span, h, q.shape[3]), k_pages, None,
        jnp.broadcast_to(page_table[None], (n,) + page_table.shape),
        base + span * jnp.arange(n, dtype=jnp.int32),
        jnp.broadcast_to(limit, (n,)), layer, sm_scale=sm_scale,
        interpret=interpret, name="paged_chunk_attention",
        block_len=block_len, value_lanes=value_lanes)
    return out.reshape(1, c, h, value_lanes)

