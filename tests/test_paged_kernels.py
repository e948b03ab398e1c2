"""Pallas paged-attention kernel family (ISSUE 18).

Pins the PR's acceptance invariants:
- every kernel in the family (decode / multi-query verify / chunked
  prefill) matches the gather path's dense-softmax math across (width, k)
  tiers and ragged per-slot page counts — same op sequence, dtypes and
  masking, so results agree to the last ULPs (the fused [R, L] dot and
  the batched einsum may accumulate partial sums in different orders;
  greedy TOKEN identity is the hard bitwise contract, asserted
  end-to-end below);
- end-to-end greedy tokens under ``attention_kernel="pallas"`` equal the
  gather engine exactly with prefix cache + speculative decoding + KV
  tier restore all on (the full hot path through the kernels);
- programs compile once per (width, k) tier at warmup — no mid-traffic
  compiles under pallas;
- ``resolve_attention_backend`` picks gather off-TPU on auto, honors an
  explicit pallas (interpret mode — this file's whole execution story on
  CPU), raises for an explicit pallas on TPU-unfriendly shapes, and
  rejects unknown names;
- the backend and its dispatch/compile counters are exported through
  ``engine_stats()`` -> llm_server ``_EXPORTED_STATS`` -> controller
  ``_ENGINE_KEYS``.
"""

import functools
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.serve.llm import kv_cache


# ---------------------------------------------------------------------------
# kernel-level bit-equivalence vs the gather math
# ---------------------------------------------------------------------------


def _rand_pool(key, hkv, pool_pages, page, d, dtype):
    kk, kv_ = jax.random.split(key)
    k_pages = jax.random.normal(kk, (hkv, pool_pages, page, d), dtype)
    v_pages = jax.random.normal(kv_, (hkv, pool_pages, page, d), dtype)
    return k_pages, v_pages


def _ref_attention(q, k_pages, v_pages, page_tables, base, limit, sm,
                   block_len=1):
    """The gather path's exact op sequence (see kv_cache._attend
    / paged_verify_step), generalized to the kernel's unified semantics:
    row t of slot b attends keys ``col <= base[b] + t`` (with positions cut
    into blocks of ``block_len``: up to the end of its own block) and
    ``col < limit[b]``."""
    b, t, h, d = q.shape
    hkv = k_pages.shape[0]
    n_rep = h // hkv
    page = k_pages.shape[2]
    max_len = page_tables.shape[1] * page
    k_seq = jnp.moveaxis(jnp.take(k_pages, page_tables, axis=1),
                         0, 3).reshape(b, max_len, hkv, d)
    v_seq = jnp.moveaxis(jnp.take(v_pages, page_tables, axis=1),
                         0, 3).reshape(b, max_len, hkv, d)
    k_full = kv_cache.gqa_expand(k_seq, n_rep)
    v_full = kv_cache.gqa_expand(v_seq, n_rep)
    col = jnp.arange(max_len)
    pos = base[:, None] + jnp.arange(t)[None, :]                  # [B,T]
    seen = (pos // block_len + 1) * block_len
    valid = (col[None, None, :] < seen[:, :, None]) \
        & (col[None, None, :] < limit[:, None, None])             # [B,T,L]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_full).astype(
        jnp.float32) * sm
    logits = jnp.where(valid[:, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v_full)


@pytest.fixture
def a_head_a_step(monkeypatch):
    """A scratch budget no KV head fits: every walking call then walks ONE
    KV head (or pool row) a grid step, the least the rule gives. The call's
    ``jit`` does not see the budget, so what it traced under the other
    budget is dropped, before and after."""
    monkeypatch.setattr(paged_ops, "_WALK_KV_BYTES", 1)
    paged_ops._gqa_walk_call.clear_cache()
    yield
    paged_ops._gqa_walk_call.clear_cache()


def _grid_steps(fn, *args) -> list:
    """The grid of every kernel in ``fn``'s program."""
    import re
    return [int(n) for n in re.findall(r"grid=\((\d+),?\)",
                                       str(jax.make_jaxpr(fn)(*args)))]


def _assert_matches(got, want):
    """Same dtype, same values to the last ULPs. Contraction accumulation
    order is the only permitted difference (fused [R, L] dot vs batched
    einsum), so tolerances are a few ULPs of the output dtype — any
    masking, scaling or dtype divergence blows well past them."""
    assert got.dtype == want.dtype
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    tol = 1e-5 if got.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(g, w, rtol=tol, atol=tol)


@pytest.mark.parametrize("b,t", [(1, 1), (4, 1), (2, 2), (4, 4), (3, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_gather_across_width_and_span(b, t, dtype):
    """(width, k) tier sweep: decode is t=1, verify is t=k+1. Ragged
    positions per slot (different live page counts) and a permuted page
    table — outputs must match the gather math."""
    hkv, n_rep, d, page, mp = 2, 2, 16, 8, 4
    h = hkv * n_rep
    key = jax.random.PRNGKey(b * 131 + t)
    kq, kp, kt = jax.random.split(key, 3)
    k_pages, v_pages = _rand_pool(kp, hkv, mp * b + 1, page, d, dtype)
    q = jax.random.normal(kq, (b, t, h, d), dtype)
    # ragged: slot i's span ends at a different depth into its pages
    base = jnp.asarray([(page * (i % mp)) + (i * 3) % page
                        for i in range(b)], jnp.int32)
    page_tables = jax.random.permutation(
        kt, mp * b) .reshape(b, mp).astype(jnp.int32) + 1
    limit = jnp.full((b,), mp * page, jnp.int32)
    sm = d ** -0.5

    got = paged_ops.paged_attention(q, k_pages, v_pages, page_tables,
                                    base, sm_scale=sm)
    want = _ref_attention(q, k_pages, v_pages, page_tables, base, limit,
                          sm)
    _assert_matches(got, want)


def test_decode_wrapper_matches_decode_attention_integration():
    """The integration point the engine actually calls: gather vs pallas
    through kv_cache._attend, under decode's geometry, must agree."""
    hkv, h, d, page, mp, b = 2, 4, 16, 8, 4, 4
    key = jax.random.PRNGKey(0)
    k_pages, v_pages = _rand_pool(key, hkv, mp * b + 1, page, d,
                                  jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, d), jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(
        jnp.int32)
    pos = jnp.asarray([0, 7, 13, 30], jnp.int32)
    # the integration point takes the whole layer-indexed pool: the layer
    # under test sits between two layers of other values
    k_pool = jnp.stack([k_pages + 1.0, k_pages, k_pages - 1.0])
    v_pool = jnp.stack([v_pages - 1.0, v_pages, v_pages + 1.0])
    layer = jnp.int32(1)
    gather, pallas = (kv_cache._attend(
        q[:, None], k_pool, v_pool, layer, kv_cache._Geometry(
            lone=1, attn_backend=backend, kernel="paged_decode_attention",
            operands=(page_tables, pos),
            cfg=types.SimpleNamespace(head_dim=d)))
        for backend in ("gather", "pallas"))
    _assert_matches(pallas, gather)
    _assert_matches(gather, _ref_attention(
        q[:, None], k_pages, v_pages, page_tables, pos,
        jnp.full((b,), mp * page, jnp.int32), d ** -0.5)[:, 0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("call", ["decode", "chunk"])
def test_five_query_heads_a_kv_head_through_the_wrappers(call, dtype):
    """ISSUE 60: 20 query heads of 128 on 4 KV heads, the first group that
    is no power of two (the wrappers pad a KV head's query rows to whole
    sublane tiles): decode and a chunk against the gather math."""
    hkv, n_rep, d, page, mp, b = 4, 5, 128, 8, 4, 3
    h = hkv * n_rep
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(60), hkv, mp * b + 1,
                                  page, d, dtype)
    sm = d ** -0.5
    if call == "decode":
        q = jax.random.normal(jax.random.PRNGKey(61), (b, h, d), dtype)
        tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
        pos = jnp.asarray([0, 13, 30], jnp.int32)
        got = paged_ops.paged_decode_attention(q, k_pages, v_pages, tables,
                                               pos, sm_scale=sm)[:, None]
        want = _ref_attention(q[:, None], k_pages, v_pages, tables, pos,
                              jnp.full((b,), mp * page, jnp.int32), sm)
    else:
        q = jax.random.normal(jax.random.PRNGKey(62), (1, 16, h, d), dtype)
        table = jnp.arange(1, mp + 1, dtype=jnp.int32)
        start, true_len = 8, 21
        got = paged_ops.paged_chunk_attention(
            q, k_pages, v_pages, table, jnp.int32(start),
            jnp.int32(true_len), sm_scale=sm)
        want = _ref_attention(
            q, k_pages, v_pages, table[None],
            jnp.asarray([start], jnp.int32),
            jnp.asarray([true_len], jnp.int32), sm)
    _assert_matches(got, want)


def test_chunk_kernel_masks_padded_tail():
    """Chunked prefill: limit=true_len must hide the padded tail pages —
    same result as the gather reference with the same bound, and NOT the
    same as an unbounded kernel when padding exists."""
    hkv, n_rep, d, page, mp = 2, 2, 16, 8, 4
    h = hkv * n_rep
    c = 16                  # bucket-padded chunk: rows past the prompt
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(2), hkv, mp + 1,
                                  page, d, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, c, h, d),
                          jnp.float32)
    page_table = jnp.arange(1, mp + 1, dtype=jnp.int32)
    # prompt ends at 19: rows 0..10 are real, 11..15 are padding whose
    # causal mask would otherwise see keys past the prompt
    start, true_len = 8, 19
    got = paged_ops.paged_chunk_attention(
        q, k_pages, v_pages, page_table,
        jnp.int32(start), jnp.int32(true_len), sm_scale=d ** -0.5)
    want = _ref_attention(
        q, k_pages, v_pages, page_table[None],
        jnp.asarray([start], jnp.int32), jnp.asarray([true_len], jnp.int32),
        d ** -0.5)
    _assert_matches(got, want)
    unbounded = paged_ops.paged_chunk_attention(
        q, k_pages, v_pages, page_table,
        jnp.int32(start), jnp.int32(mp * page), sm_scale=d ** -0.5)
    assert not np.array_equal(np.asarray(got), np.asarray(unbounded))


def test_kernel_matches_gather_under_jit():
    """Same contract inside jit — how the engine's compiled step programs
    run the kernel."""
    hkv, n_rep, d, page, mp, b, t = 2, 2, 16, 8, 4, 2, 3
    h = hkv * n_rep
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(5), hkv, mp * b + 1,
                                  page, d, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(6), (b, t, h, d), jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
    base = jnp.asarray([5, 17], jnp.int32)
    limit = jnp.full((b,), mp * page, jnp.int32)
    sm = d ** -0.5
    got = jax.jit(lambda *a: paged_ops.paged_attention(*a, sm_scale=sm))(
        q, k_pages, v_pages, page_tables, base)
    want = _ref_attention(q, k_pages, v_pages, page_tables, base, limit, sm)
    _assert_matches(got, want)


# ---------------------------------------------------------------------------
# the latent body: its work follows each slot's live length (ISSUE 45)
# ---------------------------------------------------------------------------

_LAT = dict(page=128, pages=6, latent=40, value=32, sm=24 ** -0.5)


def _latent_pools(key, slots):
    """A latent pool [1, 1, P, 128, 128] (rows of 40 numbers, padded with
    zeros) in which every slot owns ``pages`` pages of its own, and the
    slots' tables."""
    pages, page, latent = _LAT["pages"], _LAT["page"], _LAT["latent"]
    rows = jax.random.normal(key, (1, 1, slots * pages + 1, page, latent))
    pool = jnp.pad(rows, ((0, 0),) * 4 + ((0, 128 - latent),))
    tables = 1 + jnp.arange(slots * pages, dtype=jnp.int32).reshape(
        slots, pages)
    return pool, tables


def _nan_outside(pool, tables, live, axis=2):
    """``pool`` (its pages on ``axis``) with NaN in every page that is not
    among the first ceil(live / page) of its slot's table (the trash page
    0 too)."""
    keep = np.zeros(pool.shape[axis], bool)
    for row, n in zip(np.asarray(tables), live):
        keep[row[:-(-int(n) // _LAT["page"])]] = True
    shape = [1] * pool.ndim
    shape[axis] = -1
    return jnp.where(keep.reshape(shape), pool, jnp.nan)


def _latent_reference(q, pool, tables, base, limit):
    col = jnp.arange(tables.shape[1] * _LAT["page"])
    pos = base[:, None] + jnp.arange(q.shape[1])[None, :]         # [B, T]
    valid = (col <= pos[..., None]) & (col < limit[:, None, None])
    return kv_cache._latent_gather_attention(
        q, pool, 0, tables, valid[:, None], _LAT["sm"], _LAT["value"])


def _ragged_batch(t):
    """Slots of 1, 127, 128, 129 tokens, a full table, nothing, and 300
    in ONE batch, a span of ``t`` positions ending each."""
    lens = np.array([1, 127, 128, 129, 6 * 128, 0, 300])
    kq, kp = jax.random.split(jax.random.PRNGKey(45 + t))
    pool, tables = _latent_pools(kp, len(lens))
    q = jax.random.normal(kq, (len(lens), t, 4, _LAT["latent"]))
    limit = jnp.asarray(lens, jnp.int32)
    base = jnp.maximum(limit - t, 0)
    run = lambda pool: paged_ops.paged_latent_attention(   # noqa: E731
        q, pool, tables, base, limit, 0, sm_scale=_LAT["sm"],
        value_lanes=_LAT["value"])
    return run, q, pool, tables, base, limit, lens


def _chunk_in_spans():
    """The chunk call cut into two spans of 64 positions on one table: a
    chunk of 128 at position 60 under a true length of 185 lives in two
    pages; span 0 ends in page 0. (Rows at or past the true length are
    padding: their output is not read.)"""
    c, h, start, true_len = 128, 32, 60, 185
    kq, kp = jax.random.split(jax.random.PRNGKey(7))
    pool, tables = _latent_pools(kp, 1)
    q = jax.random.normal(kq, (1, c, h, _LAT["latent"]))
    run = lambda pool: paged_ops.paged_chunk_attention(    # noqa: E731
        q, pool, None, tables[0], jnp.int32(start), jnp.int32(true_len), 0,
        sm_scale=_LAT["sm"], value_lanes=_LAT["value"])[:, :true_len - start]
    return (run, q[:, :true_len - start], pool, tables, jnp.array([start]),
            jnp.array([true_len]), np.array([true_len]))


@pytest.mark.parametrize("case", [
    lambda: _ragged_batch(1), lambda: _ragged_batch(4), _chunk_in_spans],
    ids=["decode", "verify", "chunk_in_spans"])
def test_latent_body_reads_no_dead_page_and_multiplies_no_dead_row(case):
    """On a pool whose pages outside every slot's live pages hold NaN the
    outputs are finite and the gather path's (which is given the clean
    pool: it reads every page), and a slot with nothing live writes
    zeros. A table of 6 pages is walked in chunks of 4, so the last chunk
    is cut short by the table too."""
    run, q, pool, tables, base, limit, lens = case()
    got = run(_nan_outside(pool, tables, lens))
    assert got.shape == q.shape[:3] + (_LAT["value"],)
    assert np.isfinite(np.asarray(got)).all()
    want = _latent_reference(q, pool, tables, base, limit)
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not np.asarray(got[~live]).any()


# ---------------------------------------------------------------------------
# the walking body for pools of K and V per head: its work follows each
# slot's live length (ISSUE 48). Only paged_block_attention routes to it;
# here it is driven at every call shape of the family.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_len", [1, 4])
@pytest.mark.parametrize("b,t", [(1, 1), (4, 1), (2, 2), (4, 4), (3, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_walking_body_matches_gather_across_width_and_span(b, t, dtype,
                                                           block_len):
    """The sweep of test_kernel_matches_gather_across_width_and_span under
    the causal and the block mask: ragged positions (different live page
    counts, spans that start inside a block) and a permuted page table. A
    table of 6 pages is walked in chunks of 4, so a slot deep into its
    table meets two chunks and the table cuts the last one short."""
    hkv, n_rep, d, page, mp = 2, 2, 16, 8, 6
    h = hkv * n_rep
    key = jax.random.PRNGKey(b * 131 + t)
    kq, kp, kt = jax.random.split(key, 3)
    k_pages, v_pages = _rand_pool(kp, hkv, mp * b + 1, page, d, dtype)
    q = jax.random.normal(kq, (b, t, h, d), dtype)
    base = jnp.asarray([(page * ((2 * i + 1) % mp)) + (i * 3) % page
                        for i in range(b)], jnp.int32)
    page_tables = jax.random.permutation(
        kt, mp * b) .reshape(b, mp).astype(jnp.int32) + 1
    limit = jnp.full((b,), mp * page, jnp.int32)
    sm = d ** -0.5

    got = paged_ops.paged_attention(q, k_pages, v_pages, page_tables, base,
                                    sm_scale=sm, block_len=block_len,
                                    walk=True)
    want = _ref_attention(q, k_pages, v_pages, page_tables, base, limit,
                          sm, block_len)
    _assert_matches(got, want)


def test_walking_body_under_jit_with_a_traced_layer():
    """How a block program calls it: inside jit, the layer a traced
    operand, on a pool of several layers of other values."""
    hkv, n_rep, d, page, mp, b, t = 2, 2, 16, 8, 4, 2, 4
    h = hkv * n_rep
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(5), hkv, mp * b + 1,
                                  page, d, jnp.float32)
    k_pool = jnp.stack([k_pages + 1.0, k_pages, k_pages - 1.0])
    v_pool = jnp.stack([v_pages - 1.0, v_pages, v_pages + 1.0])
    q = jax.random.normal(jax.random.PRNGKey(6), (b, t, h, d), jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
    base = jnp.asarray([4, 16], jnp.int32)
    sm = d ** -0.5
    got = jax.jit(lambda layer, *a: paged_ops.paged_block_attention(
        *a, layer, block_len=4, sm_scale=sm))(
        jnp.int32(1), q, k_pool, v_pool, page_tables, base)
    want = _ref_attention(q, k_pages, v_pages, page_tables, base,
                          jnp.full((b,), mp * page, jnp.int32), sm, 4)
    _assert_matches(got, want)


def test_walking_body_on_heads_of_64_packed_two_to_a_row():
    """Heads of 64 lie two to a 128-lane pool row (_packed_heads): the
    walking body runs on the rows as they are, against the gather math on
    the same heads a row each."""
    hkv, n_rep, d, page, mp, b, t = 4, 2, 64, 8, 6, 3, 4
    h = hkv * n_rep
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(8), hkv, mp * b + 1,
                                  page, d, jnp.float32)
    pack = lambda pool: pool.reshape(hkv // 2, 2, -1, page, d).transpose(  # noqa: E731
        0, 2, 3, 1, 4).reshape(hkv // 2, -1, page, 2 * d)
    q = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, d), jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
    base = jnp.asarray([0, 20, 40], jnp.int32)
    sm = d ** -0.5
    got = paged_ops.paged_block_attention(
        q, pack(k_pages), pack(v_pages), page_tables, base, block_len=4,
        sm_scale=sm)
    want = _ref_attention(q, k_pages, v_pages, page_tables, base,
                          jnp.full((b,), mp * page, jnp.int32), sm, 4)
    _assert_matches(got, want)


def test_walking_body_on_per_kv_head_shards_of_a_tensor_mesh():
    """Under ``shard_map`` with :func:`tp_shard_specs` (how a TP engine
    runs every kernel of the family): each shard's call is a single-chip
    call over its own KV heads, which the body reads off its operands'
    shapes."""
    from jax.sharding import Mesh

    hkv, n_rep, d, page, mp, b, t = 4, 2, 16, 8, 6, 3, 4
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(11), hkv, mp * b + 1,
                                  page, d, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(12), (b, t, hkv * n_rep, d),
                          jnp.float32)
    page_tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
    base = jnp.asarray([0, 20, 44], jnp.int32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tensor",))
    got = kv_cache._attend(
        q, k_pages[None], v_pages[None], jnp.int32(0), kv_cache._Geometry(
            attn_backend="pallas", kernel="paged_block_attention",
            static={"block_len": 4}, operands=(page_tables, base),
            cfg=types.SimpleNamespace(head_dim=d), mesh=mesh))
    want = _ref_attention(q, k_pages, v_pages, page_tables, base,
                          jnp.full((b,), mp * page, jnp.int32), d ** -0.5, 4)
    _assert_matches(got, want)


def test_walking_body_on_a_chunk_with_limit_below_the_table():
    """A chunk-shaped call (one slot, ``limit`` = the prompt's length,
    below the table's span): the padded rows' keys past the prompt stay
    masked and the pages past the prompt are not walked."""
    hkv, n_rep, d, page, mp = 2, 2, 16, 8, 6
    h = hkv * n_rep
    c, start, true_len = 16, 8, 19
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(2), hkv, mp + 1,
                                  page, d, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, c, h, d), jnp.float32)
    page_table = jnp.arange(1, mp + 1, dtype=jnp.int32)
    base = jnp.asarray([start], jnp.int32)
    limit = jnp.asarray([true_len], jnp.int32)
    dead = jnp.arange(mp + 1) > -(-true_len // page)      # past page 3
    for block_len in (1, 4):
        got = paged_ops.paged_attention(
            q, jnp.where(dead[None, :, None, None], jnp.nan, k_pages),
            jnp.where(dead[None, :, None, None], jnp.nan, v_pages),
            page_table[None], base, limit, sm_scale=d ** -0.5,
            name="paged_chunk_attention", block_len=block_len, walk=True)
        want = _ref_attention(q, k_pages, v_pages, page_table[None], base,
                              limit, d ** -0.5, block_len)
        _assert_matches(got, want)


def _ragged_heads_batch(t, block_len):
    """:func:`_ragged_batch` on pools of K and V per head (2 KV heads of
    16, 2 query heads each, pages of 128, tables of 6)."""
    lens = np.array([1, 127, 128, 129, 6 * 128, 0, 300])
    page, mp, hkv, n_rep, d = _LAT["page"], 6, 2, 2, 16
    kq, kp = jax.random.split(jax.random.PRNGKey(48 + t))
    k_pages, v_pages = _rand_pool(kp, hkv, len(lens) * mp + 1, page, d,
                                  jnp.float32)
    tables = 1 + jnp.arange(len(lens) * mp, dtype=jnp.int32).reshape(
        len(lens), mp)
    q = jax.random.normal(kq, (len(lens), t, hkv * n_rep, d))
    limit = jnp.asarray(lens, jnp.int32)
    base = jnp.maximum(limit - t, 0)
    got = paged_ops.paged_attention(
        q, _nan_outside(k_pages, tables, lens, axis=1),
        _nan_outside(v_pages, tables, lens, axis=1), tables, base, limit,
        sm_scale=d ** -0.5, block_len=block_len, walk=True)
    want = _ref_attention(q, k_pages, v_pages, tables, base, limit,
                          d ** -0.5, block_len)
    return got, want, lens


@pytest.mark.parametrize("t,block_len", [(1, 1), (4, 4), (8, 4)],
                         ids=["decode", "block", "two_blocks"])
def test_walking_body_reads_no_dead_page_and_multiplies_no_dead_row(
        t, block_len):
    """The ragged batch of the latent body's test on pools of K and V
    whose pages outside every slot's live pages hold NaN (the trash page
    too): the outputs are finite and the gather path's (which is given the
    clean pools: it reads every page), and a slot with nothing live
    writes zeros."""
    got, want, lens = _ragged_heads_batch(t, block_len)
    assert np.isfinite(np.asarray(got)).all()
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert not np.asarray(got[~live]).any()


def test_the_decode_verify_and_block_wrappers_walk_on_pools_of_k_and_v():
    """The adoption is by the call's kind, from ONE table (ISSUE 61: the
    decode and verify calls beside the block call): their wrappers'
    programs hold the walking body's copies, the chunk wrapper's, the grid
    body's ONE caller left, does not; and what an engine reports as
    ``attn_walks_live`` is that table read for its cache spec."""
    hkv, d, page, mp, b = 2, 16, 8, 4, 2
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(0), hkv, mp * b + 1,
                                  page, d, jnp.float32)
    q = jnp.zeros((b, 4, 2 * hkv, d), jnp.float32)
    tables = jnp.arange(1, mp * b + 1).reshape(b, mp).astype(jnp.int32)
    lens = jnp.asarray([4, 8], jnp.int32)

    def walks(fn, *a, **kw):
        return "dma_start" in str(jax.make_jaxpr(
            lambda *a: fn(*a, **kw))(*a))

    assert walks(paged_ops.paged_block_attention, q, k_pages, v_pages,
                 tables, lens, block_len=4)
    assert walks(paged_ops.paged_verify_attention, q, k_pages, v_pages,
                 tables, lens)
    assert walks(paged_ops.paged_decode_attention, q[:, 0], k_pages,
                 v_pages, tables, lens)
    assert not walks(paged_ops.paged_chunk_attention, q[:1], k_pages,
                     v_pages, tables[0], lens[0], lens[1], block_len=4)
    assert paged_ops.WALKS_LIVE["heads"] == ("decode", "verify", "block")
    assert paged_ops.walking_calls(latent=False, block_len=4) == ["block"]
    assert paged_ops.walking_calls(latent=False) == ["decode", "verify"]
    assert paged_ops.walking_calls(latent=True) == [
        "decode", "verify", "chunk"]


# ---------------------------------------------------------------------------
# the decode and verify calls of a block without window layers walk and
# write (ISSUE 61): the dense block's, LFM2's heads of 64, Falcon-H1's five
# query heads a KV head
# ---------------------------------------------------------------------------


def _serving_call(kind, n_rep, packed=False, dtype=jnp.float32):
    """A decode call (one row a slot) or a verify call (three) as the
    serving programs make it, on pools [2 layers] of 2 KV heads (``packed``:
    4 heads of half the lanes, two to a pool row) with ``n_rep`` query
    heads each, tables of 6 pages (two chunks of the walk): a slot in its
    first page, one whose span ends a page (decode) or crosses into the
    next (verify), an IDLE slot (a table of zeros at length 0: its rows go
    to the trash page from their own place and it reads nothing else) and
    one in the walk's second chunk. Every page of both layers that is not
    live for its slot, nor the trash page, holds NaN.
    Returns (call(layer, k_pool, v_pool, rides) -> (read, k_pool, v_pool),
    the poisoned pools, the clean ones, what the reference needs)."""
    page = 16 if dtype == jnp.bfloat16 else 8
    t = 1 if kind == "decode" else 3
    hkv, pack, d, mp = (4, 2, 8, 6) if packed else (2, 1, 16, 6)
    rng = np.random.default_rng(61 + n_rep + t)
    base = np.asarray([5, 2 * page - 1 if t == 1 else 2 * page - 2, 0,
                       4 * page + 2], np.int32)
    b = len(base)
    tables = 1 + rng.permutation(b * mp).reshape(b, mp).astype(np.int32)
    tables[2] = 0
    pools = [jnp.asarray(rng.normal(size=(
        2, hkv // pack, 1 + b * mp, page, d * pack)), dtype)
        for _ in "kv"]
    live = np.zeros(1 + b * mp, bool)
    live[0] = True
    for row, n in zip(tables, base + t):
        live[row[:-(-int(n) // page)]] = True
    poisoned = [jnp.where(live[None, None, :, None, None], pool, jnp.nan)
                for pool in pools]
    q = jnp.asarray(rng.normal(size=(b, t, hkv * n_rep, d)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, t, hkv, d)), dtype)
                    for _ in "kv")
    pos = base[:, None] + np.arange(t)[None]
    page_idx = jnp.asarray(np.take_along_axis(tables, pos // page, axis=1))
    offset = jnp.asarray(pos % page)
    wrapper = paged_ops.paged_decode_attention if t == 1 \
        else paged_ops.paged_verify_attention
    drop = (lambda a: a[:, 0]) if t == 1 else (lambda a: a)

    @functools.partial(jax.jit, static_argnames="rides")
    def call(layer, k_pool, v_pool, rides=True):
        """The rows riding in, or scattered first (the parent's order)."""
        if rides:
            return wrapper(drop(q), k_pool, v_pool, jnp.asarray(tables),
                           jnp.asarray(base), layer, sm_scale=d ** -0.5,
                           write=(drop(k_new), drop(v_new), drop(page_idx)))
        k_pool, v_pool = kv_cache._write_token_kv(
            k_pool, v_pool, layer, drop(k_new), drop(v_new), drop(page_idx),
            drop(offset))
        return k_pool, v_pool

    def reference(k_set, v_set, layer=1):
        """The gather path on the layer's pools, a head a row."""
        heads = [jnp.moveaxis(pool[layer].reshape(
            hkv // pack, 1 + b * mp, page, pack, d), 3, 1).reshape(
                hkv, 1 + b * mp, page, d) for pool in (k_set, v_set)]
        return _ref_attention(q, *heads, jnp.asarray(tables),
                              jnp.asarray(base),
                              jnp.full((b,), mp * page, jnp.int32),
                              d ** -0.5)

    return call, poisoned, pools, (reference, drop, base > 0)


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["heads_at_once", "a_head_a_step"])
@pytest.mark.parametrize("packed", [False, True], ids=["heads", "packed"])
@pytest.mark.parametrize("n_rep", [4, 5, 8])
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_decode_and_verify_calls_walk_live_pages_and_write_their_rows(
        kind, n_rep, packed, grouped, request):
    """The wrappers the serving programs call, under jit with a TRACED
    layer, at 4 (the dense block's, LFM2's), 5 (Falcon-H1's) and 8 query
    heads a KV head, on heads a row and on heads of half a row packed two
    to a row: the read is the gather path's on the scattered pools (which
    is given the clean ones: it reads every page) and finite, so no dead
    page was read and no dead row multiplied; the rows that rode in are the
    scatter's bytes on every page of both layers but the trash page, which
    stays finite. ``a_head_a_step``: a table too wide for every KV head's
    pages to fit the scratch (here: a budget no head fits; on the chip a
    dense block's 8 heads of 128 past 4,096 positions): a block WITHOUT
    window layers then walks a group of KV heads a grid step too, by the
    rule of one with them: two grid steps a slot, the same read and bytes."""
    if grouped:
        request.getfixturevalue("a_head_a_step")
    call, poisoned, pools, (reference, drop, live) = _serving_call(
        kind, n_rep, packed)
    assert _grid_steps(lambda *pools: call(jnp.int32(1), *pools),
                       *poisoned) == [(1 + grouped) * len(live)]
    read, k_pool, v_pool = call(jnp.int32(1), *poisoned)
    want = drop(reference(*call(jnp.int32(1), *pools, rides=False)))
    assert np.isfinite(np.asarray(read)[live]).all()
    np.testing.assert_allclose(np.asarray(read)[live],
                               np.asarray(want)[live], atol=2e-5)
    for got, scattered in zip((k_pool, v_pool), call(
            jnp.int32(1), *poisoned, rides=False)):
        got, scattered = np.asarray(got), np.asarray(scattered)
        assert np.array_equal(got[:, :, 1:], scattered[:, :, 1:],
                              equal_nan=True)
        assert np.isfinite(got[:, :, 0]).all()


def test_a_walk_one_kv_head_of_which_passes_the_scratch_is_refused():
    """Not the interpreter's concern, which has no VMEM: the call as the
    chip's compiler would get it (``interpret=False``, traced and never
    lowered). A dense block's decode call at a table of 32,768 positions
    walks one head a step; at 131,072 one head's pages alone pass the limit
    and the call says so, where the compiler would have run out of VMEM."""
    def steps(table):
        q = jax.ShapeDtypeStruct((4, 32, 128), jnp.bfloat16)
        pool = jax.ShapeDtypeStruct((2, 8, 9, 128, 128), jnp.bfloat16)
        return _grid_steps(
            lambda q, k, v: paged_ops.paged_decode_attention(
                q, k, v, jnp.zeros((4, table // 128), jnp.int32),
                jnp.zeros((4,), jnp.int32), 1, interpret=False),
            q, pool, pool)

    assert steps(2048) == [4] and steps(4096) == [4]
    assert steps(16384) == [4 * 4] and steps(32768) == [4 * 8]
    with pytest.raises(ValueError, match="VMEM.*max_seq_len"):
        steps(131072)


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_decode_and_verify_calls_write_tiles_of_a_packed_type(kind):
    """bf16 pools, whose native tile is 16 rows (pages of 16): the same
    bytes as the scatter's, read and pools."""
    call, _, pools, (reference, drop, live) = _serving_call(
        kind, 4, dtype=jnp.bfloat16)
    read, k_pool, v_pool = call(jnp.int32(1), *pools)
    k_set, v_set = call(jnp.int32(1), *pools, rides=False)
    _assert_matches(read[live], drop(reference(k_set, v_set))[live])
    for got, scattered in ((k_pool, k_set), (v_pool, v_set)):
        assert (np.asarray(got, np.float32)[:, :, 1:]
                == np.asarray(scattered, np.float32)[:, :, 1:]).all()


# ---------------------------------------------------------------------------
# the lower edge (ISSUE 52): a window layer's read walks its slot's RING
# table from the page of the first query's oldest visible key
# ---------------------------------------------------------------------------


def _ring_case(base, t, limit, window, page, ring, seed=0, hkv=2, n_rep=2,
               d=16, dv=None, k_lanes=None, sink=False):
    """A ring pool laid out as the programs lay it (position p in entry
    ``(p // page) % ring`` of its slot's table, a later position over an
    earlier one), from a dense truth [B, L, Hkv, d]; ring entries whose
    page lies wholly below the walk's first page hold NaN (the walk must
    not copy them). Returns (q, k_pool, v_pool, tables, want): ``want`` the
    band's softmax over the truth, in numpy. ``dv`` / ``k_lanes`` (ISSUE
    55): value rows of ``dv`` lanes, key rows of ``d`` numbers stored on
    ``k_lanes`` (zeros behind them, as q's); ``sink``: a logit a query
    head in the softmax's denominator, returned last."""
    dv, k_lanes = dv or d, k_lanes or d
    b, cap = len(base), ring * page
    rng = np.random.default_rng(seed)
    top = max(base) + t
    truth = [np.pad(rng.normal(size=(b, top, hkv, w)).astype(np.float32),
                    ((0, 0),) * 3 + ((0, lanes - w),))
             for w, lanes in ((d, k_lanes), (dv, dv))]
    q = np.pad(rng.normal(size=(b, t, hkv * n_rep, d)).astype(np.float32),
               ((0, 0),) * 3 + ((0, k_lanes - d),))
    sinks = rng.normal(size=(hkv * n_rep,)).astype(np.float32) + 1.0
    pools = [np.zeros((hkv, 1 + b * ring, page, w), np.float32)
             for w in (k_lanes, dv)]
    tables = 1 + np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    want = np.zeros((b, t, hkv * n_rep, dv), np.float32)
    for s in range(b):
        hi = min(base[s] + t, limit[s]) - 1          # the last row written
        first = max(0, base[s] - window + 1) // page
        for entry in range(ring):
            # the newest page the entry holds: the largest j <= hi's page
            # with j % ring == entry
            j = hi // page - (hi // page - entry) % ring
            if j < 0:
                continue                    # never written: zeros
            if j < first:
                for pool in pools:
                    pool[:, tables[s, entry]] = np.nan
                continue
            for off in range(page):
                at = j * page + off
                if at > hi:                 # stale: the page a ring before
                    at -= cap
                if at >= 0:
                    for pool, rows in zip(pools, truth):
                        pool[:, tables[s, entry], off] = rows[s, at]
        for i in range(t):
            at = base[s] + i
            keys = [j for j in range(max(0, at - window + 1), at + 1)
                    if j < limit[s]]
            if not keys:
                continue
            for head in range(hkv * n_rep):
                sc = truth[0][s, keys, head // n_rep] @ q[s, i, head] \
                    * d ** -0.5
                top_sc = max(sc.max(), sinks[head]) if sink else sc.max()
                w = np.exp(sc - top_sc)
                total = w.sum() + (np.exp(sinks[head] - top_sc) if sink
                                   else 0.0)
                want[s, i, head] = (w / total) \
                    @ truth[1][s, keys, head // n_rep]
    out = (jnp.asarray(q), jnp.asarray(pools[0]), jnp.asarray(pools[1]),
           jnp.asarray(tables), want)
    return out + (jnp.asarray(sinks),) if sink else out


@pytest.mark.parametrize("groups", [False, True], ids=["heads_at_once",
                                                       "a_head_a_step"])
def test_decode_with_a_lower_edge_reads_the_band_off_the_ring(groups,
                                                              request):
    """Window 16, pages of 8, rings of 5: positions inside the first
    window (3), past it (37), past the ring's wrap (70, 200: entries
    written again and again) and on a page's first offset (40). With the
    scratch too small for both KV heads the same walk a head a grid step."""
    if groups:
        request.getfixturevalue("a_head_a_step")
    pos = [3, 37, 40, 70, 200]
    q, k_pool, v_pool, tables, want = _ring_case(
        pos, 1, [10 ** 6] * len(pos), 16, 8, 5)
    got = paged_ops.paged_decode_attention(
        q[:, 0], k_pool, v_pool, tables, jnp.asarray(pos, jnp.int32),
        window=16)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want[:, 0], atol=2e-5)


@pytest.mark.parametrize("start,true_len", [(0, 16), (24, 40), (120, 131),
                                            (48, 64)])
def test_chunk_with_a_lower_edge_reads_the_band_off_the_ring(start, true_len):
    """A chunk of 16 rows under a window of 16 on a ring of 5 pages of 8
    (window + chunk + a page): its first, one past the window, one past
    the ring's wrap whose padded tail lies beyond ``true_len`` (rows there
    see nothing of their own), one that starts on a ring's first entry."""
    q, k_pool, v_pool, tables, want = _ring_case(
        [start], 16, [true_len], 16, 8, 5, seed=start)
    got = paged_ops.paged_chunk_attention(
        q, k_pool, v_pool, tables[0], jnp.int32(start), jnp.int32(true_len),
        window=16)
    real = true_len - start
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got[0, :real], want[0, :real], atol=2e-5)


@pytest.mark.parametrize("t", [1, 5])
def test_a_full_layer_of_a_windowed_block_walks_from_zero(t):
    """``window=0``: the same values as the walking body without an edge
    and as the gather math, on the growing table."""
    hkv, n_rep, d, page, mp, b = 2, 2, 16, 8, 6, 3
    kq, kp = jax.random.split(jax.random.PRNGKey(t))
    k_pages, v_pages = _rand_pool(kp, hkv, mp * b + 1, page, d, jnp.float32)
    q = jax.random.normal(kq, (b, t, hkv * n_rep, d), jnp.float32)
    base = jnp.asarray([2, 17, 40], jnp.int32)
    tables = 1 + jnp.arange(mp * b, dtype=jnp.int32).reshape(b, mp)
    limit = jnp.full((b,), mp * page, jnp.int32)
    got = paged_ops.paged_attention(q, k_pages, v_pages, tables, base,
                                    window=0)
    _assert_matches(got, paged_ops.paged_attention(
        q, k_pages, v_pages, tables, base, walk=True))
    _assert_matches(got, _ref_attention(q, k_pages, v_pages, tables, base,
                                        limit, d ** -0.5))


def test_the_wrappers_of_a_windowed_block_walk_and_a_short_ring_is_refused():
    hkv, d, page, b = 2, 16, 8, 2
    k_pages, v_pages = _rand_pool(jax.random.PRNGKey(0), hkv, 5 * b + 1,
                                  page, d, jnp.float32)
    q = jnp.zeros((b, 16, 2 * hkv, d), jnp.float32)
    tables = jnp.arange(1, 5 * b + 1).reshape(b, 5).astype(jnp.int32)
    lens = jnp.asarray([4, 8], jnp.int32)

    def walks(fn, *a, **kw):
        return "dma_start" in str(jax.make_jaxpr(
            lambda *a: fn(*a, **kw))(*a))

    for window in (0, 16):
        assert walks(paged_ops.paged_decode_attention, q[:, 0], k_pages,
                     v_pages, tables, lens, window=window)
        assert walks(paged_ops.paged_chunk_attention, q[:1], k_pages,
                     v_pages, tables[0], lens[0], lens[1], window=window)
    assert paged_ops.walking_calls(latent=False, windowed=True) == [
        "decode", "chunk"]
    # 16 rows under a window of 16 walk 5 pages of 8; a ring of 4 is short
    with pytest.raises(ValueError, match="the ring table holds 4"):
        paged_ops.paged_chunk_attention(
            q[:1], k_pages, v_pages, tables[0, :4], lens[0], lens[1],
            window=16)


# ---------------------------------------------------------------------------
# key rows wider than value rows, a KV-head count a layer kind, a sink, a
# window of ONE page (ISSUE 55)
# ---------------------------------------------------------------------------

# (KV heads, query rows a KV head): a full layer's 4 x 16, a window layer's
# 8 x 8; keys of 24 numbers on rows of 32 lanes beside values of 16
_MIXED = dict(d=24, k_lanes=32, dv=16)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("hkv,n_rep,window", [(4, 16, 0), (8, 8, 8)],
                         ids=["full_16_rows", "one_page_window_8_rows"])
def test_decode_on_pools_of_two_widths(hkv, n_rep, window, sink):
    """One token a slot on key rows of 32 lanes (24 numbers, zeros behind
    them) and value rows of 16: a full layer's read from 0 (a "ring" long
    enough never to wrap) and a window layer's, whose window is exactly one
    page of 8 (a ring of 3: two pages walked a slot), at positions inside
    the first window, on a page's first and last offset and past many
    wraps; with and without a sink a query head."""
    pos = [3, 8, 15, 37, 200] if window else [3, 8, 15, 37, 60]
    ring = 3 if window else 8
    q, k_pool, v_pool, tables, want, *sinks = _ring_case(
        pos, 1, [10 ** 6] * len(pos), window or 10 ** 6, 8, ring, seed=5,
        hkv=hkv, n_rep=n_rep, sink=sink, **_MIXED)
    got = paged_ops.paged_decode_attention(
        q[:, 0], k_pool, v_pool, tables, jnp.asarray(pos, jnp.int32),
        window=window, sm_scale=24 ** -0.5,
        **({"sink": sinks[0]} if sink else {}))
    assert got.shape == (len(pos), hkv * n_rep, 16)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want[:, 0], atol=2e-5)


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("start,true_len,window", [
    (0, 16, 8), (24, 40, 8), (120, 131, 8), (32, 48, 0)])
def test_chunk_on_pools_of_two_widths(start, true_len, window, sink):
    """A chunk of 16 rows on the same pools: under a window of one page on
    a ring of 4 (its first, one past the window, one past many wraps with
    a padded tail), and a full layer's from 0."""
    ring = 4 if window else 8
    q, k_pool, v_pool, tables, want, *sinks = _ring_case(
        [start], 16, [true_len], window or 10 ** 6, 8, ring, seed=start,
        hkv=8 if window else 4, n_rep=8 if window else 16, sink=sink,
        **_MIXED)
    got = paged_ops.paged_chunk_attention(
        q, k_pool, v_pool, tables[0], jnp.int32(start), jnp.int32(true_len),
        window=window, sm_scale=24 ** -0.5,
        **({"sink": sinks[0]} if sink else {}))
    real = true_len - start
    assert got.shape == (1, 16, 64, 16)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got[0, :real], want[0, :real], atol=2e-5)


@pytest.mark.parametrize("groups", [False, True], ids=["heads_at_once",
                                                       "a_head_a_step"])
def test_a_sink_follows_its_kv_heads_group(groups, request):
    """With the scratch too small for every KV head a step walks one, and
    reads ITS rows of the sinks."""
    if groups:
        request.getfixturevalue("a_head_a_step")
    pos = [5, 21, 90]
    q, k_pool, v_pool, tables, want, sinks = _ring_case(
        pos, 1, [10 ** 6] * 3, 8, 8, 3, seed=9, hkv=4, n_rep=4, sink=True,
        **_MIXED)
    got = paged_ops.paged_decode_attention(
        q[:, 0], k_pool, v_pool, tables, jnp.asarray(pos, jnp.int32),
        window=8, sm_scale=24 ** -0.5, sink=sinks)
    np.testing.assert_allclose(got, want[:, 0], atol=2e-5)


@pytest.mark.parametrize("lone", [1, 0], ids=["decode", "chunk"])
def test_the_kernels_sink_is_the_dense_softmaxs_extra_column(lone):
    """The walking body against kv_cache's ``_dense_attention`` (the gather
    backend's numerics: one more column in the softmax, dropped before the
    values) on the same pool, a full layer with a sink; and a sink of
    -1e30 is no sink."""
    hkv, n_rep, page, mp, b = 4, 4, 8, 6, 3 if lone else 1
    rng = np.random.default_rng(3)
    k_pages = jnp.asarray(rng.normal(size=(hkv, mp * b + 1, page, 32)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(hkv, mp * b + 1, page, 16)),
                          jnp.float32)
    t = 1 if lone else 16
    q = jnp.asarray(rng.normal(size=(b, t, hkv * n_rep, 32)), jnp.float32)
    sinks = jnp.asarray(rng.normal(size=(hkv * n_rep,)) + 2.0, jnp.float32)
    base = jnp.asarray([2, 17, 40][:b], jnp.int32)
    tables = 1 + jnp.arange(mp * b, dtype=jnp.int32).reshape(b, mp)
    kpos = jnp.arange(mp * page)
    valid = kpos[None, None, :] <= (base[:, None] + jnp.arange(t))[:, :, None]
    keys = kv_cache._gather_seq(k_pages[None], 0, tables)
    vals = kv_cache._gather_seq(v_pages[None], 0, tables)
    for sink in (sinks, jnp.full_like(sinks, -1e30), None):
        got = paged_ops.paged_attention(q, k_pages, v_pages, tables, base,
                                        window=0, sm_scale=0.2, sink=sink)
        want = kv_cache._dense_attention(
            q, keys, vals, valid[:, None], 0.2,
            None if sink is None else sink)
        np.testing.assert_allclose(got, want, atol=2e-5)
    plain = kv_cache._dense_attention(q, keys, vals, valid[:, None], 0.2)
    assert float(jnp.abs(plain - want).max()) < 1e-6        # -1e30 == none
    with_sink = kv_cache._dense_attention(q, keys, vals, valid[:, None], 0.2,
                                          sinks)
    assert float(jnp.abs(plain - with_sink).max()) > 1e-2


def test_only_the_walking_body_takes_a_sink_or_two_widths():
    k_pages = jnp.zeros((2, 5, 8, 32))
    q = jnp.zeros((1, 1, 4, 32))
    tables, base = jnp.ones((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="only the walking body"):
        paged_ops.paged_attention(q, k_pages, jnp.zeros((2, 5, 8, 16)),
                                  tables, base)
    with pytest.raises(ValueError, match="only the walking body"):
        paged_ops.paged_attention(q, k_pages, k_pages, tables, base,
                                  sink=jnp.zeros((4,)))


# ---------------------------------------------------------------------------
# the call's own rows ride in the walking body (ISSUE 53)
# ---------------------------------------------------------------------------


def _write_case(name, d=16, page=8, dtype=jnp.float32):
    """One call whose rows ride in: (wrapper, q, pools [2 layers], the
    wrapper's operands, its static keywords, k_new, v_new, page_idx,
    offset, whether some row was sent to the trash page from a place of its
    own). Every page of both layers of the pools holds values of its own;
    the call is of layer 1. ``d`` / ``page`` / ``dtype``: the CPU's sizes,
    or ones a TPU tiles (the builder's chip check)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    hkv, n_rep, pack, window, static = 2, 2, 1, None, {}
    dv, mixed = None, name.endswith("_mixed")
    if mixed:
        # ISSUE 55: value rows half as wide as key rows, 8 query rows a KV
        # head, a window of ONE page, a sink a query head
        name, hkv, n_rep, dv = name[:-len("_mixed")], 4, 8, d // 2
    call, lone, redirected = paged_ops.paged_block_attention, None, False
    if name in ("block", "two_blocks", "packed"):
        # blocks of 4: one ending its page, one in a table's last page,
        # an inactive slot (a table of zeros), one deep in its table
        mp, t = 6, 4 if name == "block" else 8
        base = [page - 4, mp * page - 4, 0, 3 * page + 4]
        static = {"block_len": 4}
        if name == "packed":
            hkv, pack, d = 4, 2, d // 2
        # two_blocks: the second block lies in the next page (slot 0) and
        # past the table (slot 1: the trash page, from a place of its own)
        redirected = t == 8
    elif name in ("decode", "decode_window", "decode_groups"):
        call, lone, t = paged_ops.paged_decode_attention, 1, 1
        window = 0 if name == "decode" else page if mixed else 2 * page
        mp = 6 if name == "decode" else 5
        base = [0, page - 1, 0, 4 * page + 3] if name == "decode" \
            else [3, 4 * page + 5, 0, 25 * page]
        static = {"window": window}
    else:
        # a chunk of two pages on a ring that wraps under it, ``true_len``
        # inside the chunk (its padding: the trash page)
        assert name in ("chunk_ring", "chunk_full"), name
        call, lone, t = paged_ops.paged_chunk_attention, 0, 2 * page
        window = (page if mixed else 2 * page) if name == "chunk_ring" \
            else 0
        mp = 5 if window else 16
        base, redirected = [9 * page], True
        static = {"window": window}
    b, dv = len(base), dv or d
    tables = 1 + rng.permutation(b * mp).reshape(b, mp).astype(np.int32)
    if b > 2:
        tables[2] = 0
    pools = [jnp.asarray(rng.normal(size=(
        2, hkv // pack, 1 + b * mp, page, w * pack)), dtype)
        for w in (d, dv)]
    q = jnp.asarray(rng.normal(size=(b, t, hkv * n_rep, d)), dtype)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, t, hkv, w)), dtype)
                    for w in (d, dv))
    if mixed:
        static["sink"] = jnp.asarray(
            rng.normal(size=(hkv * n_rep,)) + 1.0, jnp.float32)
    base = np.asarray(base, np.int32)
    pos = base[:, None] + np.arange(t)[None]
    entry = pos // page % mp if window else np.minimum(pos // page, mp - 1)
    page_idx = np.take_along_axis(tables, entry, axis=1)
    operands = (jnp.asarray(tables), jnp.asarray(base))
    if lone == 0:
        true_len = int(base[0]) + t - 3
        page_idx = np.where(pos < true_len, page_idx, 0)
        operands = (jnp.asarray(tables[0]), jnp.int32(base[0]),
                    jnp.int32(true_len))
    elif not window:
        page_idx = np.where(pos < mp * page, page_idx, 0)
    drop = (lambda a: a) if lone is None else (lambda a: a[0]) \
        if lone == 0 else (lambda a: a[:, 0])
    return (call, q if lone == 0 else drop(q), pools, operands, static,
            drop(k_new), drop(v_new), drop(jnp.asarray(page_idx, jnp.int32)),
            drop(jnp.asarray(pos % page, jnp.int32)), redirected)


def _scatter_then_walk(case, layer=1):
    """(what the call gives with its rows scattered first, with them riding
    in): each (read, k_pool, v_pool)."""
    call, q, (k_pool, v_pool), operands, static, k_new, v_new, page_idx, \
        offset, _ = case
    k_set, v_set = kv_cache._write_token_kv(k_pool, v_pool, layer, k_new,
                                            v_new, page_idx, offset)
    want = call(q, k_set, v_set, *operands, layer, **static)
    got = call(q, k_pool, v_pool, *operands, layer, **static,
               write=(k_new, v_new, page_idx))
    return (want, k_set, v_set), got


def _assert_same_bytes(case, want, got):
    """The read and both pools, every layer: EXACTLY. Only where the
    caller's rule sent a row to the trash page from a place of its own
    (which the kernel drops) is the trash page left out."""
    redirected = case[-1]
    for name, a, w in zip(("read", "k_pool", "v_pool"), got, want):
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        assert a.shape == w.shape, name
        if name != "read" and redirected:
            a, w = a[:, :, 1:], w[:, :, 1:]
        assert (a == w).all(), (name, np.argwhere(a != w)[:4])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", ["block", "two_blocks", "packed", "decode",
                                  "decode_window", "chunk_ring",
                                  "chunk_full", "decode_mixed",
                                  "decode_window_mixed", "chunk_ring_mixed",
                                  "chunk_full_mixed"])
def test_rows_that_ride_in_are_the_scatters_bytes(name, dtype):
    """"Walk with the write inside" is "``_write_token_kv``, then walk",
    byte for byte, on the read and on every layer of both pools: a block
    call of B and of 2B whose second block lies in the next page, heads of
    64 two to a row, a decode call with and without a lower edge, a chunk
    on a ring that wraps (and on a growing table) with ``true_len`` inside
    it, an inactive slot beside live ones; ``_mixed`` (ISSUE 55): the same
    calls on key rows twice as wide as the value rows, 8 query rows a KV
    head, a window of one page and a sink. bf16 pools take tiles of 16
    rows, so their pages are 16."""
    case = _write_case(name, page=16 if dtype == jnp.bfloat16 else 8,
                       dtype=dtype)
    _assert_same_bytes(case, *_scatter_then_walk(case))


@pytest.mark.parametrize("name", ["decode_groups", "chunk_ring"])
def test_rows_ride_in_a_group_of_kv_heads_a_step(name, a_head_a_step):
    """A scratch too small for every KV head (``groups`` 2): each step
    lays and writes its own heads' rows."""
    case = _write_case(name)
    _assert_same_bytes(case, *_scatter_then_walk(case))


def test_rows_ride_in_under_jit_with_a_traced_layer_and_donated_pools():
    """How a program calls it: inside jit, the layer a traced operand, the
    pools donated and handed back updated, twice in a row (the second
    call's rows over the first's)."""
    case = _write_case("two_blocks")
    call, q, pools, operands, static, k_new, v_new, page_idx, offset, _ = case

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def step(layer, k_pool, v_pool, k_new, v_new):
        read, k_pool, v_pool = call(q, k_pool, v_pool, *operands, layer,
                                    **static, write=(k_new, v_new, page_idx))
        return call(q, k_pool, v_pool, *operands, layer, **static,
                    write=(v_new, k_new, page_idx)), read

    k_set, v_set = kv_cache._write_token_kv(*pools, 1, k_new, v_new,
                                            page_idx, offset)
    first = call(q, k_set, v_set, *operands, 1, **static)
    k_set, v_set = kv_cache._write_token_kv(k_set, v_set, 1, v_new, k_new,
                                            page_idx, offset)
    want = (call(q, k_set, v_set, *operands, 1, **static), k_set, v_set)
    got, read = step(jnp.int32(1), jnp.copy(pools[0]), jnp.copy(pools[1]),
                     k_new, v_new)
    assert (np.asarray(read) == np.asarray(first)).all()
    _assert_same_bytes(case, want, got)


@pytest.mark.parametrize("name", ["block", "decode_window", "chunk_ring"])
def test_a_page_the_call_does_not_write_keeps_its_bytes(name):
    """Outside the pages the rows go to (and the trash page), both pools
    are what they were: every other page of the call's layer, every page
    of the other layer."""
    case = _write_case(name)
    pools, page_idx = case[2], np.asarray(case[7])
    _, (_, k_pool, v_pool) = _scatter_then_walk(case)
    other = np.setdiff1d(np.arange(pools[0].shape[2]),
                         np.append(page_idx.ravel(), 0))
    assert other.size
    for was, now in zip(pools, (k_pool, v_pool)):
        was, now = np.asarray(was), np.asarray(now)
        assert (was[0] == now[0]).all()
        assert (was[1][:, other] == now[1][:, other]).all()
        assert (was[1] != now[1]).any()


@pytest.mark.parametrize("name", ["two_blocks", "chunk_ring", "chunk_full"])
def test_the_trash_page_after_dropped_rows_is_its_old_bytes_or_an_idle_slots(
        name):
    """THE CONTRACT of a row the caller's rule sent to the trash page from
    a place of its own (a block past the table, a chunk's padding): the
    kernel drops it, where the scatter wrote it to page 0. So page 0 is the
    one place the pools may differ from the parent's, and what it holds is
    finite: a row of it is the bytes it had, or the row an INACTIVE slot (a
    table of zeros, whose rows go there from their own place) wrote at its
    position's offset. Nothing but an inactive slot reads it."""
    case = _write_case(name)
    _, _, pools, operands, _, k_new, v_new, _, offset, redirected = case
    assert redirected
    _, got = _scatter_then_walk(case)
    tables = np.asarray(operands[0])
    tables = tables.reshape(-1, tables.shape[-1])
    offset = np.asarray(offset).reshape(tables.shape[0], -1)
    for was, now, new in zip(pools, got[1:], (k_new, v_new)):
        was, now = np.asarray(was), np.asarray(now)
        assert np.isfinite(now[:, :, 0]).all()
        assert (was[0, :, 0] == now[0, :, 0]).all()     # the other layer
        old = (was[1, :, 0] == now[1, :, 0]).all(-1)    # [heads, offsets]
        # the rows in the pool's form: [slots, T, heads, lanes]
        new = np.asarray(new).reshape(*offset.shape, *was.shape[1::3])
        for slot in np.flatnonzero(~tables.any(axis=1)):
            for t, at in enumerate(offset[slot]):
                old[:, at] |= (now[1, :, 0, at] == new[slot, t]).all(-1)
        assert old.all(), np.argwhere(~old)[:4]


@pytest.mark.parametrize("module", [paged_ops, kv_cache],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_no_top_level_name_is_defined_twice(module):
    """A later ``def`` of a name silently replaces the earlier one: a
    spliced-in second copy of a kernel body would run in place of the one
    the text describes (PR 53's review)."""
    import ast
    import collections
    import inspect
    names = collections.Counter(
        node.name for node in ast.parse(inspect.getsource(module)).body
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)))
    assert [name for name, n in names.items() if n > 1] == []


def test_the_calls_that_walk_on_pools_of_k_and_v_write_their_rows():
    """``writing_calls``: what ``_geometry`` asks for ``_write_read`` and
    what an engine reports as ``attn_writes_in_kernel``: since ISSUE 61
    the decode and verify calls of a block without window layers too. The
    grid body (the chunk call of such a block), a latent pool and a
    tensor-parallel mesh keep the scatter; the grid body refuses rows."""
    assert paged_ops.writing_calls(False, block_len=4) == ["block"]
    assert paged_ops.writing_calls(False, windowed=True) == [
        "decode", "chunk"]
    assert paged_ops.writing_calls(False) == ["decode", "verify"]
    assert paged_ops.writing_calls(True) == []
    assert paged_ops.writing_calls(False, block_len=4, tp=2) == []
    assert paged_ops.writing_calls(False, tp=2) == []
    # the chunk call of a block without window layers: the grid body's
    case = _write_case("chunk_full")
    call, q, pools, operands, _, k_new, v_new, page_idx, _, _ = case
    with pytest.raises(ValueError, match="only the walking body"):
        call(q, *pools, *operands, 1, write=(k_new, v_new, page_idx))


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------


def test_resolve_auto_is_gather_off_tpu():
    assert kv_cache.resolve_attention_backend("auto") == "gather"
    assert kv_cache.resolve_attention_backend(None) == "gather"
    assert kv_cache.resolve_attention_backend("") == "gather"


def test_resolve_explicit_pallas_honored_off_tpu():
    """CPU pallas = interpret mode — the test-gating story. It must NOT
    silently degrade to gather."""
    assert kv_cache.resolve_attention_backend("pallas") == "pallas"
    assert kv_cache.resolve_attention_backend("gather") == "gather"


def test_resolve_unknown_raises():
    with pytest.raises(ValueError, match="attention_kernel"):
        kv_cache.resolve_attention_backend("flash")


def test_resolve_on_tpu_shape_gate(monkeypatch):
    """On TPU, auto picks pallas only when the kernel tiling fits; an
    explicit pallas on shapes the kernel cannot tile raises — it is never
    served by gather under the kernel's name."""
    monkeypatch.setattr(kv_cache.jax, "default_backend", lambda: "tpu")
    good = types.SimpleNamespace(head_dim=128)
    tiny = types.SimpleNamespace(head_dim=16)
    assert kv_cache.resolve_attention_backend("auto", good, 16) == "pallas"
    assert kv_cache.resolve_attention_backend("auto", tiny, 16) == "gather"
    with pytest.raises(ValueError, match="cannot tile"):
        kv_cache.resolve_attention_backend("pallas", tiny, 16)
    with pytest.raises(ValueError, match="cannot tile"):
        kv_cache.resolve_attention_backend("pallas", good, 7)
    assert kv_cache.resolve_attention_backend("pallas", good, 16) \
        == "pallas"
    assert kv_cache.resolve_attention_backend("auto", good, 7) == "gather"


# ---------------------------------------------------------------------------
# engine: end-to-end greedy identity + compile economy + telemetry
# ---------------------------------------------------------------------------


def _cfg(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=8, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=16,
             prefill_chunk=16)
    d.update(kw)
    return LLMConfig(**d)


def _run(cfg, prompts, max_tokens=16):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, rng_seed=0)
    eng.start()
    try:
        rids = [eng.submit(p, max_tokens=max_tokens, temperature=0.0)
                for p in prompts]
        outs = [eng.result(r, timeout=120.0) for r in rids]
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    return outs, stats


SHARED = "the quick brown fox jumps over the lazy dog again and again"
PROMPTS = [SHARED + " once", SHARED + " twice",
           "abc abc abc abc abc abc"]        # repetitive: spec drafts fire


def test_engine_greedy_identity_pallas_vs_gather_full_stack():
    """The acceptance invariant: greedy tokens bit-identical across
    backends with prefix cache + speculative decoding + KV tier ALL on —
    every kernel in the family on the hot path (decode, verify, chunked
    prefill via the shared-prefix long prompts)."""
    kw = dict(spec_decode_enabled=True, kv_tier_enabled=True)
    base, gstats = _run(_cfg(attention_kernel="gather", **kw), PROMPTS)
    pall, pstats = _run(_cfg(attention_kernel="pallas", **kw), PROMPTS)
    assert all(o["error"] is None for o in base + pall)
    assert [o["tokens"] for o in pall] == [o["tokens"] for o in base]
    assert gstats["attention_backend"] == "gather"
    assert pstats["attention_backend"] == "pallas"
    assert pstats["attn_backend_pallas"] == 1
    assert pstats["attn_decode_dispatches"] > 0
    assert pstats["attn_verify_dispatches"] > 0
    assert pstats["attn_chunk_dispatches"] > 0
    assert pstats["spec_rounds"] > 0


def test_engine_pallas_compile_once_per_tier():
    """Warmup pre-compiles the pallas decode/verify programs per (width,
    k) tier and traffic must not add any; a second identical traffic wave
    must add ZERO programs of any kind (prefill/chunk buckets compile
    lazily on first use by pre-existing engine design, then stay warm)."""
    cfg = _cfg(attention_kernel="pallas", spec_decode_enabled=True,
               warmup_compile=True)
    from ray_tpu.serve.llm import LLMEngine

    def wave(eng):
        rids = [eng.submit("abc abc abc abc abc", max_tokens=12,
                           temperature=0.0) for _ in range(3)]
        outs = [eng.result(r, timeout=120.0) for r in rids]
        assert all(o["error"] is None for o in outs)

    eng = LLMEngine(cfg, rng_seed=0)
    eng.start()
    try:
        warm_dv = eng._prof.compile_count(("decode", "verify"))
        assert warm_dv > 0            # warmup compiled the kernel tiers
        wave(eng)
        assert eng._prof.compile_count(("decode", "verify")) == warm_dv
        after_first = eng.engine_stats()["attn_kernel_compiles"]
        wave(eng)
        assert eng.engine_stats()["attn_kernel_compiles"] == after_first
    finally:
        eng.shutdown()


def test_engine_gather_fallback_still_serves():
    """attention_kernel='gather' pins the reference path; backend
    telemetry must say so."""
    outs, stats = _run(_cfg(attention_kernel="gather"), ["hello world"],
                       max_tokens=8)
    assert outs[0]["error"] is None
    assert stats["attention_backend"] == "gather"
    assert stats["attn_backend_pallas"] == 0
    assert stats["attn_decode_dispatches"] > 0


def test_backend_stats_exported_through_serve_plane():
    """New keys must ride every hop of the export chain (the README table
    is drift-guarded separately in test_profiling). The controller's
    _ENGINE_KEYS tuple is function-local, so it is checked in source."""
    import inspect

    from ray_tpu.serve import controller
    from ray_tpu.serve.llm import llm_server

    keys = {"attention_backend", "attn_backend_pallas",
            "attn_kernel_compiles", "attn_decode_dispatches",
            "attn_verify_dispatches", "attn_chunk_dispatches"}
    assert keys <= set(llm_server._EXPORTED_STATS)
    src = inspect.getsource(controller)
    engine_keys = src.split("_ENGINE_KEYS = (", 1)[1]
    for k in keys:
        assert f'"{k}"' in engine_keys, k


def test_unknown_attention_kernel_fails_engine_construction():
    from ray_tpu.serve.llm import LLMEngine

    with pytest.raises(ValueError, match="attention_kernel"):
        LLMEngine(_cfg(attention_kernel="flash"), rng_seed=0)
