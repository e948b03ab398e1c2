"""Generation by diffusion over blocks through the serving engine (ISSUE
37: the SDAR-MoE block), on the CPU at the tiny preset in float32: the
paged kernel's block mask (interpreted) against dense attention; every pass
of the engine's programs against the plain float32 reference (whole
prefill, chunked prefill, a denoise pass at every count of known tokens,
the commit pass, through the cache); the engine's pass of TWO blocks (ISSUE
38: the commit of a block rides with the first denoise pass of the next)
against commit-then-denoise position by position, and what its second half
may touch; served streams equal to the reference's own generation token
for token; the junk a denoise pass writes never readable; prefix reuse on
and off; what such a block is kept out of, each with its counter. Nothing
here is a device number.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from ray_tpu.models import joyai, llama, sdar_moe
from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.parallel import expert
from ray_tpu.serve.llm import LLMConfig, LLMEngine, disagg
from ray_tpu.serve.llm import kv_cache as kvc

CFG = sdar_moe.sdar_moe_tiny()
B, MASK = CFG.block_length, CFG.mask_token_id
REF = common.load_module("reference", "sdar_moe_f32")
REF_KW = {"theta": CFG.rope_theta, "eps": CFG.norm_eps, "top_k": CFG.top_k,
          "block": B, "mask": MASK, "denoise": CFG.denoise_passes}
PAGE = 8
ENGINE = dict(max_batch_size=4, page_size=PAGE, num_pages=64,
              max_prompt_len=128, max_seq_len=192, prefill_chunk=32,
              decode_block=8, pressure_decode_block=4, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)


@pytest.fixture(scope="module")
def params():
    return sdar_moe.init_params(jax.random.PRNGKey(0), CFG)


def _engine(cfg=CFG, **over):
    eng = LLMEngine(LLMConfig(model_config=cfg, **{**ENGINE, **over}))
    eng.start()
    return eng


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, 250, size=n)]


def _serve(eng, prompts, max_tokens):
    rids = [eng.submit(p, max_tokens=max_tokens, temperature=0.0)
            for p in prompts]
    outs = [eng.result(r, timeout=300.0) for r in rids]
    assert all(o["error"] is None for o in outs), outs
    return [[int(t) for t in o["tokens"]] for o in outs]


# ---- the kernel's mask ---------------------------------------------------------

@pytest.mark.parametrize("block_len", [1, 2, 4])
def test_kernel_block_mask_against_dense_attention(block_len):
    """The block wrapper's kernel (interpreted) under ``block_len``: equal
    to dense attention under the block mask; at 1 its mask is the causal
    one, so it equals the verify wrapper's. Since ISSUE 48 the two run two
    bodies (the block wrapper walks live pages in chunks of columns, the
    verify wrapper multiplies the table's whole span at once) whose
    partial sums may order differently: equal to the test's own
    tolerance, where it was bit for bit."""
    rs = np.random.RandomState(block_len)
    slots, t, h, hkv, d, pages, mp = 3, 4, 4, 2, 16, 12, 3
    q = jnp.asarray(rs.randn(slots, t, h, d), jnp.float32)
    k_pool = jnp.asarray(rs.randn(1, hkv, pages, PAGE, d), jnp.float32)
    v_pool = jnp.asarray(rs.randn(1, hkv, pages, PAGE, d), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(pages - 1)[:slots * mp].reshape(
        slots, mp), jnp.int32)
    lens = jnp.asarray([4, 8, 12], jnp.int32)       # block edges for 1, 2, 4
    got = paged_ops.paged_block_attention(
        q, k_pool, v_pool, tables, lens, 0, block_len=block_len,
        interpret=True)
    pos = lens[:, None] + jnp.arange(t)[None, :]
    kpos = jnp.arange(mp * PAGE)
    valid = kvc._visible(kpos[None, None, :], pos[:, :, None], block_len)
    want = kvc._dense_attention(
        q, kvc._gather_seq(k_pool, 0, tables), kvc._gather_seq(v_pool, 0,
                                                               tables),
        valid[:, None], d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    if block_len == 1:
        causal = paged_ops.paged_verify_attention(
            q, k_pool, v_pool, tables, lens, 0, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(causal),
                                   atol=2e-6)
    else:   # the last position of a block sees no more than the first does
        assert not np.allclose(np.asarray(got), np.asarray(
            paged_ops.paged_verify_attention(q, k_pool, v_pool, tables, lens,
                                             0, interpret=True)))


@pytest.mark.parametrize("ends", ["inside_a_page", "on_a_page_edge",
                                  "at_max_seq_len"])
@pytest.mark.parametrize("blocks", [1, 2], ids=["one_block", "two_blocks"])
def test_the_walking_kernel_reads_no_page_past_a_slots_live_ones(
        params, blocks, ends):
    """A block pass (and the pass of two blocks) through the pallas
    backend on a POISONED pool, NaN in every page past each slot's live
    ones (the trash page too), equals the gather backend's on the clean
    pool: the kernel's work follows the context (ISSUE 48). The context
    under test beside a short neighbour; at ``max_seq_len`` the second
    block of a pass of two lies past the table's width (the trash page)
    and the live length is the table's."""
    mp = 6                                           # table width: 48 tokens
    ctx = {"inside_a_page": 2 * PAGE + B, "on_a_page_edge": 2 * PAGE,
           "at_max_seq_len": mp * PAGE - B}[ends]
    lens = np.asarray([ctx, B])
    t = blocks * B
    rs = np.random.RandomState(48)
    kv = kvc.init_paged_cache(CFG, 2 * mp + 1, PAGE)
    kv = {**kv, **{pool: jnp.asarray(0.5 * rs.randn(*kv[pool].shape),
                                     kv[pool].dtype) for pool in "kv"}}
    tables = jnp.asarray(1 + rs.permutation(2 * mp).reshape(2, mp), jnp.int32)
    live = np.zeros(2 * mp + 1, bool)
    for row, n in zip(np.asarray(tables), lens):
        live[row[:-(-min(int(n) + t, mp * PAGE) // PAGE)]] = True
    poisoned = {**kv, **{pool: jnp.where(live[None, None, :, None, None],
                                         kv[pool], jnp.nan) for pool in "kv"}}
    tokens = jnp.asarray([[3, 1, 4, 1] + [MASK] * (t - B),
                          [MASK] * t], jnp.int32)

    def logits(kv, backend):
        if blocks == 2:
            return kvc.paged_block_pair_step(
                params, kv, tables, jnp.asarray(lens, jnp.int32), tokens,
                CFG, PAGE, backend)[0]
        return kvc.paged_block_step(
            params, kv, tables, jnp.asarray(lens, jnp.int32), tokens, CFG,
            PAGE, backend, commit=False)[0]

    got = np.asarray(logits(poisoned, "pallas"))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(logits(kv, "gather")),
                               atol=1e-5)


@pytest.mark.parametrize("model,backend,want,writes", [
    (CFG, "pallas", ["block"], ["block"]), (CFG, "gather", [], []),
    (llama.llama_tiny(vocab_size=512), "pallas", ["decode", "verify"],
     ["decode", "verify"]),
    (joyai.joyai_tiny(), "pallas", ["decode", "verify", "chunk"], [])],
    ids=["sdar-pallas", "sdar-gather", "dense-pallas", "latent-pallas"])
def test_attn_walks_live_names_the_calls_that_walk(model, backend, want,
                                                   writes):
    """``attn_walks_live``: the call kinds of THIS engine's programs whose
    kernel body walks live pages, so that ``attn_live_pages_total /
    attn_table_pages_total`` is read only where it applies; and
    ``attn_writes_in_kernel``, those whose kernel also writes the call's
    own rows of K and V (ISSUE 53: the calls that walk on pools of K and V
    per head, since ISSUE 61 the dense block's decode and verify calls
    too; a latent engine scatters every call's)."""
    eng = LLMEngine(LLMConfig(model_config=model,
                              **{**ENGINE, "attention_kernel": backend}))
    assert eng.engine_stats()["attn_walks_live"] == want
    assert eng.engine_stats()["attn_writes_in_kernel"] == writes


def test_pallas_and_gather_backends_give_one_block_pass(params):
    kv = kvc.init_paged_cache(CFG, 16, PAGE)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lens = jnp.asarray([8, 12], jnp.int32)
    blocks = jnp.asarray([[5, 6, MASK, MASK], [MASK] * 4], jnp.int32)
    out = [kvc.paged_block_step(params, kv, tables, lens, blocks, CFG, PAGE,
                                backend, commit=False)[0]
           for backend in ("gather", "pallas")]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(out[1]),
                               atol=1e-5)


# ---- every pass against the reference --------------------------------------------

def _reference_block_logits(params, clean, block, g):
    """The reference's logits [B, V] for the noisy ``block`` standing for
    block ``g`` after the clean tokens."""
    _, xn, _, _ = REF._forward(params, np.asarray(clean, np.int32),
                               np.asarray(block, np.int32)[None],
                               np.asarray([g], np.int32), **REF_KW)
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF._head(xn[0], params["final_norm"],
                                    params["lm_head"], CFG.norm_eps))


def _prefilled(params, prompt, chunked: bool):
    """The cache after the engine's prompt programs, and the table."""
    kv = kvc.init_paged_cache(CFG, 32, PAGE)
    table = jnp.asarray(1 + np.arange(24), jnp.int32)
    n = len(prompt)
    if not chunked:
        toks = np.zeros((1, 128), np.int32)
        toks[0, :n] = prompt
        return kvc.paged_prefill(params, kv, table, jnp.asarray(toks),
                                 jnp.int32(n), CFG, PAGE)[1], table
    for start in range(0, n, 32):
        toks = np.zeros((1, 32), np.int32)
        seg = prompt[start:start + 32]
        toks[0, :len(seg)] = seg
        kv = kvc.paged_prefill_chunk(
            params, kv, table, jnp.asarray(toks), jnp.int32(start),
            jnp.int32(n), CFG, PAGE)[1]
    return kv, table


@pytest.mark.parametrize("known", [0, 1, 2, 3])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole_prefill", "chunked_prefill"])
def test_each_pass_is_the_references(params, known, chunked):
    """A prompt that leaves ``known`` tokens in its pending block: the
    denoise pass over that block, the commit pass once it is full, and a
    denoise pass of the NEXT block (which reads what the commit wrote),
    each against the plain reference at 1e-5."""
    prompt = _prompt(known, 72 + known)
    kept = len(prompt) - known
    kv, table = _prefilled(params, prompt, chunked)
    block = np.asarray(prompt[kept:] + [MASK] * (B - known), np.int32)
    lens = jnp.asarray([kept], jnp.int32)
    logits, kv, same = kvc.paged_block_step(
        params, kv, table[None], lens, jnp.asarray(block)[None], CFG, PAGE,
        commit=False)
    assert int(same[0]) == kept
    want = _reference_block_logits(params, prompt[:kept] + [0] * B, block,
                                   kept // B)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=1e-5)
    full = np.where(block == MASK, np.argmax(want, -1), block).astype(
        np.int32)
    none, kv, after = kvc.paged_block_step(
        params, kv, table[None], lens, jnp.asarray(full)[None], CFG, PAGE,
        commit=True)
    assert none is None and int(after[0]) == kept + B
    fresh = np.full((B,), MASK, np.int32)
    logits, _, _ = kvc.paged_block_step(
        params, kv, table[None], after, jnp.asarray(fresh)[None], CFG, PAGE,
        commit=False)
    want = _reference_block_logits(
        params, prompt[:kept] + [int(t) for t in full] + [0] * B, fresh,
        kept // B + 1)
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=1e-5)


def test_a_denoise_pass_leaves_nothing_readable(params):
    """The K / V a denoise pass writes is junk (kv_cache._span_step's
    rule): poisoned before the commit pass, it changes nothing after."""
    prompt = _prompt(7, 40)
    kv, table = _prefilled(params, prompt, False)
    lens = jnp.asarray([40], jnp.int32)
    masked = jnp.full((1, B), MASK, jnp.int32)
    clean = jnp.asarray([[3, 1, 4, 1]], jnp.int32)

    def rest(kv):
        _, kv, after = kvc.paged_block_step(
            params, kv, table[None], lens, clean, CFG, PAGE, commit=True)
        return np.asarray(kvc.paged_block_step(
            params, kv, table[None], after, masked, CFG, PAGE,
            commit=False)[0])

    _, kv, _ = kvc.paged_block_step(params, kv, table[None], lens, masked,
                                    CFG, PAGE, commit=False)
    page, off = int(table[40 // PAGE]), 40 % PAGE
    written = np.asarray(kv["k"][:, :, page, off:off + B])
    assert np.abs(written).max() > 0                 # the pass did write
    poisoned = {**kv, "k": kv["k"].at[:, :, page, off:off + B].set(1e4),
                "v": kv["v"].at[:, :, page, off:off + B].set(-1e4)}
    assert np.array_equal(rest(kv), rest(poisoned))


# ---- the pass of two blocks (the deferred commit) --------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_the_pass_of_two_blocks_is_commit_then_denoise(params, backend):
    """One batch, one pass of 2B: a slot whose pending block is CLEAN (the
    pass keeps it and denoises the next: equal to the commit pass followed
    by a denoise pass of an all-masked block) beside one whose pending
    block is not (a denoise pass of that block, nothing kept). Logits
    position by position, the lengths, and the K / V the kept block left."""
    prompts = [_prompt(31, 40), _prompt(32, 24)]
    kv = kvc.init_paged_cache(CFG, 32, PAGE)
    tables = jnp.asarray([1 + np.arange(8), 9 + np.arange(8)], jnp.int32)
    for table, prompt in zip(tables, prompts):
        toks = np.zeros((1, 128), np.int32)
        toks[0, :len(prompt)] = prompt
        kv = kvc.paged_prefill(params, kv, table, jnp.asarray(toks),
                               jnp.int32(len(prompt)), CFG, PAGE)[1]
    lens = jnp.asarray([40, 24], jnp.int32)
    fresh = np.full((B,), MASK, np.int32)
    first = np.asarray([[3, 1, 4, 1], [5, 9, MASK, MASK]], np.int32)

    def step(kv, lens, blocks, commit, tabs=tables):
        return kvc.paged_block_step(params, kv, tabs, lens,
                                    jnp.asarray(blocks), CFG, PAGE, backend,
                                    commit=commit)

    # what the parent's program ran: slot 1's denoise pass; slot 0's commit
    # (slot 1 writing the trash page), then its denoise pass of a new block
    want_1 = np.asarray(step(kv, lens, first, False)[0])[1]
    _, committed, after = step(kv, lens, first, True,
                               tables.at[1].set(0))
    want_0 = np.asarray(step(committed, after, np.stack([fresh, fresh]),
                             False)[0])[0]

    logits, got, new_lens, kept = kvc.paged_block_pair_step(
        params, kv, tables, lens,
        jnp.asarray(np.concatenate([first, np.stack([fresh, fresh])], 1)),
        CFG, PAGE, backend)
    assert logits.shape == (2, B, CFG.vocab_size)
    assert [bool(x) for x in kept] == [True, False]
    assert [int(x) for x in new_lens] == [40 + B, 24]
    np.testing.assert_allclose(np.asarray(logits[0]), want_0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits[1]), want_1, atol=1e-5)
    page, off = int(tables[0, 40 // PAGE]), 40 % PAGE
    for pool in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(got[pool][:, :, page, off:off + B]),
            np.asarray(committed[pool][:, :, page, off:off + B]), atol=1e-6)
    with pytest.raises(ValueError, match="two blocks"):
        kvc.paged_block_pair_step(params, kv, tables, lens,
                                  jnp.asarray(first), CFG, PAGE, backend)


@pytest.mark.parametrize("clean", [True, False],
                         ids=["kept_block", "prompt_block"])
def test_the_second_half_touches_no_page_of_another_sequence(params, clean):
    """A poisoned pool, two sequences in one pass of 2B: one at the LAST
    block edge of a full table (its second half lies past the table's
    width), one a block before the end of its only page (its second half
    lies in a table entry it does not own). Every page but the trash page
    keeps the poison outside the rows its owner's span names."""
    mp = 3                                           # table width: 24 tokens
    kv = kvc.init_paged_cache(CFG, 16, PAGE)
    kv = {**kv, "k": jnp.full_like(kv["k"], 7e3),
          "v": jnp.full_like(kv["v"], -7e3)}
    tables = jnp.asarray([[4, 5, 6], [9, 0, 0]], jnp.int32)
    lens = np.asarray([mp * PAGE - B, PAGE - B])
    block = [3, 1, 4, 1] if clean else [3, 1, MASK, MASK]
    tokens = jnp.asarray([block + [MASK] * B] * 2, jnp.int32)
    logits, got, new_lens, kept = kvc.paged_block_pair_step(
        params, kv, tables, jnp.asarray(lens, jnp.int32), tokens, CFG, PAGE)
    assert [bool(x) for x in kept] == [clean, clean]
    assert [int(x) for x in new_lens] == [int(n) + B * clean for n in lens]
    own = {6: lens[0] % PAGE, 9: lens[1] % PAGE}     # page: first row written
    for pool, poison in (("k", 7e3), ("v", -7e3)):
        pages = np.asarray(got[pool])                # [L, Hkv, P, page, D]
        for page in range(1, pages.shape[2]):
            untouched = pages[:, :, page, :own.get(page, PAGE)]
            assert np.all(untouched == poison), (pool, page)
        for page, row in own.items():                # the first half, written
            assert np.all(np.abs(pages[:, :, page, row:row + B]) < 1e3)


# ---- served streams against the reference's own generation ----------------------

@pytest.mark.parametrize("left", [0, 1, 2, 3])
def test_streams_are_the_references_for_every_prompt_remainder(params, left):
    """Prompts that leave 0-3 tokens in their first block (one by a whole
    prefill, one by chunks), side by side."""
    prompts = [_prompt(10 + left, 20 + left), _prompt(20 + left, 72 + left)]
    eng = _engine()
    try:
        got = _serve(eng, prompts, 12)
    finally:
        eng.shutdown()
    for p, g in zip(prompts, got):
        assert g == REF.generate(params, p, 12, **REF_KW)[0]


@pytest.mark.parametrize("max_tokens", [9, 10, 11, 12])
def test_streams_are_the_references_for_every_cut(params, max_tokens):
    """``max_tokens`` of every remainder: the last block is revealed whole
    and cut, and the cut tokens are counted."""
    prompt = _prompt(max_tokens, 24)
    eng = _engine()
    try:
        got = _serve(eng, [prompt], max_tokens)[0]
        st = eng.engine_stats()
    finally:
        eng.shutdown()
    assert got == REF.generate(params, prompt, max_tokens, **REF_KW)[0]
    assert len(got) == max_tokens
    assert st["tokens_cut_total"] >= -max_tokens % B
    assert st["tokens_out"] == max_tokens


def test_a_stop_token_inside_a_block_cuts_it(params):
    prompt = _prompt(3, 21)
    whole = REF.generate(params, prompt, 16, **REF_KW)[0]
    # a token first served in the middle of a block (prompt leaves 1)
    at = next(i for i, t in enumerate(whole)
              if (21 + i) % B in (1, 2) and t not in whole[:i])
    eng = _engine()
    try:
        eng.tokenizer.eos_token_id = whole[at]
        got = _serve(eng, [prompt], 16)[0]
        cut = eng.engine_stats()["tokens_cut_total"]
    finally:
        eng.shutdown()
    assert got == whole[:at] and cut >= 1
    assert got == REF.generate(params, prompt, 16, stop=whole[at],
                               **REF_KW)[0]


def test_a_stream_to_max_seq_len_beside_a_cut_one_on_a_poisoned_pool(params):
    """One stream ends at ``max_seq_len`` (its table is full: the last
    pass of two blocks reaches past its width), its neighbour's last block
    is cut. The pool starts poisoned: both are the reference's streams, so
    nothing unwritten was read, and the pages no sequence was given still
    hold the poison, so no overshoot left its sequence's pages."""
    prompts = [_prompt(41, 120), _prompt(42, 21)]
    budgets = [ENGINE["max_seq_len"] - 120, 10]
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    eng.kv = {**eng.kv, "k": jnp.full_like(eng.kv["k"], 7e3),
              "v": jnp.full_like(eng.kv["v"], -7e3)}
    handed = set()
    alloc = eng.allocator.alloc

    def recording(n):
        pages = alloc(n)
        handed.update(pages or ())
        return pages

    eng.allocator.alloc = recording
    eng.start()
    try:
        rids = [eng.submit(p, max_tokens=n, temperature=0.0)
                for p, n in zip(prompts, budgets)]
        outs = [eng.result(r, timeout=300.0) for r in rids]
        st = eng.engine_stats()
    finally:
        eng.shutdown()
    for p, n, o in zip(prompts, budgets, outs):
        assert o["error"] is None
        assert [int(t) for t in o["tokens"]] \
            == REF.generate(params, p, n, **REF_KW)[0]
    assert len(outs[0]["tokens"]) == budgets[0]      # to max_seq_len
    assert st["tokens_cut_total"] >= 1
    assert len(handed) == 24 + 4                     # a full table, and 32 / 8
    rest = [p for p in range(1, ENGINE["num_pages"]) if p not in handed]
    assert np.all(np.asarray(eng.kv["k"])[:, :, rest] == 7e3)
    assert np.all(np.asarray(eng.kv["v"])[:, :, rest] == -7e3)


def test_prefix_reuse_on_and_off_give_the_same_tokens():
    """Two prompts that share 256 tokens (32 pages): the second takes the
    first's pages and prefills only its tail, by the chunk program, from a
    block edge."""
    cfg = sdar_moe.sdar_moe_tiny(max_seq_len=384)
    shared = _prompt(1, 256)
    prompts = [shared + _prompt(2, 9), shared + _prompt(3, 14)]
    out = {}
    for on in (True, False):
        eng = _engine(cfg, max_prompt_len=320, max_seq_len=384, num_pages=128,
                      prefix_cache_enabled=on)
        try:
            out[on] = [_serve(eng, [p], 10)[0] for p in prompts]
            st = eng.engine_stats()
        finally:
            eng.shutdown()
        assert (st["prefix_hits"] >= 1) == on
        assert (st["prefix_hit_tokens"] >= 256) == on
    assert out[True] == out[False]


# ---- counters, and what the block is kept out of ---------------------------------

@pytest.fixture(scope="module")
def counted():
    """16 tokens after a prompt on a block edge: four dispatches of one
    block (nothing queues, so the idle tier runs the smallest warmed tier:
    ISSUE 42; the parent ran two of two), S = 2 passes a block, the first
    of each over two blocks."""
    eng = _engine()
    try:
        _serve(eng, [_prompt(5, 24)], 16)
        return eng.engine_stats()
    finally:
        eng.shutdown()


LAYERS = CFG.n_layers
COUNTS = {
    "tokens_out": 16, "tokens_cut_total": 0,
    # S passes a block (a pass of two blocks is ONE pass): 4 blocks x 2
    "block_passes_total": 8, "steps": 8, "denoise_passes_total": 8,
    # no commit runs alone; every pass of two blocks but the first (whose
    # slot came from a prefill) kept a block and denoised the next
    "commit_passes_total": 0, "fused_passes_total": 3,
    "slot_passes_total": 8,                  # over tokens_out: S / B = 0.5
    # the last block of a finished stream is never committed
    "blocks_committed_total": 3,
    "routed_layer_steps_total": 8 * LAYERS,
    # a block: a pass of 2B rows and one of B, top_k experts a row
    "expert_rows_total": 4 * 3 * B * LAYERS * CFG.top_k,
    "prefills": 1, "phase_block_dispatch_n": 4, "phase_decode_dispatch_n": 0,
    "dispatch_tier_idle_total": 4, "idle_lead_k": 1,
}


@pytest.mark.parametrize("counter", sorted(COUNTS))
def test_passes_and_blocks_are_counted(counted, counter):
    assert counted[counter] == COUNTS[counter]


def test_passes_a_token_and_experts_touched(counted):
    assert counted["slot_passes_total"] / counted["tokens_out"] \
        == CFG.denoise_passes / B == 0.5
    assert 0 < counted["experts_touched_total"] \
        <= 8 * LAYERS * CFG.n_experts


def test_speculation_and_the_tier_are_bypassed_and_counted(params, tmp_path):
    eng = _engine(spec_decode_enabled=True, kv_tier_enabled=True,
                  kv_tier_disk_dir=str(tmp_path))
    try:
        prompt = _prompt(9, 30)
        got = _serve(eng, [prompt], 8)[0]
        st = eng.engine_stats()
    finally:
        eng.shutdown()
    assert got == REF.generate(params, prompt, 8, **REF_KW)[0]
    assert st["spec_bypassed_block"] == 1 and st["spec_rounds"] == 0
    assert st["kv_tier_bypassed_block"] == 1 and st["spilled_pages"] == 0


def test_disaggregated_handoff_is_refused_and_counted():
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="pending block"):
        disagg.prefill_only(eng, _prompt(40, 20))
    assert eng.engine_stats()["disagg_refused_block"] == 1
    dec = disagg.DecodeEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError):
        dec.submit_prefilled({})
    assert dec.stats["disagg_refused_block"] == 1


@pytest.mark.parametrize("field", ["page_size", "prefill_chunk",
                                   "max_seq_len"])
def test_edges_must_be_block_edges(field):
    with pytest.raises(ValueError, match="block length"):
        LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, field: 30}))


def test_block_and_tensor_parallel_are_the_blocks_to_state():
    spec = sdar_moe.cache_spec(CFG)
    assert (spec.block_length, spec.mask_token) == (B, MASK)
    assert spec.routed_layers == spec.paged_layers == CFG.n_layers
    assert [ld.routed_layer for ld in sdar_moe.serve_layers(CFG)] \
        == list(range(CFG.n_layers))                     # walked
    assert CFG.reveal_per_pass == 2
    with pytest.raises(ValueError, match="tensor-parallel"):
        sdar_moe.check_tp_divides(CFG, 2)
    with pytest.raises(NotImplementedError):
        sdar_moe.load_params("x", CFG)
    full = sdar_moe.SdarMoeConfig(n_layers=7)
    assert sdar_moe.num_params(full) == 4_984_176_384    # 9.97 GB in bf16


# ---- the softmax router ----------------------------------------------------------

@pytest.mark.parametrize("norm", [True, False])
def test_softmax_router_takes_the_most_probable_and_renormalises(norm):
    rs = np.random.RandomState(0)
    g = jnp.asarray(rs.randn(6, 16), jnp.float32)
    router = jnp.asarray(rs.randn(16, 8), jnp.float32)
    idx, w = expert.route_softmax_top_k(g, router, 3, norm_topk_prob=norm)
    p = np.asarray(jax.nn.softmax(g @ router, axis=-1))
    want = np.argsort(-p, axis=-1)[:, :3]
    assert np.array_equal(np.asarray(idx), want)
    taken = np.take_along_axis(p, want, axis=-1)
    if norm:
        taken = taken / taken.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w), taken, atol=1e-6)
