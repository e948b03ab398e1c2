"""The Trinity-Large block (``model_type`` ``afmoe``: a gated GQA mixer under
four norms a layer, WINDOW layers beside full ones, routed experts beside
a shared one, one chip's share of the experts) through the serving engine
(ISSUE 52), on the CPU at the tiny preset in float32 (window 16, pages of
8, chunks of 32: a ring of 7 pages = 56 positions): the engine's greedy
tokens against the plain float32 reference, whose window is a band in a
mask, through a whole prefill, a chunked prefill and decode past the window
and past the ring's wrap; the ring itself (bounded, a page written again
only when no later query can see it, both kinds of pages freed, admission
refused when either is short); the shares of a partition of the experts
adding up to the uncut layer; what a block with window layers is kept out
of, each with its counter. Nothing here is a device number.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from ray_tpu.models import afmoe
from ray_tpu.models.block import block_of
from ray_tpu.serve.llm import LLMConfig, LLMEngine, disagg
from ray_tpu.serve.llm import kv_cache as kvc

CFG = afmoe.afmoe_tiny()
FAM = common.load_module("models", "afmoe")
REF = common.load_module("reference", "afmoe_f32")
REF_KW = FAM.reference_kwargs(CFG)
ENGINE = dict(max_batch_size=4, page_size=8, num_pages=97, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=32, decode_block=4,
              pressure_decode_block=2, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)
RING = 7        # (window 16 + chunk 32) / 8 + 1


@pytest.fixture(scope="module")
def params():
    return afmoe.init_params(jax.random.PRNGKey(0), CFG)


def _engine(**over):
    eng = LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, **over}))
    eng.start()
    return eng


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, 250, size=n)]


def _reference_agrees(params, prompts, outs, max_tokens, **ref_kw):
    """Every served token is the float32 reference's best at its position
    (teacher-forced: check 2's function, margin a rounding's)."""
    samples = [{"prompt_ids": p, "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": max_tokens} for p, o in zip(prompts, outs)]
    got = checks.served_tokens_check(REF, {**REF_KW, **ref_kw}, params,
                                     samples, 1e-3, eos=None)
    assert got["tokens_checked"] > 0
    return got


# ---- the engine, end to end ------------------------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("lengths,max_tokens", [
    ((20,), 50), ((70,), 12), ((20, 70, 33, 100, 9), 12)],
    ids=["whole_prefill", "chunked_prefill", "mixed"])
def test_engine_tokens_are_the_references(params, lengths, max_tokens,
                                          backend):
    """20 tokens: one whole-prompt program, then 50 decode steps, past the
    window (16) and the ring's first wrap (56); 70 and 100: chunks of 32,
    whose third wraps the ring, then decode across the page edge at 72
    (the ring's entry 9 % 7 = 2, written a second time)."""
    eng = _engine(attention_kernel=backend)
    try:
        prompts = [_prompt(i, n) for i, n in enumerate(lengths)]
        rids = [eng.submit(p, max_tokens=max_tokens, temperature=0.0)
                for p in prompts]
        outs = [eng.result(r, timeout=300.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        assert _reference_agrees(params, prompts, outs, max_tokens)["ok"]
        st = eng.engine_stats()
        assert st["ring_pages"] == RING
        assert st["window_pages_recycled_total"] > 0
        # both kinds of pages are back (page 0 of each pool: the trash page)
        assert st["free_pages"] == ENGINE["num_pages"] - 1
        assert st["window_pages_in_use"] == st["full_pages_in_use"] == 0
        assert eng.window_allocator.available() == 4 * RING
        # asked for by default and not done: prompts longer than a page
        assert st["prefix_bypassed_window"] == sum(n > 8 for n in lengths)
        assert st["prefix_hits"] == 0
        assert st["attn_walks_live"] == st["attn_writes_in_kernel"] == (
            ["decode", "chunk"] if backend == "pallas" else [])
    finally:
        eng.shutdown()


@pytest.mark.parametrize("wrong", [{"window": 15}, {"window": 17},
                                   {"rotate_full": True}, {"gate": False}],
                         ids=lambda kw: "-".join(f"{k}_{v}"
                                                 for k, v in kw.items()))
def test_a_reference_with_one_rule_wrong_disagrees(params, wrong):
    """The same streams against a reference whose window is one token short
    or long, that rotates in the full layer, or has no gate: refused."""
    eng = _engine()
    try:
        prompts = [_prompt(7, 70), _prompt(8, 100)]
        outs = [eng.result(eng.submit(p, max_tokens=12, temperature=0.0),
                           timeout=300.0) for p in prompts]
    finally:
        eng.shutdown()
    assert _reference_agrees(params, prompts, outs, 12)["ok"]
    assert not _reference_agrees(params, prompts, outs, 12, **wrong)["ok"]


# ---- the ring --------------------------------------------------------------

@pytest.mark.parametrize("window,page,span", [
    (16, 8, 32), (16, 8, 1), (16, 8, 5), (13, 8, 9), (4096, 128, 512),
    (4096, 128, 2048), (4096, 128, 1)])
def test_a_ring_entry_is_written_again_only_out_of_every_windows_sight(
        window, page, span):
    """A call at positions [s, s + span) writes the ring entries of its
    pages; each held the page ``ring`` before, whose LAST token must lie
    below the oldest key the call's first query sees, ``s - window + 1``
    (write-then-read: the call reads after it has written). And for a span
    of whole pages the ring is no page longer than some s needs."""
    ring = kvc.ring_pages(window, page, span)
    assert ring == -(-(window + span) // page) + 1
    starts = range(0, 4 * ring * page + 3)
    for s in starts:
        for logical in range(s // page, (s + span - 1) // page + 1):
            last_of_overwritten = (logical - ring + 1) * page - 1
            assert last_of_overwritten < s - window + 1, (s, logical)
    # one page fewer and some call overwrites a token it still reads
    assert span % page or any(
        ((s + span - 1) // page - (ring - 1) + 1) * page - 1
        >= s - window + 1 for s in starts)


def test_pools_are_sized_by_the_ring_not_by_the_context():
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    spec = afmoe.cache_spec(CFG)
    assert (spec.paged_layers, spec.window_layers, spec.window) == (1, 3, 16)
    assert eng.kv["k"].shape == (1, 2, ENGINE["num_pages"], 8, 16)
    assert eng.kv["kw"].shape == (3, 2, 4 * RING + 1, 8, 16)
    assert eng.page_tables.shape == (4, 192 // 8 + RING)
    assert kvc.pool_nbytes(eng.kv) == 2 * (
        eng.kv["k"].nbytes + eng.kv["kw"].nbytes)
    # at the cell's size: 24 slots x 37 pages and the trash page
    assert kvc.ring_pages(4096, 128, 512) == 37


def test_window_pages_in_use_stay_under_the_rings_at_any_context(params):
    """Four slots decode to contexts of 150 (19 pages of the growing
    table): none ever holds more than its ring of 7 of the window pool."""
    eng = _engine()
    try:
        rids = [eng.submit(_prompt(50 + i, 90 + i), max_tokens=60,
                           temperature=0.0) for i in range(6)]
        most_window = most_full = 0
        while not all(eng._requests[r].done_event.is_set() for r in rids):
            st = eng.engine_stats()
            most_window = max(most_window, st["window_pages_in_use"])
            most_full = max(most_full, st["full_pages_in_use"])
            time.sleep(0.02)
        outs = [eng.result(r, timeout=300.0) for r in rids]
        assert all(o["error"] is None and o["tokens"] for o in outs)
        assert 0 < most_window <= 4 * RING
        assert most_full > 4 * RING          # the growing tables pass it
        st = eng.engine_stats()
        pages = [-(-(90 + i + len(o["tokens"])) // 8)
                 for i, o in enumerate(outs)]
        assert st["window_pages_recycled_total"] >= sum(
            max(0, n - 1 - RING) for n in pages) > 0
        assert st["window_pages_in_use"] == st["full_pages_in_use"] == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_waits_when_either_kind_of_page_is_short(params, short):
    """A request is admitted with its pages of BOTH kinds or not at all:
    with one pool drained it waits and holds nothing of the other."""
    eng = _engine()
    try:
        pool = eng.allocator if short == "full" else eng.window_allocator
        other = eng.window_allocator if short == "full" else eng.allocator
        with eng._lock:
            held = pool.alloc(pool.available() - 2)
        rid = eng.submit(_prompt(60, 40), max_tokens=8, temperature=0.0)
        time.sleep(0.5)
        st = eng.engine_stats()
        assert st["active_slots"] == 0 and st["waiting"] == 1
        assert other.available() == other.num_pages - 1
        assert pool.available() == 2
        with eng._lock:
            pool.free(held)
        out = eng.result(rid, timeout=300.0)
        assert out["error"] is None and len(out["tokens"]) == 8
        assert pool.available() == pool.num_pages - 1
        assert other.available() == other.num_pages - 1
    finally:
        eng.shutdown()


def test_200_admissions_and_finishes_leak_no_page_of_either_kind(params):
    eng = _engine(max_batch_size=8, num_pages=200)
    try:
        rng = np.random.RandomState(7)
        rids = [eng.submit(_prompt(100 + i, int(rng.randint(3, 70))),
                           max_tokens=int(rng.randint(1, 4)), temperature=0.0)
                for i in range(200)]
        outs = [eng.result(r, timeout=600.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        st = eng.engine_stats()
        assert st["free_pages"] == 200 - 1 and st["active_slots"] == 0
        assert eng.window_allocator.available() == 8 * RING
        assert sorted(eng.free_slots) == list(range(8))
    finally:
        eng.shutdown()


# ---- one chip's share of the experts ---------------------------------------

def test_the_shares_of_a_partition_add_up_to_the_uncut_layer(params):
    """The routed layer of the model on 8 chips of 2 experts each (chip c
    holds the experts the router scores in columns 2c, 2c + 1: its model
    is the block with ``experts_held`` 2 and the router's columns rolled so
    that its own come first): the 8 shares and ONE shared expert (every
    chip computes it, the deployment counts it once) add up to the plain
    reference's uncut layer, and each share is the reference's ``held``."""
    lp = params["layers"][2]
    moe, n, per = lp["moe"], CFG.n_experts, 2
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim), jnp.float32)
    g = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.norm_eps) \
        * lp["ffn_norm"]
    kw = dict(eps=CFG.norm_eps, top_k=CFG.top_k, scaling=CFG.scaling,
              use_bias=True, post_norm=False)
    uncut = REF._routed(x, lp, None, held=(0, n), shared=True, **kw)[0] - x
    chip = afmoe.afmoe_tiny(experts_held=per)
    total = 0.0
    for lo in range(0, n, per):
        mine = {**moe, "router": jnp.roll(moe["router"], -lo, axis=1),
                "bias": jnp.roll(moe["bias"], -lo),
                **{k: moe[k][lo:lo + per]
                   for k in ("w_gate", "w_up", "w_down")}}
        share, shared, idx = afmoe.routed_parts(g, mine, chip)
        assert idx.shape == (24, CFG.top_k) and int(idx.max()) < n
        want = REF._routed(x, {**lp, "moe": {**moe, **{
            k: moe[k][lo:lo + per] for k in ("w_gate", "w_up", "w_down")}}},
            None, held=(lo, lo + per), shared=False, **kw)[0] - x
        np.testing.assert_allclose(share, want, atol=2e-5)
        total = total + share
    assert float(jnp.abs(uncut).max()) > 0.1
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)


def test_a_share_of_the_experts_serves_the_references_share(params):
    """The engine on a block that holds 8 of its router's 16 experts: the
    streams are the reference's with ``held=(0, 8)`` (a pick of an expert
    held elsewhere adds nothing), not the uncut model's."""
    cfg = afmoe.afmoe_tiny(experts_held=8)
    cut = afmoe.init_params(jax.random.PRNGKey(0), cfg)
    assert cut["layers"][1]["moe"]["w_gate"].shape[0] == 8
    assert cut["layers"][1]["moe"]["router"].shape[1] == 16
    assert afmoe.cache_spec(cfg).n_experts == 8
    eng = LLMEngine(LLMConfig(model_config=cfg, **ENGINE))
    eng.start()
    try:
        prompts = [_prompt(3, 70), _prompt(4, 25)]
        outs = [eng.result(eng.submit(p, max_tokens=10, temperature=0.0),
                           timeout=300.0) for p in prompts]
        st = eng.engine_stats()
        assert 0 < st["experts_touched_total"] \
            <= st["routed_layer_steps_total"] * 8
    finally:
        eng.shutdown()
    samples = [{"prompt_ids": p, "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": 10} for p, o in zip(prompts, outs)]
    kw = FAM.reference_kwargs(cfg)
    assert kw["held"] == (0, 8)
    assert checks.served_tokens_check(REF, kw, cut, samples, 1e-3,
                                      eos=None)["ok"]


# ---- what window layers are kept out of ------------------------------------

def test_speculation_and_the_tier_are_bypassed_and_counted(params, tmp_path):
    eng = _engine(spec_decode_enabled=True, kv_tier_enabled=True,
                  kv_tier_disk_dir=str(tmp_path))
    try:
        assert not eng._spec_on and not eng._prefix_cache_on
        prompts = [[5, 6, 7, 8] * 6, _prompt(30, 50)]
        outs = [eng.result(eng.submit(p, max_tokens=6, temperature=0.0),
                           timeout=300.0) for p in prompts]
        assert _reference_agrees(params, prompts, outs, 6)["ok"]
        st = eng.engine_stats()
        assert st["spec_bypassed_window"] == 2
        assert st["prefix_bypassed_window"] == 2
        assert st["kv_tier_bypassed_window"] == 2
        assert st["attn_verify_dispatches"] == 0
        assert st["spilled_pages"] == st["restored_pages"] == 0
    finally:
        eng.shutdown()


def test_verify_refuses_a_block_with_window_layers(params):
    kv = kvc.init_paged_cache(CFG, 25, 8, window_pages=2 * RING + 1)
    with pytest.raises(NotImplementedError, match="window layers"):
        kvc.paged_verify_step(
            params, kv, jnp.zeros((2, 24 + RING), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 3), jnp.int32), CFG, 8,
            "gather")


def test_disaggregated_handoff_is_refused_and_counted():
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="window layers"):
        disagg.prefill_only(eng, _prompt(40, 20))
    assert eng.engine_stats()["disagg_refused_window"] == 1
    dec = disagg.DecodeEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="window layers"):
        dec.submit_prefilled({"prompt_tokens": [1, 2], "first_token": 3})
    assert dec.stats["disagg_refused_window"] == 1


def test_tensor_parallel_and_checkpoints_are_refused_by_the_block():
    with pytest.raises(ValueError, match="tp_degree must be 1"):
        block_of(CFG).check_tp_divides(CFG, 2)
    with pytest.raises(ValueError, match="tp_degree must be 1"):
        LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, "tp_degree": 2}))
    with pytest.raises(NotImplementedError, match="checkpoint"):
        afmoe.load_params("/nowhere", CFG)


# ---- the cache spec and the layers -----------------------------------------

def test_cache_spec_layer_definitions_and_parameter_count():
    lds = afmoe.serve_layers(CFG)
    assert [(d.mixer, d.ffn, d.page_layer, d.routed_layer, d.window)
            for d in lds] == [
        ("gated", "dense", 0, -1, 16), ("gated", "routed", 1, 0, 16),
        ("gated", "routed", 2, 1, 16), ("gated", "routed", 0, 2, 0)]
    assert kvc.has_window_layers(CFG) and not kvc.has_slot_state(CFG)
    assert not kvc.has_latent_cache(CFG)
    # the cell's model: s s s f s, the ring pool four layers, 32 of 256
    cell = afmoe.AfmoeConfig(n_layers=5, n_dense=1, experts_held=32)
    spec = afmoe.cache_spec(cell)
    assert (spec.paged_layers, spec.window_layers, spec.window,
            spec.n_experts, spec.top_k) == (1, 4, 4096, 32, 4)
    assert [d.window for d in afmoe.serve_layers(cell)] \
        == [4096, 4096, 4096, 0, 4096]
    assert afmoe.num_params(cell) == 5_398_136_064          # 10.80 GB bf16
    whole = afmoe.AfmoeConfig()
    assert 398e9 < afmoe.num_params(whole) < 399e9
    shapes = jax.eval_shape(
        lambda: afmoe.init_params(jax.random.PRNGKey(0), CFG))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == afmoe.num_params(CFG)


def test_a_window_layer_rotates_and_a_full_layer_does_not(params):
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, CFG.dim), jnp.float32)
    lds = afmoe.serve_layers(CFG)
    far = afmoe.rope_freqs(CFG, jnp.arange(100, 105)[None])
    near = afmoe.rope_freqs(CFG, jnp.arange(5)[None])
    for ld, moves in ((lds[0], True), (lds[3], False)):
        layer = afmoe.serve_params(params, CFG)["layers"][lds.index(ld)]
        q0, k0, v0, g0 = afmoe.serve_gated_qkv(x, layer, *near, CFG, ld)
        q1, k1, v1, g1 = afmoe.serve_gated_qkv(x, layer, *far, CFG, ld)
        assert (float(jnp.abs(q0 - q1).max()) > 1e-3) == moves
        assert (float(jnp.abs(k0 - k1).max()) > 1e-3) == moves
        np.testing.assert_array_equal(v0, v1)
        np.testing.assert_array_equal(g0, g1)
        assert g0.shape == q0.shape and 0 < float(g0.min()) \
            and float(g0.max()) < 1
