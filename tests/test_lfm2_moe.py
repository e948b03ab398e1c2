"""LFM2-MoE through the serving engine (ISSUE 35), on the CPU at the tiny
preset in float32: the engine's greedy tokens against the plain float32
reference (whole prefill, chunked prefill with the conv state carried,
decode through cache and state, a slot and its state row reused after
another sequence); the expert layer's shares; dropless routing under skew;
what a block with slot state is kept out of, each with its counter; the
Pallas kernels (interpreted) at heads of 64 against the gather backend.
Nothing here is a device number.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from ray_tpu.models import lfm2_moe
from ray_tpu.models.block import block_of
from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.parallel import expert
from ray_tpu.serve.llm import LLMConfig, LLMEngine, disagg
from ray_tpu.serve.llm import kv_cache as kvc

CFG = lfm2_moe.lfm2_moe_tiny()
REF = common.load_module("reference", "lfm2_moe_f32")
REF_KW = {"theta": CFG.rope_theta, "eps": CFG.norm_eps, "top_k": CFG.top_k,
          "scaling": CFG.scaling}
ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=32, decode_block=4,
              pressure_decode_block=2, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)


@pytest.fixture(scope="module")
def params():
    return lfm2_moe.init_params(jax.random.PRNGKey(0), CFG)


def _engine(**over):
    eng = LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, **over}))
    eng.start()
    return eng


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, 250, size=n)]


def _reference_agrees(params, prompts, outs, max_tokens):
    """Every served token is the float32 reference's best at its position
    (teacher-forced: check 2's function, margin a rounding's)."""
    samples = [{"prompt_ids": p, "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": max_tokens} for p, o in zip(prompts, outs)]
    got = checks.served_tokens_check(REF, REF_KW, params, samples, 1e-3,
                                     eos=None)
    assert got["ok"] and got["tokens_checked"] > 0, got


@pytest.mark.parametrize("lengths", [(20,), (70,), (20, 70, 33, 100, 9)],
                         ids=["whole_prefill", "chunked_prefill", "mixed"])
def test_engine_tokens_are_the_references(params, lengths):
    """20 tokens: one whole-prompt program; 70 and 100: chunks of 32 with
    the conv state carried from chunk to chunk; then decode through pages
    and state, several sequences side by side."""
    eng = _engine()
    try:
        prompts = [_prompt(i, n) for i, n in enumerate(lengths)]
        rids = [eng.submit(p, max_tokens=12, temperature=0.0)
                for p in prompts]
        outs = [eng.result(r, timeout=120.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        _reference_agrees(params, prompts, outs, 12)
        st = eng.engine_stats()
        assert st["routed_layer_steps_total"] > 0
        assert 0 < st["experts_touched_total"] \
            <= st["routed_layer_steps_total"] * CFG.n_experts
        assert st["expert_rows_total"] > 0 and st["state_slots_in_use"] == 0
    finally:
        eng.shutdown()


def test_a_slot_and_its_state_row_reused_after_another_sequence(params):
    """One slot: the second and third sequences take the slot, the pages
    and so the state rows the first left full."""
    eng = _engine(max_batch_size=1, num_pages=26)
    try:
        prompts = [_prompt(10, 90), _prompt(11, 17), _prompt(12, 40)]
        outs = [eng.result(eng.submit(p, max_tokens=10, temperature=0.0),
                           timeout=120.0) for p in prompts]
        _reference_agrees(params, prompts, outs, 10)
    finally:
        eng.shutdown()


def test_the_state_still_rides_the_page_table_a_row_a_page(params):
    """ISSUE 60 gave the allocator a reserved range of first pages for a
    block whose state pool has a row a SLOT; this block's pool keeps a row
    a PAGE, any free page may be a sequence's first, and a sequence's state
    row is that page."""
    spec = lfm2_moe.cache_spec(CFG)
    assert not spec.state_per_slot and not spec.state_arrays
    eng = _engine()
    try:
        assert eng.allocator.first_pages == 0
        assert all(a.shape == (ENGINE["num_pages"], 2 * CFG.dim)
                   for a in eng.kv["state"])
        st = eng.engine_stats()
        assert st["state_rows"] == ENGINE["num_pages"]
        assert st["first_pages_free"] == 0
        assert set(st["state_pool_bytes"]) == {"taps"}
        assert st["state_bytes_per_slot"] == 3 * 2 * CFG.dim * 4
        # a first page far up the pool: the sequence's state lands in its row
        held = eng.allocator.alloc(40)
        prompt = _prompt(40, 21)
        out = eng.result(eng.submit(prompt, max_tokens=6, temperature=0.0),
                         timeout=120.0)
        _reference_agrees(params, [prompt], [out], 6)
        rows = [int(r) for r in np.flatnonzero(
            np.abs(np.asarray(eng.kv["state"][0])).sum(axis=1))]
        assert set(rows) - {0} == {41}      # (idle lanes: the trash row)
        eng.allocator.free(held)
    finally:
        eng.shutdown()


# ---- the expert layer ------------------------------------------------------

def _plain_routed(g, moe, idx, w):
    """Token by token, expert by expert, in float64 numpy."""
    g = np.asarray(g, np.float64)
    out = np.zeros_like(g)
    for n in range(g.shape[0]):
        for e, we in zip(np.asarray(idx[n]), np.asarray(w[n], np.float64)):
            gate = g[n] @ np.asarray(moe["w_gate"][e], np.float64)
            up = g[n] @ np.asarray(moe["w_up"][e], np.float64)
            out[n] += we * ((gate / (1 + np.exp(-gate)) * up)
                            @ np.asarray(moe["w_down"][e], np.float64))
    return out


def _held(moe, held):
    return {k: moe[k][held.start:held.stop]
            for k in ("w_gate", "w_up", "w_down")}


def test_four_shares_of_two_experts_add_up_to_the_whole_layer(params):
    moe = params["layers"][2]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(3), (37, CFG.dim), jnp.float32)
    idx, w = expert.route_sigmoid_top_k(g, moe["router"], moe["bias"],
                                        CFG.top_k)       # the router: once
    whole = expert.expert_share(g, idx, w, moe, range(CFG.n_experts))
    shares = [expert.expert_share(g, idx, w, _held(moe, held), held)
              for held in (range(0, 2), range(2, 4), range(4, 6),
                           range(6, 8))]
    want = _plain_routed(g, moe, idx, w)
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(sum(shares)), want, atol=2e-5)
    assert all(float(jnp.max(jnp.abs(s))) > 0 for s in shares)


def test_routing_selects_under_the_bias_and_weighs_without_it(params):
    moe = params["layers"][2]["moe"]
    g = jax.random.normal(jax.random.PRNGKey(4), (16, CFG.dim), jnp.float32)
    bias = jnp.zeros((CFG.n_experts,)).at[6].set(10.0)   # always chosen
    idx, w = expert.route_sigmoid_top_k(g, moe["router"], bias, CFG.top_k)
    s = jax.nn.sigmoid(g @ moe["router"])
    assert bool(jnp.all(jnp.any(idx == 6, axis=-1)))
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(chosen / (chosen.sum(-1, keepdims=True)
                                            + 1e-6)), rtol=1e-6)


def test_dropless_when_every_token_lands_on_one_expert(params):
    """A router skewed onto expert 3: all 64 tokens take it (and one more
    expert each); none is dropped, whatever a capacity would have been."""
    moe = dict(params["layers"][3]["moe"])
    moe["bias"] = jnp.zeros((CFG.n_experts,)).at[3].set(10.0)
    g = jax.random.normal(jax.random.PRNGKey(5), (64, CFG.dim), jnp.float32)
    idx, w = expert.route_sigmoid_top_k(g, moe["router"], moe["bias"],
                                        CFG.top_k)
    assert int(jnp.sum(idx == 3)) == 64
    got = expert.expert_share(g, idx, w, moe, range(CFG.n_experts))
    np.testing.assert_allclose(np.asarray(got),
                               _plain_routed(g, moe, idx, w), atol=2e-5)


# ---- what a block with slot state is kept out of ---------------------------

def test_shared_prefix_is_bypassed_counted_and_correct(params):
    """Two prompts share four pages: no page is shared, both are right."""
    eng = _engine(prefix_cache_enabled=True)
    try:
        head = _prompt(20, 40)
        prompts = [head + _prompt(21, 9), head + _prompt(22, 13)]
        outs = [eng.result(eng.submit(p, max_tokens=8, temperature=0.0),
                           timeout=120.0) for p in prompts]
        _reference_agrees(params, prompts, outs, 8)
        st = eng.engine_stats()
        assert st["prefix_bypassed_stateful"] == 2
        assert st["prefix_hits"] == 0 and st["prefix_hit_tokens"] == 0
        assert st["prefix_summary_pages"] == 0
    finally:
        eng.shutdown()


def test_speculation_is_bypassed_counted_and_correct(params):
    eng = _engine(spec_decode_enabled=True)
    try:
        prompts = [[5, 6, 7, 8] * 6]            # an n-gram draft would fire
        outs = [eng.result(eng.submit(prompts[0], max_tokens=16,
                                      temperature=0.0), timeout=120.0)]
        _reference_agrees(params, prompts, outs, 16)
        st = eng.engine_stats()
        assert st["spec_bypassed_stateful"] == 1 and st["spec_rounds"] == 0
        assert st["attn_verify_dispatches"] == 0
    finally:
        eng.shutdown()


def test_the_verify_program_refuses_a_block_with_slot_state(params):
    kv = kvc.init_paged_cache(CFG, 8, 8)
    with pytest.raises(NotImplementedError, match="slot state"):
        kvc.paged_verify_step(params, kv, jnp.zeros((2, 4), jnp.int32),
                              jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2, 3), jnp.int32), CFG, 8)


def test_kv_tier_restore_is_bypassed_and_counted(params, tmp_path):
    eng = _engine(prefix_cache_enabled=True, kv_tier_enabled=True,
                  kv_tier_disk_dir=str(tmp_path))
    try:
        assert eng._kv_tier is None and eng.allocator.spill_hook is None
        prompts = [_prompt(30, 50)]
        outs = [eng.result(eng.submit(prompts[0], max_tokens=6,
                                      temperature=0.0), timeout=120.0)]
        _reference_agrees(params, prompts, outs, 6)
        st = eng.engine_stats()
        assert st["kv_tier_bypassed_stateful"] == 1
        assert st["spilled_pages"] == st["restored_pages"] == 0
    finally:
        eng.shutdown()


def test_disaggregated_handoff_is_refused_and_counted():
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="per-sequence state"):
        disagg.prefill_only(eng, _prompt(40, 20))
    assert eng.engine_stats()["disagg_refused_stateful"] == 1
    dec = disagg.DecodeEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="per-sequence state"):
        dec.submit_prefilled({"prompt_tokens": [1, 2], "first_token": 3})
    assert dec.stats["disagg_refused_stateful"] == 1


def test_tensor_parallel_is_refused_by_the_block():
    with pytest.raises(ValueError, match="tensor-parallel"):
        block_of(CFG).check_tp_divides(CFG, 2)


# ---- the cache spec and the pool -------------------------------------------

def test_cache_spec_and_layer_definition():
    cfg = lfm2_moe.Lfm2MoeConfig(layer_types=(
        "conv", "conv", "full_attention", "conv") * 4)
    spec = lfm2_moe.cache_spec(cfg)
    assert (spec.paged_layers, spec.state_layers, spec.routed_layers) \
        == (4, 12, 14)
    assert spec.state_shape == (2, 2048) and spec.head_dim == 64
    layers = lfm2_moe.serve_layers(cfg)
    assert [ld.mixer for ld in layers[:4]] == ["conv", "conv", "attn", "conv"]
    assert [ld.ffn for ld in layers[:3]] == ["dense", "dense", "routed"]
    assert [ld.page_layer for ld in layers if ld.mixer == "attn"] \
        == [0, 1, 2, 3]
    assert [ld.routed_layer for ld in layers[2:]] == list(range(14))
    # 5.399 B parameters at depth 16 (the configuration's arithmetic)
    assert lfm2_moe.num_params(cfg) == (
        14 * (352_321_536 + 65_536 + 32) + 12 * 16_783_360 + 4 * 10_485_888
        + 2 * 44_040_192 + 134_217_728 + 16 * 2 * 2048 + 2048)


def test_heads_of_64_lie_two_to_a_pool_row_and_state_is_flat():
    assert kvc.pool_heads_lanes(8, 64) == (4, 128)
    assert kvc.pool_heads_lanes(8, 128) == (8, 128)
    assert kvc.pool_heads_lanes(2, 16) == (2, 16)
    cfg = lfm2_moe.lfm2_moe_tiny(head_dim=64)
    kv = kvc.init_paged_cache(cfg, 10, 8)
    assert kv["k"].shape == (1, 1, 10, 8, 128)
    assert len(kv["state"]) == 3 and kv["state"][0].shape == (10, 2 * 64)
    assert kv["routing"].shape == (2, cfg.max_seq_len, 2)
    assert kvc.has_slot_state(cfg)
    assert paged_ops.can_tile(64, 128, jnp.bfloat16)
    assert not paged_ops.can_tile(32, 128, jnp.bfloat16)


@pytest.mark.parametrize("hkv", [2, 4])
def test_pallas_kernels_at_heads_of_64_equal_the_gather_backend(hkv):
    """The interpreted kernels on the packed pool (two heads a 128-lane
    row) against the gather backend, through the paged programs: chunked
    prefill, then decode."""
    cfg = lfm2_moe.lfm2_moe_tiny(head_dim=64, n_heads=8, n_kv_heads=hkv)
    p = lfm2_moe.init_params(jax.random.PRNGKey(1), cfg)
    page = 8
    toks = np.random.RandomState(2).randint(0, 500, (1, 48)).astype(np.int32)
    table = np.zeros((24,), np.int32)
    table[:8] = 1 + np.arange(8)
    tables = np.stack([table, np.zeros_like(table)])
    got = {}
    for backend in ("gather", "pallas"):
        kv = kvc.init_paged_cache(cfg, 16, page)
        for start in (0, 32):
            chunk = toks[:, start:start + 32]
            lg, kv = kvc.paged_prefill_chunk(
                p, kv, table, np.pad(chunk, ((0, 0), (0, 32 - chunk.shape[1]))),
                jnp.int32(start), jnp.int32(45), cfg, page, backend)
        dl, kv, _ = kvc.paged_decode_step(
            p, kv, tables, jnp.asarray([45, 0]), jnp.asarray([7, 0]), cfg,
            page, backend)
        got[backend] = (lg, dl[0])
    for a, b in zip(got["gather"], got["pallas"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
