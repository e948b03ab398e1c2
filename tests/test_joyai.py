"""The JoyAI-LLM-Flash block (latent attention, routed experts beside a
shared one) through the serving engine (ISSUE 44), on the CPU at the tiny
preset in float32: the absorbed form read off the latent cache against the
expanded form; the engine's greedy tokens against the plain float32
reference (whole prefill, chunked prefill, decode through the cache; with
speculation; with a shared prefix); the latent pool (one array, a page's
bytes, one array a token write, nothing leaks); what a block with a latent
cache is kept out of, each with its counter. Nothing here is a device
number.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from ray_tpu.models import joyai
from ray_tpu.models.block import block_of
from ray_tpu.serve.llm import LLMConfig, LLMEngine, disagg
from ray_tpu.serve.llm import kv_cache as kvc

CFG = joyai.joyai_tiny()
REF = common.load_module("reference", "joyai_f32")
REF_KW = {"theta": CFG.rope_theta, "eps": CFG.norm_eps, "top_k": CFG.top_k,
          "scaling": CFG.scaling}
ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=32, decode_block=4,
              pressure_decode_block=2, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)


@pytest.fixture(scope="module")
def params():
    return joyai.init_params(jax.random.PRNGKey(0), CFG)


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


# ---- absorbed = expanded ---------------------------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_absorbed_off_the_cache_equals_expanded_full_forward(params, backend):
    """One layer's mixer: positions 0..22 written by the expanded prefill,
    position 23 decoded in the absorbed form against the latent pool; its
    output equals the expanded mixer's over all 24 tokens, to rounding."""
    layer, page, n = params["layers"][1], 8, 24
    one = joyai.joyai_tiny(n_layers=1, n_dense=0)     # a pool of one layer
    x = jax.random.normal(jax.random.PRNGKey(3), (1, n, CFG.dim), jnp.float32)
    cos, sin = joyai.rope_freqs(CFG, jnp.arange(n)[None])
    q, k, v, entry = joyai.serve_latent_expanded(x, layer, cos, sin, CFG)
    causal = jnp.tril(jnp.ones((n, n), bool))
    want = joyai.serve_attn_out(kvc._dense_attention(
        q, k, v, causal[None, None], CFG.head_dim ** -0.5), layer)[0, n - 1]

    kv = kvc.init_paged_cache(one, 5, page)
    table = jnp.arange(1, 5, dtype=jnp.int32)
    pos = jnp.arange(n - 1)
    pool = kvc._write_token_rows(kv["k"], 0, entry[:, :n - 1],
                                 table[pos // page][None], (pos % page)[None])
    last = jnp.asarray([n - 1])
    got, new = kvc._latent_mixer(
        x[:, n - 1:], {"k": pool}, layer, None, 0, kvc._Geometry(
            cfg=CFG, cos=cos[:, n - 1:], sin=sin[:, n - 1:],
            page_idx=table[last // page], offset=last % page, lone=1,
            attn_backend=backend, kernel="paged_decode_attention",
            operands=(table[None], last), value_dim=CFG.kv_rank))
    np.testing.assert_allclose(got[0, 0] - x[0, n - 1], want, atol=2e-6)
    # the row the decode wrote is the expanded form's own entry, zero padded
    row = new["k"][0, 0, table[(n - 1) // page], (n - 1) % page]
    np.testing.assert_allclose(row[:CFG.latent_dim], entry[0, n - 1],
                               atol=1e-6)
    assert not np.asarray(row[CFG.latent_dim:]).any()


# ---- the engine, end to end ------------------------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("lengths", [(20,), (70,), (20, 70, 33, 100, 9)],
                         ids=["whole_prefill", "chunked_prefill", "mixed"])
def test_engine_tokens_are_the_references(params, lengths, backend):
    """20 tokens: one whole-prompt program (expanded); 70 and 100: chunks
    of 32 (absorbed, against the pool); then decode through the latent
    cache, several sequences side by side."""
    eng = _engine(attention_kernel=backend)
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
        # 3 layers x 128 lanes (40 numbers padded) x 4 B
        assert st["kv_bytes_per_token"] == 3 * 128 * 4
        assert st["kv_tier_bypassed_latent"] == 0
        assert st["free_pages"] == ENGINE["num_pages"] - 1
    finally:
        eng.shutdown()


def test_shared_prefix_shares_latent_pages_and_streams_the_same(params):
    """Prefix reuse stays on: the second prompt reads the first's pages
    (hits counted) and streams what it streams unshared."""
    head = _prompt(20, 40)
    prompts = [head + _prompt(21, 9), head + _prompt(22, 13)]
    outs = {}
    for shared in (True, False):
        eng = _engine(prefix_cache_enabled=shared)
        try:
            outs[shared] = [eng.result(eng.submit(p, max_tokens=8,
                                                  temperature=0.0),
                                       timeout=120.0) for p in prompts]
            st = eng.engine_stats()
            if shared:
                assert st["prefix_hits"] == 1
                assert st["prefix_hit_tokens"] == 40     # five pages of 8
                assert st["prefix_hit_pages"] == 5
        finally:
            eng.shutdown()
    assert [o["tokens"] for o in outs[True]] \
        == [o["tokens"] for o in outs[False]]
    _reference_agrees(params, prompts, outs[True], 8)


def test_speculation_runs_through_the_latent_pool(params):
    """The verify program is the absorbed form over k + 1 positions a
    slot: a repeating prompt draws n-gram drafts, and the stream is the
    reference's whether or not any draft is taken."""
    eng = _engine(spec_decode_enabled=True)
    try:
        prompts = [[5, 6, 7, 8] * 6]
        outs = [eng.result(eng.submit(prompts[0], max_tokens=16,
                                      temperature=0.0), timeout=120.0)]
        _reference_agrees(params, prompts, outs, 16)
        assert eng.engine_stats()["attn_verify_dispatches"] > 0
    finally:
        eng.shutdown()


def test_200_admissions_and_finishes_leak_no_page(params):
    eng = _engine(max_batch_size=8, num_pages=96)
    try:
        rng = np.random.RandomState(7)
        rids = [eng.submit(_prompt(100 + i, int(rng.randint(3, 40))),
                           max_tokens=int(rng.randint(1, 4)), temperature=0.0)
                for i in range(200)]
        outs = [eng.result(r, timeout=300.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        st = eng.engine_stats()
        # (free_pages: what an admission could obtain, parked prefix
        # pages included; page 0 is the trash page)
        assert st["free_pages"] == 96 - 1 and st["active_slots"] == 0
        assert sorted(eng.free_slots) == list(range(8))
    finally:
        eng.shutdown()


# ---- what a latent cache is kept out of ------------------------------------

def test_kv_tier_is_bypassed_and_counted(params, tmp_path):
    eng = _engine(kv_tier_enabled=True, kv_tier_disk_dir=str(tmp_path))
    try:
        assert eng._kv_tier is None and eng.allocator.spill_hook is None
        prompts = [_prompt(30, 50)]
        outs = [eng.result(eng.submit(prompts[0], max_tokens=6,
                                      temperature=0.0), timeout=120.0)]
        _reference_agrees(params, prompts, outs, 6)
        st = eng.engine_stats()
        assert st["kv_tier_bypassed_latent"] == 1
        assert st["spilled_pages"] == st["restored_pages"] == 0
    finally:
        eng.shutdown()


def test_disaggregated_handoff_is_refused_and_counted():
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="latent row"):
        disagg.prefill_only(eng, _prompt(40, 20))
    assert eng.engine_stats()["disagg_refused_latent"] == 1
    dec = disagg.DecodeEngine(LLMConfig(model_config=CFG, **ENGINE))
    with pytest.raises(NotImplementedError, match="latent row"):
        dec.submit_prefilled({"prompt_tokens": [1, 2], "first_token": 3})
    assert dec.stats["disagg_refused_latent"] == 1


def test_tensor_parallel_is_refused_by_the_block():
    with pytest.raises(ValueError, match="one KV head"):
        block_of(CFG).check_tp_divides(CFG, 2)
    with pytest.raises(ValueError, match="one KV head"):
        LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, "tp_degree": 2}))


# ---- the cache spec and the pool -------------------------------------------

def test_cache_spec_and_layer_definition():
    spec = joyai.cache_spec(CFG)
    assert (spec.paged_layers, spec.n_kv_heads) == (3, 1)
    assert (spec.latent_dim, spec.value_dim, spec.head_dim) == (40, 32, 24)
    assert (spec.routed_layers, spec.top_k, spec.n_experts) == (2, 4, 16)
    assert spec.state_layers == 0 and spec.block_length == 1
    lds = joyai.serve_layers(CFG)
    assert [(d.mixer, d.ffn, d.page_layer, d.routed_layer) for d in lds] == [
        ("latent", "dense", 0, -1), ("latent", "routed", 1, 0),
        ("latent", "routed", 2, 1)]
    assert kvc.has_latent_cache(CFG) and not kvc.has_slot_state(CFG)
    full = joyai.JoyaiConfig(n_layers=5)
    assert joyai.num_params(full) == 5_558_141_952          # 11.12 GB bf16
    assert joyai.cache_spec(full).latent_dim == 576


def test_the_pool_is_one_array_of_padded_rows_and_pages_move_as_one():
    full = joyai.JoyaiConfig(n_layers=5)
    assert kvc.latent_lanes(576) == 640 and kvc.latent_lanes(512) == 512
    # a page of 128 tokens: 5 layers x 128 x 640 lanes x 2 B
    assert kvc.page_raw_nbytes(full, 128) == 5 * 128 * 640 * 2 == 819_200
    kv = kvc.init_paged_cache(CFG, 10, 8)
    assert set(kv) == {"k", "routing"} and kv["k"].shape == (3, 1, 10, 8, 128)
    assert kvc.pool_nbytes(kv) == kv["k"].nbytes
    assert kvc.page_raw_nbytes(CFG, 8) * 10 == kv["k"].nbytes
    # the page operations carry a one-array pool as (pages, None)
    kv = {**kv, "k": kv["k"].at[:, :, 3].set(1.0).at[:, :, 5].set(2.0)}
    bk, bv = kvc.gather_pages(kv, [3, 5, 0])
    assert bv is None and bk.shape == (3, 1, 3, 8, 128)
    hk, hv = kvc.fetch_pages(bk, bv, 2)
    assert hv is None and hk.shape[2] == 2
    pk, pv = kvc.pack_pages([(hk[:, :, :1], None), (hk[:, :, 1:], None)], 4)
    assert pv is None and pk.shape[2] == 4 and not pk[:, :, 2:].any()
    zk, zv = kvc.zero_pages(kv, 2)
    assert zv is None and zk.shape[2] == 2
    back = kvc.scatter_pages(kv, jnp.asarray(pk), None,
                             jnp.asarray([7, 8, 0, 0]))
    assert "v" not in back
    assert float(back["k"][0, 0, 7, 0, 0]) == 1.0
    assert float(back["k"][2, 0, 8, 7, 127]) == 2.0


def test_a_token_write_is_one_scatter_into_the_one_array(params):
    """The decode program of the tiny model: one scatter a layer into the
    pool, none into a second array, and no pool-shaped copy in the jaxpr's
    own ops (the compiled program is held on a described v5e in
    tests/test_flash_attention.py)."""
    kv = kvc.init_paged_cache(CFG, 9, 8)
    jaxpr = jax.make_jaxpr(lambda p, kv, t, sl, x: kvc.paged_decode_step(
        p, kv, t, sl, x, CFG, 8, "gather"))(
        params, kv, jnp.zeros((2, 8), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32))
    pool_shape = kv["k"].shape
    scatters = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scatter"
                and e.outvars[0].aval.shape == pool_shape]
    assert len(scatters) == CFG.n_layers
    copies = [e for e in jaxpr.jaxpr.eqns
              if e.primitive.name in ("copy", "concatenate", "pad")
              and e.outvars[0].aval.shape == pool_shape]
    assert not copies
