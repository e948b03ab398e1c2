"""The MiMo-V2-Flash block (``model_type`` ``mimo_v2_flash``: query and key
heads wider than value heads, a KV-head count, a theta and a learned sink a
layer KIND, a window of ONE page, routed experts with no shared one, one
chip's share of the experts) through the serving engine (ISSUE 55), on the
CPU at the tiny preset in float32 (heads of 24 lanes on values of 16, 2 KV
heads in a full layer and 4 in a window layer, window 8 = pages of 8,
chunks of 32: a ring of 6 pages = 48 positions): the paged programs' LOGITS
and the engine's greedy tokens against the plain float32 reference, whose
window is a band in a mask and whose sink one more column of the softmax,
through a whole prefill, a chunked prefill and decode past the window, the
ring's wrap and window + ring, on the gather and the pallas (interpreted)
backends; the pools at their own heads and widths; the shares of a
partition of the experts adding up to the uncut layer. Nothing here is a
device number.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from ray_tpu.models import mimo
from ray_tpu.models.block import block_of
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm import kv_cache as kvc

CFG = mimo.mimo_tiny()
FAM = common.load_module("models", "mimo_v2_flash")
REF = common.load_module("reference", "mimo_v2_flash_f32")
REF_KW = FAM.reference_kwargs(CFG)
PAGE, CHUNK = 8, 32
ENGINE = dict(max_batch_size=4, page_size=PAGE, num_pages=97,
              max_prompt_len=128, max_seq_len=192, prefill_chunk=CHUNK,
              decode_block=4, pressure_decode_block=2, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)
RING = 6        # (window 8 + chunk 32) / 8 + 1
FULL_W = 192 // PAGE


@pytest.fixture(scope="module")
def params():
    return mimo.init_params(jax.random.PRNGKey(0), CFG)


def _engine(cfg=CFG, **over):
    eng = LLMEngine(LLMConfig(model_config=cfg, **{**ENGINE, **over}))
    eng.start()
    return eng


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, 250, size=n)]


def _reference_agrees(params, prompts, outs, max_tokens, cfg=CFG, **ref_kw):
    """Every served token is the float32 reference's best at its position
    (teacher-forced: check 2's function, margin a rounding's)."""
    samples = [{"prompt_ids": p, "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": max_tokens} for p, o in zip(prompts, outs)]
    got = checks.served_tokens_check(
        REF, {**FAM.reference_kwargs(cfg), **ref_kw}, params, samples, 1e-3,
        eos=None)
    assert got["tokens_checked"] > 0
    return got


# ---- the paged programs against the reference, logits ----------------------

@pytest.fixture(scope="module")
def reference_logits(params):
    toks = np.random.RandomState(11).randint(0, 250, size=(1, 150))
    return toks, np.asarray(REF.logits_at(params, toks, np.arange(150),
                                          **REF_KW)[0])


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("phase", ["whole_prefill_then_decode",
                                   "chunked_prefill_then_decode"])
def test_paged_programs_give_the_references_logits(params, reference_logits,
                                                   backend, phase):
    """A whole prompt of 20 then 60 decode steps (past the window at 8, the
    ring's wrap at 48 and window + ring at 56, across nine page edges); a
    prompt of 110 in chunks of 32 (the second past the window, the third
    wraps the ring, the last partial) then 30 decode steps across the page
    edges at 112 ... 136: every logit the reference's full forward pass
    gives at that position, to float32 rounding."""
    toks, want = reference_logits
    FAM._RING_SPAN[CFG] = CHUNK
    init, prefill, chunk, decode = FAM.build_programs(CFG, PAGE, backend)
    kv = init(1 + 2 * FULL_W)
    table = jnp.arange(1, 1 + FULL_W)[None]
    worst = 0.0
    if phase.startswith("whole"):
        at = 20
        lg, kv = prefill(params, kv, table[0], jnp.asarray(toks[:, :at]),
                         jnp.int32(at))
        stop = 80
    else:
        at, stop = 110, 140
        for s in range(0, at, CHUNK):
            c = toks[:, s:min(s + CHUNK, at)]
            c = np.pad(c, ((0, 0), (0, CHUNK - c.shape[1])))
            lg, kv = chunk(params, kv, table[0], jnp.asarray(c),
                           jnp.int32(s), jnp.int32(at))
    worst = float(np.abs(np.asarray(lg) - want[at - 1]).max())
    for p in range(at, stop):
        lg, kv, _ = decode(params, kv, table, jnp.array([p]),
                           jnp.asarray(toks[:, p]))
        worst = max(worst, float(np.abs(np.asarray(lg[0]) - want[p]).max()))
    assert float(np.abs(want).max()) > 0.5
    assert worst < 5e-5, worst


# ---- the engine, end to end ------------------------------------------------

@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("lengths,max_tokens", [
    ((20,), 50), ((70,), 12), ((20, 70, 33, 100, 9), 12)],
    ids=["whole_prefill", "chunked_prefill", "mixed"])
def test_engine_tokens_are_the_references(params, lengths, max_tokens,
                                          backend):
    """20 tokens: one whole-prompt program, then 50 decode steps, past the
    window (8) and the ring's first wrap (48); 70 and 100: chunks of 32,
    whose second wraps the ring, then decode across page edges."""
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
        assert st["free_pages"] == ENGINE["num_pages"] - 1
        assert st["window_pages_in_use"] == st["full_pages_in_use"] == 0
        assert eng.window_allocator.available() == 4 * RING
        assert st["prefix_bypassed_window"] == sum(n > 8 for n in lengths)
        walks = ["decode", "chunk"] if backend == "pallas" else []
        assert st["attn_walks_live"] == st["attn_writes_in_kernel"] \
            == st["attn_sink_calls"] == walks
    finally:
        eng.shutdown()


@pytest.mark.parametrize("wrong", [
    {"window": 7}, {"window": 9}, {"window_sink": False},
    {"full_sink": mimo.SINK_MEAN}, {"value_scale": 1.0}, {"rotary": 24},
    {"theta_full": 10000.0, "theta_window": 5000000.0}],
    ids=["window_7", "window_9", "no_sink", "sink_in_full_layers",
         "no_value_scale", "every_lane_rotated", "thetas_swapped"])
def test_a_reference_with_one_rule_wrong_disagrees(params, wrong):
    """The same streams against a reference whose window is one token short
    or long, that leaves the sink out of the window layers or adds one to
    the full layers, does not scale the values, rotates every lane, or has
    the two thetas the other way round: refused."""
    eng = _engine()
    try:
        prompts = [_prompt(7, 70), _prompt(8, 100)]
        outs = [eng.result(eng.submit(p, max_tokens=24, temperature=0.0),
                           timeout=300.0) for p in prompts]
    finally:
        eng.shutdown()
    assert _reference_agrees(params, prompts, outs, 24)["ok"]
    assert not _reference_agrees(params, prompts, outs, 24, **wrong)["ok"]


# ---- the pools -------------------------------------------------------------

def test_pools_hold_each_kind_at_its_own_heads_and_widths():
    """Key rows of 24 lanes on whole vectors beside value rows of 16; 2 KV
    heads in the growing pool, 4 in the rings'; one page of window."""
    eng = LLMEngine(LLMConfig(model_config=CFG, **ENGINE))
    spec = mimo.cache_spec(CFG)
    assert (spec.paged_layers, spec.window_layers, spec.window,
            spec.n_kv_heads, spec.window_kv_heads, spec.head_dim,
            spec.value_dim) == (2, 3, 8, 2, 4, 24, 16)
    n = ENGINE["num_pages"]
    assert eng.kv["k"].shape == (2, 2, n, PAGE, 128)
    assert eng.kv["v"].shape == (2, 2, n, PAGE, 16)
    assert eng.kv["kw"].shape == (3, 4, 4 * RING + 1, PAGE, 128)
    assert eng.kv["vw"].shape == (3, 4, 4 * RING + 1, PAGE, 16)
    assert eng.page_tables.shape == (4, FULL_W + RING)
    st = eng.engine_stats()
    assert st["pool_lanes"] == {"k": [24, 128], "v": [16, 16]}
    assert st["kv_bytes_per_token"] == 4 * (2 * 2 + 3 * 4) * (128 + 16)
    assert st["attn_sink_calls"] == []          # the gather backend
    assert kvc.key_lanes(192) == 256 and kvc.key_lanes(24) == 128
    # at the cell's size: a window of one page, four pages a prefill chunk
    assert kvc.ring_pages(128, 128, 512) == 6


def test_the_kernel_tiles_key_rows_of_192_stored_on_256(monkeypatch):
    from ray_tpu.ops import paged_attention as paged_ops
    assert paged_ops.can_tile(256, 128, jnp.bfloat16, value_dim=128)
    assert not paged_ops.can_tile(192, 128, jnp.bfloat16, value_dim=128)
    assert not paged_ops.can_tile(256, 128, jnp.bfloat16, value_dim=96)
    monkeypatch.setattr(kvc.jax, "default_backend", lambda: "tpu")
    cell = mimo.MimoConfig(n_layers=7, pattern=(0, 1, 1, 1, 1, 0, 1),
                           moe_freq=(0,) + (1,) * 6, experts_held=16)
    assert kvc.resolve_attention_backend("auto", cell, 128) == "pallas"
    assert kvc.resolve_attention_backend("auto", CFG, 8) == "gather"


# ---- one chip's share of the experts ---------------------------------------

def test_the_shares_of_a_partition_add_up_to_the_uncut_layer(params):
    """The routed layer of the model on 8 chips of 2 experts each (chip c
    holds the experts the router scores in columns 2c, 2c + 1: its model
    is the block with ``experts_held`` 2 and the router's columns rolled so
    that its own come first): the 8 shares add up to the plain reference's
    uncut layer (no shared expert to count once), and each share is the
    reference's ``held``."""
    lp = params["layers"][2]
    moe, n, per = lp["moe"], CFG.n_experts, 2
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.dim), jnp.float32)
    g = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.norm_eps) \
        * lp["ffn_norm"]
    kw = dict(eps=CFG.norm_eps, top_k=CFG.top_k, use_bias=True)
    uncut = REF._routed(x, lp, None, held=(0, n), **kw)[0] - x
    chip = mimo.mimo_tiny(experts_held=per)
    total = 0.0
    for lo in range(0, n, per):
        mine = {**moe, "router": jnp.roll(moe["router"], -lo, axis=1),
                "bias": jnp.roll(moe["bias"], -lo),
                **{k: moe[k][lo:lo + per]
                   for k in ("w_gate", "w_up", "w_down")}}
        share, idx = mimo.routed_parts(g, mine, chip)
        assert idx.shape == (24, CFG.top_k) and int(idx.max()) < n
        want = REF._routed(x, {**lp, "moe": {**moe, **{
            k: moe[k][lo:lo + per] for k in ("w_gate", "w_up", "w_down")}}},
            None, held=(lo, lo + per), **kw)[0] - x
        np.testing.assert_allclose(share, want, atol=2e-5)
        total = total + share
    assert float(jnp.abs(uncut).max()) > 0.1
    np.testing.assert_allclose(total, uncut, atol=5e-5)


def test_a_share_of_the_experts_serves_the_references_share():
    """The engine on a block that holds 8 of its router's 16 experts: the
    streams are the reference's with ``held=(0, 8)``."""
    cfg = mimo.mimo_tiny(experts_held=8)
    cut = mimo.init_params(jax.random.PRNGKey(0), cfg)
    assert cut["layers"][1]["moe"]["w_gate"].shape[0] == 8
    assert cut["layers"][1]["moe"]["router"].shape[1] == 16
    assert mimo.cache_spec(cfg).n_experts == 8
    eng = _engine(cfg)
    try:
        prompts = [_prompt(3, 70), _prompt(4, 25)]
        outs = [eng.result(eng.submit(p, max_tokens=10, temperature=0.0),
                           timeout=300.0) for p in prompts]
        st = eng.engine_stats()
        assert 0 < st["experts_touched_total"] \
            <= st["routed_layer_steps_total"] * 8
    finally:
        eng.shutdown()
    assert FAM.reference_kwargs(cfg)["held"] == (0, 8)
    assert _reference_agrees(cut, prompts, outs, 10, cfg)["ok"]


# ---- the block -------------------------------------------------------------

def test_cache_spec_layer_definitions_and_parameter_count():
    lds = mimo.serve_layers(CFG)
    assert [(d.mixer, d.ffn, d.page_layer, d.routed_layer, d.window, d.sink)
            for d in lds] == [
        ("sink", "dense", 0, -1, 0, False), ("sink", "routed", 0, 0, 8, True),
        ("sink", "routed", 1, 1, 8, True), ("sink", "routed", 2, 2, 8, True),
        ("sink", "routed", 1, 3, 0, False)]
    assert kvc.has_window_layers(CFG) and not kvc.has_slot_state(CFG)
    assert not kvc.has_latent_cache(CFG)
    # the cell's model: f s s s s f s, 16 of 256 experts held
    cell = mimo.MimoConfig(n_layers=7, pattern=(0, 1, 1, 1, 1, 0, 1),
                           moe_freq=(0,) + (1,) * 6, experts_held=16)
    spec = mimo.cache_spec(cell)
    assert (spec.paged_layers, spec.window_layers, spec.window,
            spec.n_experts, spec.top_k, spec.routed_layers) \
        == (2, 5, 128, 16, 8, 6)
    assert [d.window for d in mimo.serve_layers(cell)] \
        == [0, 128, 128, 128, 128, 0, 128]
    assert mimo.num_params(cell) == 4_523_620_160           # 9.05 GB bf16
    whole = mimo.MimoConfig()
    assert sum(whole.pattern) == 39 and whole.pattern[:7] == (
        0, 1, 1, 1, 1, 0, 1) and whole.pattern[-1] == 0
    assert 308e9 < mimo.num_params(whole) < 310e9
    shapes = jax.eval_shape(
        lambda: mimo.init_params(jax.random.PRNGKey(0), CFG))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == mimo.num_params(CFG)
    with pytest.raises(ValueError, match="state a kind"):
        mimo.serve_layers(mimo.mimo_tiny(n_layers=6))


def test_a_layer_kind_rotates_its_first_lanes_by_its_own_theta(params):
    """The first 8 lanes of a head move with the position, by another
    angle in a window layer than in a full one; the other 16 and the values
    never do; the values are scaled; a window layer hands its sinks on."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, CFG.dim), jnp.float32)
    lds = mimo.serve_layers(CFG)
    served = mimo.serve_params(params, CFG)["layers"]
    near = mimo.rope_freqs(CFG, jnp.arange(5)[None])
    far = mimo.rope_freqs(CFG, jnp.arange(100, 105)[None])
    assert float(jnp.abs(far[0][0] - far[0][1]).max()) > 0.1    # two thetas
    for i, heads in ((0, 2), (1, 4)):
        q0, k0, v0, s0 = mimo.serve_sink_qkv(x, served[i], *near, CFG, lds[i])
        q1, k1, v1, s1 = mimo.serve_sink_qkv(x, served[i], *far, CFG, lds[i])
        assert q0.shape == (1, 5, 8, 24) and k0.shape == (1, 5, heads, 24)
        assert v0.shape == (1, 5, heads, 16)
        r = CFG.rotary_dim
        assert float(jnp.abs(q0[..., :r] - q1[..., :r]).max()) > 1e-3
        assert float(jnp.abs(k0[..., :r] - k1[..., :r]).max()) > 1e-3
        np.testing.assert_array_equal(q0[..., r:], q1[..., r:])
        np.testing.assert_array_equal(k0[..., r:], k1[..., r:])
        np.testing.assert_array_equal(v0, v1)
        plain = jnp.einsum("btd,dhk->bthk", mimo.rms_norm(
            x, served[i]["attn_norm"], CFG.norm_eps),
            params["layers"][i]["attn"]["wv"])
        np.testing.assert_allclose(v0, 0.707 * plain, rtol=1e-5, atol=1e-6)
        assert (s0 is None) == (i == 0)
    assert served[1]["attn"]["sink"].dtype == jnp.float32
    assert 2.0 < float(served[1]["attn"]["sink"].mean()) < 6.0


def test_tensor_parallel_and_checkpoints_are_refused_by_the_block():
    with pytest.raises(ValueError, match="tp_degree must be 1"):
        block_of(CFG).check_tp_divides(CFG, 2)
    with pytest.raises(ValueError, match="tp_degree must be 1"):
        LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, "tp_degree": 2}))
    with pytest.raises(NotImplementedError, match="checkpoint"):
        mimo.load_params("/nowhere", CFG)
