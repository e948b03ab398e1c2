"""A DENSE block with heads of 64 on the packed pool (two KV heads to a
128-lane row, kv_cache.pool_heads_lanes): not only the LFM2 block gets
that layout, so the dense block is driven through everything that moves
pages on it: tensor parallelism, prefix reuse, speculative verify, the kv
tier's spill and restore, and a disaggregated handoff. And where a chip's
share of KV heads is odd the pool stays a head a row, off the Pallas
kernels, as it ran before heads of 64 were packed.
"""

import time
import types

import jax.numpy as jnp
import pytest

from ray_tpu.models import lfm2_moe, llama
from ray_tpu.ops import paged_attention as paged_ops
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm import kv_cache as kvc

PROMPT = "the quick brown fox jumps over the lazy dog"   # 43 byte-tokens
LONG = PROMPT + " " + PROMPT                             # 87 -> 5 full pages


def _cfg(n_kv_heads=4, **kw):
    # dim 256 over 4 heads: heads of 64. Pool geometry of test_tp_serving:
    # a cap-2 prefix cache evicts (and the tier spills) the chain's head
    d = dict(model_config=llama.llama_tiny(
                 vocab_size=512, dim=256, n_heads=4, n_kv_heads=n_kv_heads),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=96, max_seq_len=160, max_tokens=8,
             prefix_cache_max_pages=2, kv_tier_enabled=True)
    d.update(kw)
    return LLMConfig(**d)


_WANT: dict = {}


def _want_tokens(n_kv_heads, prompt=LONG):
    """Greedy tokens of a one-chip engine on the gather backend with
    prefix cache and tier off."""
    if (n_kv_heads, prompt) not in _WANT:
        off = LLMEngine(_cfg(n_kv_heads, kv_tier_enabled=False,
                             prefix_cache_enabled=False,
                             attention_kernel="gather"), rng_seed=0)
        off.start()
        try:
            for text in (LONG, PROMPT):
                _WANT[n_kv_heads, text] = off.generate(
                    text, max_tokens=8, temperature=0.0)["tokens"]
        finally:
            off.shutdown()
    return _WANT[n_kv_heads, prompt]


def _wait(pred, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not pred():
        time.sleep(0.02)
    return pred()


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_dense_heads_of_64_full_stack_on_the_packed_pool(backend, tp):
    want = _want_tokens(4)
    eng = LLMEngine(_cfg(4, tp_degree=tp, attention_kernel=backend,
                         spec_decode_enabled=True, spec_draft_len=2),
                    rng_seed=0)
    eng.start()
    try:
        k = eng.kv["k"]
        assert k.shape == (2, 2, 64, 16, 128)        # 4 heads of 64: 2 rows
        assert k.sharding.shard_shape(k.shape)[1] == 2 // tp
        assert eng.engine_stats()["attention_backend"] == backend
        cold = eng.generate(LONG, temperature=0.0)
        assert cold["error"] is None and cold["tokens"] == want
        # the chain's head was evicted and spilled: the rerun restores it
        assert _wait(lambda: eng.engine_stats()["spilled_pages"] >= 3)
        assert eng.generate(LONG, temperature=0.0)["tokens"] == want
        st = eng.engine_stats()
        assert st["restored_pages"] >= 3 and st["tier_hit_tokens"] >= 48
        # a prompt whose indexed page is still there reuses it in place
        for _ in range(2):
            assert eng.generate(PROMPT, temperature=0.0)["tokens"] \
                == _want_tokens(4, PROMPT)
        assert eng.engine_stats()["prefix_hit_tokens"] >= 16
    finally:
        eng.shutdown()


def test_dense_heads_of_64_disaggregated_handoff_on_the_packed_pool():
    from ray_tpu.serve.llm.disagg import DecodeEngine, prefill_only
    cfg = _cfg(4, kv_tier_enabled=False, prefix_cache_enabled=False)
    want = _want_tokens(4)
    pre = LLMEngine(cfg, rng_seed=0)              # prefill role: no loop
    dec = DecodeEngine(cfg, rng_seed=0)
    dec.start()
    try:
        state = prefill_only(pre, LONG, temperature=0.0)
        assert state["kv_k"].shape[1] == 2 and state["kv_k"].shape[4] == 128
        got = dec.result(dec.submit_prefilled(state, max_tokens=8),
                         timeout=120.0)
        assert got["error"] is None and got["tokens"] == want
    finally:
        dec.shutdown()


def test_an_odd_share_of_heads_of_64_stays_a_head_a_row(monkeypatch):
    assert kvc.pool_heads_lanes(8, 64, 4) == (4, 128)
    assert kvc.pool_heads_lanes(8, 64, 8) == (8, 64)     # one head a chip
    assert kvc.pool_heads_lanes(3, 64) == (3, 64)
    assert paged_ops.can_tile(64, 128, jnp.bfloat16, 2)
    assert not paged_ops.can_tile(64, 128, jnp.bfloat16, 1)
    assert not paged_ops.can_tile(64, 128, jnp.bfloat16, 3)
    assert paged_ops.can_tile(128, 128, jnp.bfloat16, 1)
    # tp == n_kv_heads ran on the gather backend before heads of 64 were
    # packed, and still does: tokens of the one-chip engine
    want = _want_tokens(2)
    eng = LLMEngine(_cfg(2, tp_degree=2, kv_tier_enabled=False), rng_seed=0)
    eng.start()
    try:
        assert eng.kv["k"].shape == (2, 2, 64, 16, 64)
        assert eng.generate(LONG, temperature=0.0)["tokens"] == want
    finally:
        eng.shutdown()
    # and on a TPU "auto" keeps such a pool off the kernels
    monkeypatch.setattr(kvc.jax, "default_backend", lambda: "tpu")
    model = types.SimpleNamespace(head_dim=64, n_kv_heads=8)
    assert kvc.resolve_attention_backend("auto", model, 16, 4) == "pallas"
    assert kvc.resolve_attention_backend("auto", model, 16, 8) == "gather"
    with pytest.raises(ValueError, match="cannot tile"):
        kvc.resolve_attention_backend("pallas", model, 16, 8)


def test_a_routed_block_refuses_more_slots_than_its_routing_record_holds():
    model = lfm2_moe.lfm2_moe_tiny(max_seq_len=8)
    with pytest.raises(ValueError, match="max_seq_len"):
        LLMEngine(LLMConfig(model_config=model, max_batch_size=16,
                            page_size=8, num_pages=16, max_prompt_len=8,
                            max_seq_len=8), rng_seed=0)
