"""Falcon-H1 through the serving engine (ISSUE 60), on the CPU at the tiny
preset in float32: the paged programs' LOGITS against the plain float32
reference (whole prefill, a chunked prefill whose chunks are longer than
the scan's chunk and whose last is partial, decode through pages and state
across a page edge, two sequences of different length in one decode batch),
both backends; the engine's served tokens against the reference (a slot and
its state row reused: stale state must not leak); the state pool a row a
SLOT and the allocator's reserved first pages; what a block with slot state
is kept out of, each with its counter; the grouped-query paged kernels
(interpreted) at five query heads a KV head. Nothing here is a device
number.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from ray_tpu.models import falcon_h1
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm import kv_cache as kvc

CFG = falcon_h1.falcon_h1_tiny()
FAM = common.load_module("models", "falcon_h1")
REF = common.load_module("reference", "falcon_h1_f32")
REF_KW = FAM.reference_kwargs(CFG)
# the benchmark's rehearsal preset IS the tiny preset (one statement of it)
SZ = FAM.sizes(common.load_cell("falcon-h1-34b-serve-decode")[2], True)
ENGINE = dict(max_batch_size=4, page_size=8, num_pages=64, max_prompt_len=128,
              max_seq_len=192, prefill_chunk=32, decode_block=4,
              pressure_decode_block=2, pipeline_depth=2,
              attention_kernel="gather", warmup_compile=False)
# whole <= prefill_chunk < chunked: 27 tokens whole; 77 = two chunks of 32
# (four scan chunks each) and 13 more, which end inside a scan chunk; the
# decode steps cross a page edge (80 = 10 pages of 8)
SPEC = {"depth": 2, "whole_prompt_tokens": 27, "chunked_prompt_tokens": 77,
        "decode_steps": 6, "tolerance": 2e-4}


@pytest.fixture(scope="module")
def params():
    return falcon_h1.init_params(jax.random.PRNGKey(0), CFG)


def test_the_adapters_tiny_config_is_the_modules():
    assert FAM.model_config(SZ) == CFG
    assert falcon_h1.num_params(CFG) == sum(
        x.size for x in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: falcon_h1.init_params(
                jax.random.PRNGKey(0), CFG))))


def test_the_count_at_the_published_widths_is_the_issues():
    """6 layers and the whole vocabulary: 5,254.6 M parameters."""
    cfg = falcon_h1.FalconH1Config(n_layers=6)
    assert falcon_h1.num_params(cfg) == 6 * 430_120_032 + 2 * 261_120 * 5_120 \
        + 5_120
    spec = falcon_h1.cache_spec(cfg)
    assert spec.state_arrays == (((15_360,), ""),
                                 ((32, 256, 128), "float32"))


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("seed", [1, 2])
def test_logits_are_the_references(backend, seed):
    """checks.logits_check: whole prefill, chunked prefill, then both
    sequences (lengths 27 and 77) in one decode batch, beside two idle
    lanes that meet in the trash row."""
    eng = {**ENGINE, "attention_kernel": backend}
    got = checks.logits_check(FAM, SZ, eng, SPEC, seed)
    assert got["ok"] and got["backend"] == backend, got
    # the logits span what the other configurations' do
    assert 2.0 < max(e["ref_max_abs"] for e in got["errors"].values()) < 8.0


@pytest.mark.parametrize("override", [
    {"mamba": False}, {"attention": False}, {"mlp": False},
    {"state_reset_every": 32}, {"skip": False}, {"conv_bias": False}])
def test_a_reference_with_a_rule_left_out_is_refused(override):
    got = checks.logits_check(FAM, SZ, ENGINE, SPEC, 1, **override)
    assert not got["ok"] and got["max_abs_err"] > 50 * SPEC["tolerance"], got


def _engine(**over):
    eng = LLMEngine(LLMConfig(model_config=CFG, **{**ENGINE, **over}))
    eng.start()
    return eng


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(
        0, 250, size=n)]


def _reference_agrees(params, prompts, outs, max_tokens):
    samples = [{"prompt_ids": p, "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": max_tokens} for p, o in zip(prompts, outs)]
    got = checks.served_tokens_check(REF, REF_KW, params, samples, 1e-3,
                                     eos=None)
    assert got["ok"] and got["tokens_checked"] > 0, got


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_engine_tokens_are_the_references(params, backend):
    """Whole and chunked prompts side by side, then decode blocks through
    pages and state; on the pallas backend the in-place update kernel and
    the paged kernels (interpreted) at five query heads a KV head."""
    eng = _engine(attention_kernel=backend)
    try:
        lengths = (20, 70, 33, 100, 9)
        prompts = [_prompt(i, n) for i, n in enumerate(lengths)]
        rids = [eng.submit(p, max_tokens=12, temperature=0.0)
                for p in prompts]
        outs = [eng.result(r, timeout=300.0) for r in rids]
        assert all(o["error"] is None for o in outs)
        _reference_agrees(params, prompts, outs, 12)
        st = eng.engine_stats()
        assert st["state_slots_in_use"] == 0
        assert st["state_rows"] == ENGINE["max_batch_size"] + 1
        assert st["first_pages_free"] == ENGINE["max_batch_size"]
        taps = 3 * CFG.conv_dim * 4 * CFG.n_layers
        rec = 4 * 16 * 16 * 4 * CFG.n_layers
        assert st["state_bytes_per_slot"] == taps + rec
        assert st["state_pool_bytes"] == {"taps": 5 * taps,
                                          "recurrent": 5 * rec}
    finally:
        eng.shutdown()


def test_a_slot_and_its_state_row_reused_after_another_sequence(params):
    """One slot, so one state row: the second and third sequences find the
    first's state there and must read zeros."""
    eng = _engine(max_batch_size=1, num_pages=26)
    try:
        prompts = [_prompt(10, 90), _prompt(11, 17), _prompt(12, 40)]
        outs = [eng.result(eng.submit(p, max_tokens=10, temperature=0.0),
                           timeout=300.0) for p in prompts]
        _reference_agrees(params, prompts, outs, 10)
        assert eng.kv["state"][0][1].shape == (2, 4, 16, 16)
        assert eng.kv["state"][0][1].dtype == jnp.float32
    finally:
        eng.shutdown()


def test_what_a_block_with_slot_state_is_kept_out_of(params):
    eng = _engine(prefix_cache_enabled=True, spec_decode_enabled=True)
    try:
        head = _prompt(20, 40)
        prompts = [head + _prompt(21, 9), head + _prompt(22, 13)]
        outs = [eng.result(eng.submit(p, max_tokens=8, temperature=0.0),
                           timeout=300.0) for p in prompts]
        _reference_agrees(params, prompts, outs, 8)
        st = eng.engine_stats()
        assert st["prefix_bypassed_stateful"] == 2
        assert st["spec_bypassed_stateful"] == 2 and st["spec_rounds"] == 0
        assert st["prefix_hits"] == 0 and st["admission_waited_state_row"] == 0
        with pytest.raises(NotImplementedError, match="state"):
            eng.refuse_stateful("prefill_only")
        assert eng.engine_stats()["disagg_refused_stateful"] == 1
    finally:
        eng.shutdown()


def test_the_verify_program_refuses_the_block(params):
    kv = kvc.init_paged_cache(CFG, 8, 8)
    with pytest.raises(NotImplementedError, match="slot state"):
        kvc.paged_verify_step(params, kv, jnp.zeros((2, 4), jnp.int32),
                              jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2, 3), jnp.int32), CFG, 8)


def test_no_tensor_parallel_rules_yet():
    with pytest.raises(ValueError, match="tp_degree must be 1"):
        falcon_h1.check_tp_divides(CFG, 2)
    falcon_h1.check_tp_divides(CFG, 1)


# ---- the state pool a row a slot: the allocator's reserved first pages ----

def test_first_pages_come_from_the_reserved_range_and_nothing_else_does():
    al = kvc.PageAllocator(20, first_pages=3)
    a, b = al.alloc(4), al.alloc(2)
    assert 1 <= a[0] <= 3 and 1 <= b[0] <= 3 and a[0] != b[0]
    assert all(p > 3 for p in a[1:] + b[1:])
    assert al.first_pages_free() == 1 and al.available() == 19 - 6
    c = al.alloc(1)
    assert 1 <= c[0] <= 3 and al.first_pages_free() == 0
    # pages are free, no state row is: refused
    assert al.available() == 12 and al.alloc(2) is None
    al.free(b)
    assert al.first_pages_free() == 1
    d = al.alloc(3)
    assert d[0] == b[0] and all(p > 3 for p in d[1:])    # freed and reused
    al.free(a + c + d)
    assert al.first_pages_free() == 3 and al.available() == 19


def test_later_pages_run_out_before_the_reserved_range_is_touched():
    al = kvc.PageAllocator(8, first_pages=2)      # 5 later pages
    a = al.alloc(5)
    assert al.alloc(3) is None and al.first_pages_free() == 1
    b = al.alloc(2)
    assert b[0] in (1, 2) and b[0] != a[0]
    with pytest.raises(ValueError, match="first_pages"):
        kvc.PageAllocator(4, first_pages=4)


def test_an_allocator_without_a_reserved_range_is_as_it_was():
    al = kvc.PageAllocator(6)
    assert al.alloc(5) == [1, 2, 3, 4, 5] and al.first_pages_free() == 0
    assert al.alloc(1) is None


def test_the_engine_counts_an_admission_that_had_pages_and_no_state_row(
        params):
    """Two slots, and one first page taken from under the engine: the
    second request has a slot and pages and waits for a row, counted
    once."""
    eng = _engine(max_batch_size=2)
    try:
        held = eng.allocator.alloc(1)
        rids = [eng.submit(_prompt(30 + i, 12), max_tokens=6,
                           temperature=0.0) for i in range(2)]
        first = eng.result(rids[0], timeout=300.0)
        eng.allocator.free(held)
        second = eng.result(rids[1], timeout=300.0)
        assert first["error"] is None and second["error"] is None
        assert eng.engine_stats()["admission_waited_state_row"] == 1
    finally:
        eng.shutdown()


def test_a_cache_at_a_row_a_page_or_a_row_a_slot():
    per_page = kvc.init_paged_cache(CFG, 12, 8)
    per_slot = kvc.init_paged_cache(CFG, 12, 8, state_rows=5)
    assert per_page["state"][0][0].shape == (12, 3 * CFG.conv_dim)
    assert per_slot["state"][3][1].shape == (5, 4, 16, 16)
    assert per_slot["k"].shape == per_page["k"].shape
    got = kvc.state_nbytes(per_slot)
    assert got["rows"] == 5 and set(got["pool_bytes"]) == {"taps",
                                                           "recurrent"}
    assert kvc.state_nbytes({"k": per_page["k"]}) == {
        "rows": 0, "pool_bytes": {}, "bytes_per_slot": 0}
