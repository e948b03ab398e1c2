"""The engine loop's lead over the device is set by one rule (ISSUE 31):
at most ``pipeline_depth`` entries in flight, and no eager device op on
the loop thread.

CPU, tiny model. What is held here:
- the loop thread applies no primitive eagerly (every value it hands a
  program is numpy or what a program returned), whatever the number of
  admissions, the batch width or the prefill path;
- a prefill program writes its first token where the next block reads it
  (row ``slot`` of ``_dev_tokens``): whole prefill, last chunk; a chunk
  that is not the last and disagg's ``prefill_only`` touch the trash row
  only; ``_overrides`` carries host ints only (rollback, adoption);
- one compiled entry a prefill bucket whatever the slot, and the handoff
  compiles nothing of its own;
- ``len(_pending) <= pipeline_depth`` after every ``_step``;
- the tokens of a fixed seed are the parent commit's, greedy and sampled
  (the order of key splits is part of what a seed means).
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.llm import LLMConfig, LLMEngine

LOOP_THREAD = "llm-engine"


def _cfg(**kw):
    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8,
             prefix_cache_enabled=False)
    d.update(kw)
    return LLMConfig(**d)


def _prompts():
    rs = np.random.RandomState(31)
    return [rs.randint(1, 500, size=n).tolist()
            for n in (5, 19, 40, 64, 33, 12)]


PROMPTS = _prompts()

# What the parent commit (8f6ed93) emits for PROMPTS: max_tokens 12,
# rng_seed 0, temperature 0, all submitted before start(); the same with
# prefill_chunk 0 (whole prefills) and 16 (prompts of 19-64 tokens go in
# 2-4 chunks).
PARENT_GREEDY = [
    [69, 408, 126, 495, 421, 363, 95, 432, 64, 98, 64, 89],
    [103, 366, 433, 334, 118, 103, 399, 459, 459, 433, 433, 433],
    [282, 69, 394, 69, 40, 495, 459, 180, 43, 397, 31, 40],
    [100, 437, 171, 362, 91, 64, 170, 84, 139, 429, 95, 427],
    [238, 83, 31, 209, 159, 224, 132, 242, 242, 242, 242, 34],
    [471, 399, 47, 471, 433, 433, 433, 330, 22, 330, 286, 173],
]
# ... and for PROMPTS[2] alone at temperature 1.0, top_k 0, rng_seed 7,
# max_tokens 24: every token is drawn with a key split off the loop's key,
# so these hold the ORDER of the splits (one a prefill or chunk, one a
# decode step). Since ISSUE 58 a warm-up leaves the loop's key alone (until
# then every warmed program split it once a step, so a seed's streams
# followed the count of programs): these are what commit d784841 emits
# with ``warmup_compile=False``, at every tier alike.
PARENT_SAMPLED = {
    0: [98, 366, 507, 288, 11, 14, 65, 140, 502, 300, 442, 236, 314, 329,
        460, 248, 494, 444, 44, 26, 377, 466, 351, 251],
    16: [507, 396, 444, 152, 65, 140, 502, 8, 20, 63, 179, 144, 377, 341,
         494, 444, 44, 26, 377, 466, 351, 33, 291, 25],
}


@contextlib.contextmanager
def eager_calls():
    """Every primitive applied eagerly (outside a jit trace: jax's
    ``EvalTrace``, which hands it to ``dispatch.apply_primitive``) while
    the block runs, as (thread name, primitive name). A call of a jitted
    function is not one; ``jnp.stack`` of two scalars is three."""
    from jax._src import core

    calls = []
    orig = core.EvalTrace.process_primitive

    def counted(self, primitive, args, params):
        calls.append((threading.current_thread().name, primitive.name))
        return orig(self, primitive, args, params)

    core.EvalTrace.process_primitive = counted
    try:
        yield calls
    finally:
        core.EvalTrace.process_primitive = orig


def _rows(eng):
    """The token vector as the next dispatch would read it (host copy)."""
    return np.asarray(eng._dev_tokens).copy()


def _drain(eng, passes=200):
    """Loop passes on THIS thread until nothing is live or pending."""
    for _ in range(passes):
        eng._loop_pass()
        with eng._lock:
            live = (eng._waiting or eng._prefilling or eng._pending
                    or any(r is not None for r in eng.slot_req))
        if not live:
            return
    raise AssertionError("engine did not drain")


def test_the_counter_sees_eager_ops_and_not_jitted_calls():
    """If jax moves its eager dispatch elsewhere, the zero below must not
    pass for want of a counter."""
    import jax
    import jax.numpy as jnp

    one = jax.jit(lambda x: x + 1)
    x = np.arange(4, dtype=np.int32)
    one(x)
    with eager_calls() as calls:
        one(x)
        assert calls == []
        a = jnp.int32(3)
        jnp.stack([a, a])
        key, sub = jax.random.split(jax.random.PRNGKey(0))
    names = [n for _t, n in calls]
    assert "concatenate" in names and "random_split" in names
    assert all(t == threading.current_thread().name for t, _n in calls)


@pytest.mark.parametrize("width,requests,chunk", [
    (4, 3, 0), (4, 8, 0), (4, 8, 16), (8, 8, 16), (8, 16, 16), (8, 16, 0),
], ids=lambda v: str(v))
def test_loop_thread_applies_no_eager_primitive(width, requests, chunk):
    """0 eager primitives on the loop thread across admissions, chunked
    prefills and decode blocks; the parent applied 7-22 an admission (63
    for these 8 whole prefills at width 4, 345 for 16 chunked at width
    8), so this also says the count does not grow with admissions or
    width."""
    eng = LLMEngine(_cfg(max_batch_size=width, prefill_chunk=chunk,
                         max_tokens=6), rng_seed=0)
    order = [i % len(PROMPTS) for i in range(requests)]
    first = (requests + 1) // 2
    with eager_calls() as calls:
        # half before the loop runs, the rest while it decodes: admissions
        # beside live streams, slots recycled, width 4 -> 8 filled
        rids = [eng.submit(PROMPTS[i], temperature=0.0)
                for i in order[:first]]
        eng.start()
        try:
            for i in order[first:]:
                rids.append(eng.submit(PROMPTS[i], temperature=0.0))
                time.sleep(0.01)
            outs = [eng.result(r, timeout=120) for r in rids]
        finally:
            eng.shutdown()
    assert [o["tokens"] for o in outs] == [PARENT_GREEDY[i][:6]
                                           for i in order]
    st = eng.stats
    assert st["prefills"] == requests and st["attn_decode_dispatches"] > 0
    if chunk:
        assert st["attn_chunk_dispatches"] > requests // 2
    on_loop = [n for t, n in calls if t == LOOP_THREAD]
    assert on_loop == []
    assert eng._overrides == {}


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole", "last_chunk"])
def test_next_dispatch_reads_the_sampled_first_token(chunk):
    eng = LLMEngine(_cfg(prefill_chunk=chunk), rng_seed=0)
    eng.submit(PROMPTS[2], temperature=0.0)
    eng._admit()
    while eng._prefilling:
        eng._prefill_chunks()
    tok_dev, [(col, slot, req)], k, seq, _touched = eng._pending[-1]
    assert (col, k, seq) == (0, 1, -1) and req.slot == slot
    assert _rows(eng)[slot] == int(tok_dev) == PARENT_GREEDY[2][0]
    # the handoff is the program's: nothing queued for the patch
    assert eng._overrides == {}
    _drain(eng)
    assert req.generated == PARENT_GREEDY[2][:8]


def test_intermediate_chunks_leave_every_live_row_as_it_was():
    eng = LLMEngine(_cfg(prefill_chunk=16), rng_seed=0)
    eng.submit(PROMPTS[0], temperature=0.0, max_tokens=40)
    eng.submit(PROMPTS[5], temperature=0.0, max_tokens=40)
    for _ in range(3):
        eng._loop_pass()
    while eng._pending:
        eng._harvest_one()
    live = _rows(eng)
    trash = eng.cfg.max_batch_size
    assert np.count_nonzero(live[:trash]) >= 2   # two streams under way
    eng.submit(PROMPTS[3], temperature=0.0)      # 64 tokens: four chunks
    eng._admit()
    (req,) = eng._prefilling
    for _ in range(3):
        eng._prefill_chunks()
        assert eng._prefilling == [req] and not eng._pending
        np.testing.assert_array_equal(_rows(eng)[:trash], live[:trash])
    eng._prefill_chunks()                        # the last: arms the slot
    assert not eng._prefilling
    after = _rows(eng)
    assert after[req.slot] == PARENT_GREEDY[3][0]
    others = [s for s in range(trash) if s != req.slot]
    np.testing.assert_array_equal(after[others], live[others])


def test_prefill_only_leaves_every_live_row_as_it_was():
    from ray_tpu.serve.llm import disagg

    eng = LLMEngine(_cfg(), rng_seed=0)
    trash = eng.cfg.max_batch_size
    eng._dev_tokens = eng._patch_toks(
        eng._dev_tokens, np.arange(trash + 1, dtype=np.int32),
        np.arange(100, 101 + trash, dtype=np.int32))
    live = _rows(eng)
    with eager_calls() as calls:
        state = disagg.prefill_only(eng, PROMPTS[2], temperature=0.0)
    assert state["first_token"] == PARENT_GREEDY[2][0]
    np.testing.assert_array_equal(_rows(eng)[:trash], live[:trash])
    # its page gather stays eager (no loop runs beside it); the key split
    # and the operands do not
    assert "random_split" not in [n for _t, n in calls]


def test_two_admissions_in_one_pass_land_in_their_own_rows():
    eng = LLMEngine(_cfg(prefill_chunk=0), rng_seed=0)
    eng.submit(PROMPTS[1], temperature=0.0)
    eng.submit(PROMPTS[4], temperature=0.0)
    assert eng._admit() == 2
    (t1, [(_c1, s1, r1)], *_e1), (t2, [(_c2, s2, r2)], *_e2) = \
        eng._pending
    assert s1 != s2
    rows = _rows(eng)
    assert rows[s1] == int(t1) == PARENT_GREEDY[1][0]
    assert rows[s2] == int(t2) == PARENT_GREEDY[4][0]
    _drain(eng)
    assert r1.generated == PARENT_GREEDY[1][:8]
    assert r2.generated == PARENT_GREEDY[4][:8]


def test_one_compiled_entry_a_bucket_and_no_program_for_the_handoff():
    eng = LLMEngine(_cfg(prefill_chunk=16, warmup_compile=False),
                    rng_seed=0)
    # buckets: 16 whole (5, 12 tokens), chunk programs of 16 (and the
    # tails' buckets); four slots, nine admissions: every slot is reused
    order = [0, 5, 1, 2, 0, 5, 4, 5, 0]
    rids = [eng.submit(PROMPTS[i], temperature=0.0, max_tokens=4)
            for i in order[:3]]
    eng.start()
    try:
        for i in order[3:]:
            rids.append(eng.submit(PROMPTS[i], temperature=0.0,
                                   max_tokens=4))
            time.sleep(0.02)
        for r in rids:
            assert eng.result(r, timeout=120)["error"] is None
    finally:
        eng.shutdown()
    assert eng._prefill_fn(16)._cache_size() == 1
    assert eng._chunk_fn(16)._cache_size() == 1
    assert all(fn._cache_size() == 1 for fn in eng._prefill_cache.values())
    # the first token needs no program of its own: the token patch (host
    # ints only) never ran, and the key split is one program
    assert eng._patch_toks._cache_size() == 0
    assert eng._split_key._cache_size() == 1
    assert eng._patch_state._cache_size() == 1


def test_rollback_override_is_a_host_int_and_tokens_are_unchanged():
    """A verify round whose draft misses rolls the slot back through a
    host-int override; the stream goes on as if it had never speculated."""
    want = PARENT_GREEDY[2]
    eng = LLMEngine(_cfg(max_tokens=12, spec_draft_len=4), rng_seed=0)
    eng._propose_locked = lambda req: []     # no chained round: one miss
    eng.submit(PROMPTS[2], temperature=0.0)
    eng._admit()
    while eng._pending:
        eng._harvest_one()
    (slot, req), = [(s, r) for s, r in enumerate(eng.slot_req) if r]
    assert req.generated == want[:1]
    # two drafted tokens right, the third wrong: accept 2, emit 3
    draft = [want[1], want[2], (want[3] + 1) % 512, 7]
    eng._dispatch_verify([(slot, req, draft, len(PROMPTS[2]))])
    eng._harvest_one()
    assert req.generated == want[:4]
    assert eng._overrides == {slot: want[3]}
    assert all(type(v) is int for v in eng._overrides.values())
    with eager_calls() as calls:
        _drain(eng)
    assert req.generated == want
    assert calls == []


def test_adoption_override_is_a_host_int_the_next_dispatch_reads():
    from ray_tpu.serve.llm import disagg

    pre = LLMEngine(_cfg(), rng_seed=0)
    state = disagg.prefill_only(pre, PROMPTS[2], temperature=0.0)
    dec = disagg.DecodeEngine(_cfg(max_tokens=12), params=pre.params,
                              rng_seed=0)
    rid = dec.submit_prefilled(state, max_tokens=12)
    dec._admit()
    (slot, req), = [(s, r) for s, r in enumerate(dec.slot_req) if r]
    assert dec._overrides == {slot: PARENT_GREEDY[2][0]}
    assert type(dec._overrides[slot]) is int
    dirty, dec._dirty_slots = dec._dirty_slots, {}
    overrides, dec._overrides = dec._overrides, {}
    dec._dev_tokens = dec._flush_slot_patches(dirty, overrides)
    assert _rows(dec)[slot] == PARENT_GREEDY[2][0]
    _drain(dec)
    assert req.request_id == rid and req.generated == PARENT_GREEDY[2]


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("max_tokens", [1, 5], ids=["prefill_only", "decode"])
def test_pending_stays_within_pipeline_depth(depth, max_tokens):
    """Four admissions before every pass: each queues an entry of its own
    beside the pass's block, and the parent trimmed one a dispatch."""
    eng = LLMEngine(_cfg(max_batch_size=8, num_pages=128,
                         pipeline_depth=depth, max_tokens=max_tokens),
                    rng_seed=0)
    seen = []
    step = eng._step

    def held_step():
        out = step()
        seen.append(len(eng._pending))
        assert eng.engine_stats()["pending_pipeline_depth"] <= depth
        return out

    eng._step = held_step
    rids = []
    for p in range(8):
        rids += [eng.submit(PROMPTS[(p + i) % 6], temperature=0.0)
                 for i in range(4)]
        eng._loop_pass()
    _drain(eng)
    assert len(seen) >= 8 and max(seen) <= depth
    assert eng.stats["prefills"] == 32
    for r in rids:
        out = eng.result(r, timeout=1)
        assert out["error"] is None and len(out["tokens"]) == max_tokens


def test_decode_dispatch_span_says_how_hard_the_bound_engaged():
    # one block of 8 ends every stream of 5 tokens, so each pass below
    # starts from an empty engine
    eng = _at_idle_tier(LLMEngine(
        _cfg(max_batch_size=8, num_pages=128, pipeline_depth=2,
             max_tokens=5), rng_seed=0), 8)
    spans = []
    span = eng._prof.span

    def recording(name, **args):
        spans.append((name, args))
        return span(name, **args)

    eng._prof.span = recording
    harvests = []
    harvest = eng._harvest_one
    eng._harvest_one = lambda: (harvests.append(len(spans)), harvest())[1]
    trims = []
    for p in range(6):
        for i in range(3):
            eng.submit(PROMPTS[(p + i) % 6], temperature=0.0)
        eng._admit()
        before = len(harvests)
        eng._step()
        trims.append(len(harvests) - before)
        while eng._pending:
            harvest()
    dispatches = [a for n, a in spans if n == "decode_dispatch"]
    assert len(dispatches) == 6
    # three admissions' entries pending as the block goes out, depth 2:
    # the bound forces two harvests, and the span said so beforehand
    assert [d["inflight"] for d in dispatches] == [3] * 6
    assert [d["trimmed"] for d in dispatches] == trims == [2] * 6


def _at_idle_tier(eng, tier):
    """Hold the idle tier (ISSUE 42: serve/llm/lead.py) at ``tier``: one
    of the three k the engine warms. The parent ran it at decode_block."""
    from ray_tpu.serve.llm.lead import IdleLead
    eng._lead = IdleLead((tier,))
    return eng


IDLE_TIERS = pytest.mark.parametrize(
    "tier", [1, 2, 8], ids=["k1", "pressure", "ceiling"])


@IDLE_TIERS
@pytest.mark.parametrize("chunk", [0, 16], ids=["whole", "chunked"])
def test_greedy_tokens_of_a_fixed_seed_equal_the_parents(chunk, tier):
    """... at every tier the idle lead can hold: greedy output does not
    depend on how many steps a dispatch fuses."""
    eng = _at_idle_tier(LLMEngine(_cfg(max_tokens=12, prefill_chunk=chunk),
                                  rng_seed=0), tier)
    rids = [eng.submit(p, temperature=0.0) for p in PROMPTS]
    eng.start()
    try:
        got = [eng.result(r, timeout=120)["tokens"] for r in rids]
    finally:
        eng.shutdown()
    assert got == PARENT_GREEDY
    assert eng.stats["dispatch_tier_idle_total"] > 0


@IDLE_TIERS
@pytest.mark.parametrize("chunk", [0, 16], ids=["whole", "chunked"])
def test_sampled_tokens_of_a_fixed_seed_equal_the_parents(chunk, tier):
    """One stream alone: a key split a prefill or chunk and one a decode
    step, in the same order however the steps are fused."""
    eng = _at_idle_tier(LLMEngine(
        _cfg(max_tokens=24, top_k=0, prefill_chunk=chunk), rng_seed=7), tier)
    rid = eng.submit(PROMPTS[2], temperature=1.0)
    eng.start()
    try:
        got = eng.result(rid, timeout=120)["tokens"]
    finally:
        eng.shutdown()
    assert got == PARENT_SAMPLED[chunk]


def test_warmup_compiles_what_the_loop_dispatches():
    """After warmup_compile, traffic adds no entry to the decode, patch
    or key-split programs: warmup builds its operands as the loop does."""
    eng = LLMEngine(_cfg(warmup_compile=True, max_tokens=6), rng_seed=0)
    eng._warmup_decode_programs()
    progs = {"decode": eng._decode, "patch_state": eng._patch_state,
             "patch_toks": eng._patch_toks, "split_key": eng._split_key}
    warmed = {n: f._cache_size() for n, f in progs.items()}
    assert warmed["split_key"] == 1 and warmed["patch_toks"] == 1
    before_rng = np.asarray(eng._rng).copy()
    eng._split_key(eng._rng)       # warming a split never advances the key
    np.testing.assert_array_equal(np.asarray(eng._rng), before_rng)
    for p in PROMPTS[:5]:
        eng.submit(p, temperature=0.0)
    _drain(eng)
    # a host-int patch, as a rollback's
    eng._dev_tokens = eng._flush_slot_patches({0: (0, 0.0)}, {0: 5})
    assert {n: f._cache_size() for n, f in progs.items()} == warmed


def test_token_vector_is_replicated_on_the_tp_mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    eng = LLMEngine(_cfg(tp_degree=2, max_tokens=12, prefill_chunk=16),
                    rng_seed=0)
    assert eng._dev_tokens.sharding.is_fully_replicated
    assert len(eng._dev_tokens.sharding.device_set) == 2
    rids = [eng.submit(p, temperature=0.0) for p in PROMPTS[:4]]
    eng.start()
    try:
        got = [eng.result(r, timeout=180)["tokens"] for r in rids]
    finally:
        eng.shutdown()
    assert got == PARENT_GREEDY[:4]
    assert len(eng._dev_tokens.sharding.device_set) == 2
