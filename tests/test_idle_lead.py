"""The idle tier's lead over the device (ISSUE 42): with nothing queued the
engine dispatches the smallest warmed tier (one step) and climbs, through the
pressure tier's k, towards ``decode_block`` only when it sees the device run
dry through the tier's own fault (``serve/llm/lead.py``; engine.py ``_select_block``,
``_decode_step``).

CPU, tiny models. What is held here: the rule alone (when it climbs, what
it does not count, when it comes back, that it stays inside its tiers);
the tier ``_select_block`` returns in every state of the queue, with
speculation on and with a block length of 4; that the ENGINE climbs after
dry dispatches with live slots and not after a collection or a
``loop_wait``; the counters in ``engine_stats()``; the ``lead`` argument of
the dispatch span. That a seeded stream yields the same tokens at every tier
is ``tests/test_engine_loop_dispatch.py``'s; that every k picked is a warmed
one, ``tests/test_engine_program_hashes.py``'s. Nothing here is a device
number.
"""

import gc
import random

import pytest

from ray_tpu.models import llama, sdar_moe
from ray_tpu.serve.llm import LLMConfig, LLMEngine
from ray_tpu.serve.llm.lead import (CLIMB_DRY, CLIMB_WINDOW, DESCEND_CLEAN,
                                    HOLD_MAX, IdleLead)


def _feed(lead, pattern, gc_n=0):
    for dry in pattern:
        lead.observe(dry, gc_n)


# ---- the rule alone --------------------------------------------------------

def test_starts_at_the_smallest_tier_and_sorts_what_it_is_given():
    lead = IdleLead((8, 2, 8))
    assert lead.tiers == (2, 8) and lead.k == 2
    assert (lead.climbs, lead.descents) == (0, 0)


@pytest.mark.parametrize("spacing,climbs", [
    (1, True), (CLIMB_WINDOW // CLIMB_DRY, True),
    (CLIMB_WINDOW // (CLIMB_DRY - 1) - 1, True),
    (CLIMB_WINDOW // (CLIMB_DRY - 1), False), (CLIMB_WINDOW, False)])
def test_climbs_on_dry_dispatches_inside_the_window_only(spacing, climbs):
    """CLIMB_DRY dry dispatches, one every ``spacing``: a climb if the
    first and the last are less than CLIMB_WINDOW apart."""
    lead = IdleLead((2, 8))
    for _ in range(CLIMB_DRY):
        _feed(lead, [0] * (spacing - 1) + [1])
    assert (lead.k, lead.climbs) == ((8, 1) if climbs else (2, 0))


def test_fewer_dry_dispatches_than_the_threshold_never_climb():
    lead = IdleLead((2, 8))
    for _ in range(50):       # one stall of the host now and then
        _feed(lead, [1] * (CLIMB_DRY - 1) + [0] * CLIMB_WINDOW)
    assert (lead.k, lead.climbs) == (2, 0)


def test_a_dry_dispatch_under_a_full_collection_is_not_the_tiers():
    lead = IdleLead((2, 8), gc_n=5)
    for n in range(6, 6 + 4 * CLIMB_DRY):     # a collection before each
        lead.observe(1, n)
    assert (lead.k, lead.climbs) == (2, 0)
    _feed(lead, [1] * CLIMB_DRY, gc_n=6 + 4 * CLIMB_DRY - 1)
    assert (lead.k, lead.climbs) == (8, 1)    # the same count: no excuse


def test_an_excused_dry_dispatch_does_not_end_a_clean_run_either():
    lead = IdleLead((2, 8))
    _feed(lead, [1] * CLIMB_DRY)
    _feed(lead, [0] * (DESCEND_CLEAN - 1))
    lead.observe(1, 1)                        # under a collection
    assert lead.k == 8
    lead.observe(0, 1)
    assert (lead.k, lead.descents) == (2, 1)


def test_comes_back_after_a_clean_run_and_doubles_it_when_that_was_wrong():
    lead = IdleLead((2, 8))
    _feed(lead, [1] * CLIMB_DRY)
    _feed(lead, [0] * (DESCEND_CLEAN - 1))
    assert lead.k == 8
    _feed(lead, [0])
    assert (lead.k, lead.descents) == (2, 1)
    # dry again at once: the descent was wrong, the next clean run is
    # twice as long
    _feed(lead, [1] * CLIMB_DRY)
    assert (lead.k, lead.climbs) == (8, 2)
    _feed(lead, [0] * (2 * DESCEND_CLEAN - 1))
    assert lead.k == 8
    _feed(lead, [0])
    assert (lead.k, lead.descents) == (2, 2)
    # a climb long after a descent doubles nothing
    _feed(lead, [0] * (4 * DESCEND_CLEAN) + [1] * CLIMB_DRY)
    _feed(lead, [0] * (2 * DESCEND_CLEAN))
    assert (lead.k, lead.climbs, lead.descents) == (2, 3, 3)


def test_the_clean_run_needed_is_bounded():
    lead = IdleLead((2, 8))
    for _ in range(12):
        _feed(lead, [1] * CLIMB_DRY)
        while lead.k == 8:
            _feed(lead, [0])
    assert lead._hold == DESCEND_CLEAN * HOLD_MAX


@pytest.mark.parametrize("tiers", [(1,), (2,), (1, 2), (2, 8), (1, 2, 8)])
def test_k_is_always_one_of_the_tiers_and_moves_one_at_a_time(tiers):
    rs = random.Random(42)
    lead = IdleLead(tiers)
    seen, before, gc_n = set(), 0, 0
    for _ in range(20000):
        gc_n += rs.random() < 0.01
        lead.observe(int(rs.random() < rs.choice((0.0, 0.02, 0.5))), gc_n)
        i = tiers.index(lead.k)
        assert abs(i - before) <= 1
        seen.add(lead.k)
        before = i
    assert seen == set(tiers)
    assert lead.climbs - lead.descents == before


# ---- the engine ------------------------------------------------------------

def _cfg(**kw):
    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8,
             prefix_cache_enabled=False, warmup_compile=False)
    d.update(kw)
    return LLMConfig(**d)


def _climb(eng, rungs=1):
    for _ in range(rungs * CLIMB_DRY):
        eng._lead.observe(1, eng._collector.pause_n)


QUEUES = {   # (waiting, free slots, prefilling) -> the tier that picks k
    "empty": (([], [0], []), "idle"),
    "waiting_with_free_slots": (([object()], [0], []), "admit"),
    "waiting_for_slots": (([object()], [], []), "pressure"),
    "chunk_mid_flight": (([], [0], [object()]), "admit"),
}
ENGINES = {  # configuration -> k of (admit, pressure, idle by rung climbed)
    "dense_8_2": (dict(decode_block=8, pressure_decode_block=2),
                  (1, 2, (1, 2, 8))),
    "dense_8_1": (dict(decode_block=8, pressure_decode_block=1),
                  (1, 1, (1, 8, 8))),
    "pressure_above_the_ceiling": (
        dict(decode_block=4, pressure_decode_block=16), (1, 4, (1, 4, 4))),
    "speculation_caps_the_idle_tier": (
        dict(decode_block=8, pressure_decode_block=4,
             spec_decode_enabled=True, spec_draft_len=3), (1, 4, (1, 3, 3))),
    "speculation_above_the_pressure_tier": (
        dict(decode_block=8, pressure_decode_block=2,
             spec_decode_enabled=True, spec_draft_len=4), (1, 2, (1, 2, 4))),
    "block_length_4": (
        dict(model_config=sdar_moe.sdar_moe_tiny(), decode_block=8,
             pressure_decode_block=4, page_size=8, attention_kernel="gather"),
        (1, 1, (1, 2, 2))),
    "block_length_4_of_16": (
        dict(model_config=sdar_moe.sdar_moe_tiny(), decode_block=16,
             pressure_decode_block=8, page_size=8, attention_kernel="gather"),
        (1, 2, (1, 2, 4))),
}


@pytest.fixture(scope="module")
def engines():
    built = {}

    def get(name):
        if name not in built:
            built[name] = LLMEngine(_cfg(**ENGINES[name][0]), rng_seed=0)
        return built[name]
    return get


@pytest.mark.parametrize("queue", QUEUES)
@pytest.mark.parametrize("name", ENGINES)
def test_select_block_by_queue_state_and_lead(engines, name, queue):
    eng = engines(name)
    admit, pressure, idle = ENGINES[name][1]
    (waiting, free, prefilling), tier = QUEUES[queue]
    eng._lead = IdleLead(eng._lead.tiers, gc_n=eng._collector.pause_n)
    eng._waiting, eng.free_slots, eng._prefilling = waiting, free, prefilling
    for rung in idle:       # a climb moves the idle tier's k and no other
        want = {"admit": admit, "pressure": pressure, "idle": rung}[tier]
        assert eng._select_block() == want and eng._last_tier == tier
        _climb(eng)


def _one_long_stream(**kw):
    eng = LLMEngine(_cfg(max_tokens=80, pipeline_depth=3, **kw), rng_seed=0)
    rid = eng.submit(list(range(1, 20)), temperature=0.0)
    return eng, rid


def _pass_with_the_device_dry(eng, before_pass=lambda: None):
    """One pass of the loop that finds everything it dispatched done."""
    if eng._newest is not None:
        eng._newest.block_until_ready()
    before_pass()
    eng._loop_pass()


def test_the_engine_climbs_after_dry_dispatches_with_live_slots():
    eng, rid = _one_long_stream()
    assert eng.engine_stats()["idle_lead_k"] == 1
    ks = []
    for _ in range(2 * CLIMB_DRY + 3):
        _pass_with_the_device_dry(eng)
        ks.append(eng._last_block)
    st = eng.engine_stats()
    # the first pass admits (its decode block follows the prefill: not
    # dry); the next CLIMB_DRY are dry at k = 1, CLIMB_DRY more at the
    # pressure tier's k; then the ceiling
    assert ks == [1] * (CLIMB_DRY + 1) + [2] * CLIMB_DRY + [8, 8]
    assert (st["idle_lead_k"], st["lead_climbs_total"],
            st["lead_descents_total"]) == (8, 2, 0)
    assert st["dispatch_tier_idle_total"] == 2 * CLIMB_DRY + 3
    assert st["dry_dispatches_total"] >= 2 * CLIMB_DRY
    while eng._pending:
        eng._harvest_one()
    assert len(eng._requests[rid].generated) == 1 + sum(ks)


def test_no_climb_on_dry_dispatches_after_a_loop_wait():
    """``loop_wait`` parks the loop (``_parked``): the next dispatch is
    the end of an idle wait, not a host that fell behind."""
    eng, _rid = _one_long_stream()

    def park():
        eng._parked = True
    for _ in range(4 * CLIMB_DRY):
        _pass_with_the_device_dry(eng, park)
    st = eng.engine_stats()
    assert (st["idle_lead_k"], st["lead_climbs_total"]) == (1, 0)
    assert st["dry_dispatches_total"] == 0


def test_no_climb_on_dry_dispatches_under_full_collections():
    eng, _rid = _one_long_stream()
    for _ in range(4 * CLIMB_DRY):
        _pass_with_the_device_dry(eng, gc.collect)
    st = eng.engine_stats()
    assert st["dry_dispatches_total"] >= 4 * CLIMB_DRY - 1   # counted still
    assert (st["idle_lead_k"], st["lead_climbs_total"]) == (1, 0)


def test_dry_dispatches_of_another_tier_say_nothing_of_the_idle_tier():
    eng, _rid = _one_long_stream()
    _pass_with_the_device_dry(eng)

    def queue_a_caller():
        eng._waiting = [object()]       # callers wait for slots
        eng.free_slots = []

    def drop_it():
        eng._waiting = []
    for _ in range(2 * CLIMB_DRY):
        if eng._newest is not None:
            eng._newest.block_until_ready()
        queue_a_caller()
        try:
            eng._step()
        finally:
            drop_it()
    st = eng.engine_stats()
    assert st["dispatch_tier_pressure_total"] == 2 * CLIMB_DRY
    assert st["dry_dispatches_total"] >= 2 * CLIMB_DRY
    assert (st["idle_lead_k"], st["lead_climbs_total"]) == (1, 0)


def test_tier_counters_add_up_to_the_decode_dispatches():
    eng = LLMEngine(_cfg(max_tokens=12, max_batch_size=2, prefill_chunk=16),
                    rng_seed=0)
    rids = [eng.submit(list(range(1, n)), temperature=0.0)
            for n in (40, 9, 30, 12, 50)]   # more callers than slots, chunks
    eng.start()
    try:
        for r in rids:
            assert eng.result(r, timeout=120)["error"] is None
    finally:
        eng.shutdown()
    st = eng.engine_stats()
    tiers = [st[f"dispatch_tier_{t}_total"]
             for t in ("admit", "pressure", "idle")]
    assert sum(tiers) == st["attn_decode_dispatches"]
    assert all(n > 0 for n in tiers), tiers
    assert st["idle_lead_k"] in (1, 2, 8)


def test_dispatch_span_carries_the_steps_in_flight():
    eng, _rid = _one_long_stream()
    spans = []
    span = eng._prof.span

    def recording(name, **args):
        spans.append((name, args))
        return span(name, **args)

    eng._prof.span = recording
    for _ in range(4):
        eng._loop_pass()
    leads = [a["lead"] for n, a in spans if n == "decode_dispatch"]
    inflight = [a["inflight"] for n, a in spans if n == "decode_dispatch"]
    # a prefill's first token holds no decode step; then k = 1 an entry,
    # at most pipeline_depth entries
    assert leads[0] == 0 and inflight[0] == 1
    assert leads[1:] == [1, 2, 3]


LEAD_KEYS = ("dispatch_tier_admit_total", "dispatch_tier_pressure_total",
             "dispatch_tier_idle_total", "idle_lead_k", "lead_climbs_total",
             "lead_descents_total")


@pytest.mark.parametrize("key", LEAD_KEYS)
def test_engagement_counters_ride_the_export_chain(engines, key):
    """engine_stats() -> llm_server _EXPORTED_STATS (gauges) -> controller
    _ENGINE_KEYS (detailed_status; function-local, so read in source)."""
    import inspect

    from ray_tpu.serve import controller
    from ray_tpu.serve.llm import llm_server

    assert isinstance(engines("dense_8_2").engine_stats()[key], int)
    assert key in llm_server._EXPORTED_STATS
    assert f'"{key}"' in inspect.getsource(controller).split(
        "_ENGINE_KEYS = (", 1)[1]


def test_the_thresholds_are_what_the_docs_say():
    """README's telemetry table and config.py name them."""
    assert (CLIMB_DRY, CLIMB_WINDOW, DESCEND_CLEAN) == (3, 64, 256)
