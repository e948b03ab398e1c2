"""Traffic generators: pure functions of (parameters, seed); lengths inside
the file's clamps; the same work for every seed in another order; the open
loop times from the due time and reports lateness."""

import json
import random
import statistics
import time

import pytest

from benchmark import common
from benchmark.traffic import closed_loop, lengths, open_loop, train_batches

CELLS = ["mistral7b-serve-chat", "mistral7b-serve-peak"]


def _traffic(cell):
    return common.load_json(common.bench_dir(), "workloads",
                            f"{cell}.json")["traffic"]


def _plan(cell, seed, seconds=51.0):
    params = _traffic(cell)
    return common.load_module("traffic", params["generator"]).plan(
        params, seed, seconds)


@pytest.mark.parametrize("cell", CELLS)
def test_plan_is_a_pure_function_of_parameters_and_seed(cell):
    a, b = _plan(cell, 2**31 + 12345), _plan(cell, 2**31 + 12345)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


@pytest.mark.parametrize("cell", CELLS)
def test_two_seeds_differ_in_order_and_text_not_in_work(cell):
    a, b = _plan(cell, 11)["requests"], _plan(cell, 2**31 + 7)["requests"]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [r["prompt_tokens"] for r in a] != [r["prompt_tokens"] for r in b]
    for key in ("prompt_tokens", "max_tokens"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 99])
def test_lengths_stay_inside_the_clamps(cell, seed):
    params = _traffic(cell)
    for r in _plan(cell, seed)["requests"]:
        p, o = params["prompt_tokens"], params["output_tokens"]
        assert p["min"] <= r["prompt_tokens"] <= p["max"]
        assert o["min"] <= r["max_tokens"] <= o["max"]
        # BOS + one id per character
        assert len(common.byte_encode(r["prompt"])) == r["prompt_tokens"]


def _one_shuffle(values, params, seed, salt):
    """The order of a cell's lengths as ``lengths.requests_for`` made it
    before ``deal_block`` (PR 59), kept here as the reference: ONE shuffle
    of the n quantiles by the file's ``schedule_seed``, then the seed's
    rotation."""
    values = list(values)
    random.Random(int(params["schedule_seed"]) * 7919 + salt).shuffle(values)
    k = random.Random(seed).randrange(len(values))
    return values[k:] + values[:k]


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  common.manifest()["workloads"]])
def test_a_file_without_deal_block_keeps_its_schedule_to_the_letter(cell):
    """Dealing in blocks is a parameter of a cell's file; a file without
    it (every cell's but peak's, and every rehearsal) gets the lengths it
    got before the parameter existed, in the same order."""
    whole = common.load_json(common.bench_dir(), "workloads", f"{cell}.json")
    for part in (whole, whole["rehearsal"]):
        params = part["traffic"]
        if "prompt_tokens" not in params:
            continue                              # the train cell's steps
        plain = {k: v for k, v in params.items() if k != "deal_block"}
        n = int(params.get("pool", 102))
        for seed in (3, 2**31 + 41):
            for key, salt in (("prompt_tokens", 1), ("output_tokens", 2)):
                assert lengths.rotated(lengths.dealt_lengths(
                    plain[key], n, plain, salt), seed) == _one_shuffle(
                        lengths.lognormal_lengths(plain[key], n), plain,
                        seed, salt)


def test_requests_are_the_dealt_lengths_with_text_from_the_seed():
    params = _traffic("mistral7b-serve-chat")
    reqs = lengths.requests_for(params, 3, 40)
    for key, name, salt in (("prompt_tokens", "prompt_tokens", 1),
                            ("output_tokens", "max_tokens", 2)):
        assert [r[name] for r in reqs] == _one_shuffle(
            lengths.lognormal_lengths(params[key], 40), params, 3, salt)
    assert [r["index"] for r in reqs] == list(range(40))


def test_dealt_in_blocks_every_stretch_of_the_cycle_holds_the_same_mix():
    """Peak's file: 2,048 requests in 16 blocks, each the 128 stratified
    quantiles once in its own order. A window sees about 485 requests from
    wherever the seed enters the cycle: over all 2,048 entry points the
    quartiles of their prompt tokens lie 1.8 % apart and of their output
    tokens 1.0 %, where one shuffle of 2,048 quantiles gives 4.1 % and
    3.4 % (this test's own numbers)."""
    params = _traffic("mistral7b-serve-peak")
    n, block = params["pool"], params["deal_block"]
    assert n % block == 0 and n // block == 16
    for key, salt in (("prompt_tokens", 1), ("output_tokens", 2)):
        cycle = lengths.dealt_lengths(params[key], n, params, salt)
        quantiles = lengths.lognormal_lengths(params[key], block)
        blocks = [cycle[i:i + block] for i in range(0, n, block)]
        assert all(sorted(b) == quantiles for b in blocks)
        assert len({tuple(b) for b in blocks}) == len(blocks)
        plain = lengths.dealt_lengths(
            params[key], n, {"schedule_seed": params["schedule_seed"]}, salt)
        assert sorted(plain) == lengths.lognormal_lengths(params[key], n)

        def swing(values, width=485):
            twice = values + values
            sums = [sum(twice[k:k + width]) for k in range(len(values))]
            q1, med, q3 = statistics.quantiles(sums, n=4)
            return (q3 - q1) / med
        assert swing(cycle) < 0.5 * swing(plain)
        assert swing(cycle) < 0.02 < 0.03 < swing(plain)
    # the seed still chooses where the window enters the cycle, and the text
    a, b = _plan("mistral7b-serve-peak", 11), _plan("mistral7b-serve-peak", 12)
    assert [r["max_tokens"] for r in a["requests"]] \
        != [r["max_tokens"] for r in b["requests"]]
    with pytest.raises(ValueError, match="deal_block"):
        lengths.dealt_lengths(params["prompt_tokens"], 8,
                              dict(params, deal_block=-1), 1)


def test_peaks_pool_outlasts_the_loop_at_a_faster_engine_than_todays():
    """The loop runs ramp + window + cool-down = 87 s. At 1,200 tokens/s
    (a fifth over what the cell reads) and the mix's 113.7 output tokens a
    request it completes 918 requests: the pool holds at least twice that,
    so no engine in reach empties it inside the window (a pool of 512 was
    spent by any engine past 924 tokens/s, and a faster one read LOWER)."""
    params = _traffic("mistral7b-serve-peak")
    plan = _plan("mistral7b-serve-peak", 5)
    out = sum(r["max_tokens"] for r in plan["requests"]) / params["pool"]
    assert out == pytest.approx(113.66, abs=0.05)
    loop_s = params["ramp_s"] + 51 + params["cooldown_s"]
    assert params["pool"] >= 2 * 1200 * loop_s / out


def test_open_loop_schedule_fills_the_window_at_the_rate():
    params = _traffic("mistral7b-serve-chat")
    plan = _plan("mistral7b-serve-chat", 5)
    due = [r["due_s"] for r in plan["requests"]]
    assert len(due) == round(params["rate_per_s"] * 51)
    assert due == sorted(due) and 0 < due[0] and due[-1] < 51.0
    # ten samples beyond the 90th percentile
    assert len(due) - int(0.9 * len(due)) >= 10


def test_open_loop_times_from_the_due_time_and_reports_lateness():
    plan = open_loop.plan({"rate_per_s": 40.0, "schedule_seed": 1,
                           "prompt_tokens": {"median": 8, "sigma": 0.1,
                                             "min": 4, "max": 16},
                           "output_tokens": {"median": 4, "sigma": 0.1,
                                             "min": 2, "max": 8},
                           "drain_timeout_s": 5}, 1, 0.5)

    def send(req, due, stop_at=None):
        sent = time.monotonic()
        time.sleep(0.01)
        return {"index": req["index"], "due": due, "sent": sent,
                "first": time.monotonic(), "done": time.monotonic(),
                "error": None}

    t0 = time.monotonic() + 0.05
    recs = open_loop.drive(plan, send, t0, 0.5)
    assert [r["index"] for r in recs] == list(range(len(plan["requests"])))
    for r, q in zip(recs, plan["requests"]):
        assert r["due"] == pytest.approx(t0 + q["due_s"])
        # never early; how late is the machine's business (the suite runs
        # six workers wide) and is what the record reports
        assert 0.0 <= r["sent"] - r["due"] < 30.0
        assert r["first"] - r["due"] >= 0.01          # latency from due


def test_closed_loop_keeps_its_callers_busy_through_the_cool_down():
    plan = closed_loop.plan({"clients": 4, "ramp_s": 0.0, "cooldown_s": 0.2,
                             "pool": 64, "schedule_seed": 1,
                             "prompt_tokens": {"median": 8, "sigma": 0.1,
                                               "min": 4, "max": 16},
                             "output_tokens": {"median": 4, "sigma": 0.1,
                                               "min": 2, "max": 8}}, 1, 1.0)
    live, peak, stops = [0], [0], set()

    def send(req, due, stop_at=None):
        live[0] += 1
        peak[0] = max(peak[0], live[0])
        stops.add(stop_at)
        time.sleep(0.02)
        live[0] -= 1
        return {"index": req["index"], "due": due, "done": time.monotonic(),
                "error": None}

    t0 = time.monotonic()
    recs = closed_loop.drive(plan, send, t0, 0.3)
    assert peak[0] <= 4 and 8 <= len(recs) <= 64
    # new requests are taken through the cool-down and not after it
    assert stops == {t0 + 0.3 + 0.2}
    assert t0 + 0.3 < max(r["due"] for r in recs) < t0 + 0.5


def _stream(first, last, n, chunks=5, **kw):
    times = [first + (last - first) * i / (chunks - 1) for i in range(chunks)]
    return {"first": first, "chunk_times": times, "done": last + 0.001,
            "completion_tokens": n, "max_tokens": n, "error": None,
            "abandoned": False, **kw}


@pytest.mark.parametrize("record,expected", [
    (_stream(12.0, 18.0, 60), 60.0),                 # wholly inside
    (_stream(8.0, 12.0, 40), 20.0),                  # straddles the start
    (_stream(58.0, 62.0, 40), 20.0),                 # straddles the end
    (_stream(5.0, 65.0, 120), 100.0),                # longer than the window
    (_stream(2.0, 9.0, 50), 0.0),                    # before it
    (_stream(61.0, 70.0, 50), 0.0),                  # after it
    (_stream(30.0, 30.0, 1, chunks=2), 1.0),         # one token, one instant
    (dict(_stream(30.0, 40.0, 9), error="HTTP 500"), 0.0),
    # cut at the end of the cool-down, begun inside the window: max_tokens
    # ending at the cut
    (dict(_stream(50.0, 0.0, 0), done=None, abandoned=True,
          abandoned_at=90.0, max_tokens=80), 20.0),
    # cut before its first token
    ({"first": None, "chunk_times": [], "done": None, "abandoned": True,
      "abandoned_at": 90.0, "max_tokens": 80, "completion_tokens": 0,
      "error": None}, 0.0),
])
def test_tokens_inside_the_window_by_hand(record, expected):
    mod = common.load_module("metrics", "serve_tokens_per_s")
    assert mod.window_tokens([record], 10.0, 60.0) == pytest.approx(expected)
    run = {"records": [record, record],
           "window": {"t0": 10.0, "t1": 60.0, "seconds": 50.0}}
    assert mod.reduce(run) == pytest.approx(2 * expected / 50.0)


def test_train_batches_are_pure_and_in_vocabulary():
    a = train_batches.batch(2**31 + 5, 3, 8, 64, 512)
    b = train_batches.batch(2**31 + 5, 3, 8, 64, 512)
    assert a.shape == (8, 65) and (a == b).all()
    assert a.min() >= 0 and a.max() < 512
    assert not (a == train_batches.batch(2**31 + 5, 4, 8, 64, 512)).all()
    assert not (a == train_batches.batch(2**31 + 6, 3, 8, 64, 512)).all()
    cell = common.load_json(common.bench_dir(), "workloads",
                            "mistral7b-train-fsdp4.json")
    assert train_batches.plan(cell["traffic"], 9, 51.0) == {
        "mode": "train", "seed": 9, "distinct_batches": 1}


def test_every_seed_replays_one_schedule_from_another_start():
    """The chat cell's schedule: every seed sees the same cyclic sequence of
    (gap, prompt, output) triples, from another starting point."""
    params = _traffic("mistral7b-serve-chat")
    a = open_loop.plan(params, 5, 51.0)["requests"]
    b = open_loop.plan(params, 2**31 + 9, 51.0)["requests"]

    def triples(reqs):
        gaps = [r["due_s"] - (reqs[i - 1]["due_s"] if i else 0.0)
                for i, r in enumerate(reqs)]
        return [(round(g, 9), r["prompt_tokens"], r["max_tokens"])
                for g, r in zip(gaps, reqs)]

    ta, tb = triples(a), triples(b)
    k = tb.index(ta[0])
    assert k != 0 and tb[k:] + tb[:k] == ta
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


@pytest.mark.parametrize("cell", CELLS)
def test_both_serve_cells_order_their_lengths_by_the_one_rule(cell):
    a, b = _plan(cell, 5)["requests"], _plan(cell, 2**31 + 9)["requests"]
    pa = [(r["prompt_tokens"], r["max_tokens"]) for r in a]
    pb = [(r["prompt_tokens"], r["max_tokens"]) for r in b]
    assert pa != pb and any(pb[k:] + pb[:k] == pa for k in range(len(pb)))


LONG = {"generator": "open_loop", "rate_per_s": 4.4, "schedule_seed": 24,
        "prompt_tokens": {"median": 1408, "sigma": 0.25, "min": 1024,
                          "max": 1920},
        "output_tokens": {"median": 32, "sigma": 0.5, "min": 16, "max": 64},
        "drain_timeout_s": 60}


def test_the_serve_configuration_admits_long_prompts_uncut():
    """The traffic of the queued cell mistral7b-serve-longprompt (PERF.md
    section 7; measured by PR 27, not added) is a row of data for the
    configuration as it stands: every prompt lies above the engine's
    prefill_chunk (none uses a whole-prompt program) and within its
    max_prompt_len (none is cut), prompt + output fits max_seq_len, and
    the warm set holds every bucket a final chunk can fall into."""
    config = common.load_cell("mistral7b-serve-chat")[2]
    eng = config["engine"]
    assert eng["prefill_chunk"] < LONG["prompt_tokens"]["min"]
    assert LONG["prompt_tokens"]["max"] <= eng["max_prompt_len"]
    assert LONG["prompt_tokens"]["max"] + LONG["output_tokens"]["max"] \
        <= eng["max_seq_len"]
    for seed in (3, 2**31 + 77):
        reqs = open_loop.plan(LONG, seed, 51.0)["requests"]
        assert len(reqs) == 224
        for r in reqs:
            assert 1024 <= r["prompt_tokens"] <= 1920
            assert 16 <= r["max_tokens"] <= 64
            progs = common.programs_for_prompt(r["prompt_tokens"], eng)
            assert 2 <= len(progs) <= 4
            assert {k for k, _b in progs} == {"chunk"}
    warm = common.warm_prompt_lengths(1024, 1920, eng)
    finals = {common.programs_for_prompt(n, eng)[-1] for n in warm}
    assert finals == {("chunk", b) for b in (16, 32, 64, 128, 256, 512)}
    assert all(1024 <= n <= 1920 for n in warm)


def test_chat_and_peak_run_the_programs_they_ran_under_the_old_cap():
    """max_prompt_len went 1024 -> 1920 (PR 27): no bucket up to
    prefill_chunk is capped by either, so prompts up to 1024 use the same
    programs, and the cells' warm sets are the same."""
    eng = common.load_cell("mistral7b-serve-chat")[2]["engine"]
    old = dict(eng, max_prompt_len=1024)
    assert eng["max_prompt_len"] == 1920
    for n in range(33, 1025):
        assert common.programs_for_prompt(n, eng) \
            == common.programs_for_prompt(n, old)
    assert common.warm_prompt_lengths(33, 1024, eng) \
        == common.warm_prompt_lengths(33, 1024, old)
