"""The MiMo-V2-Flash family in the benchmark (ISSUE 55): check 1 at the tiny
preset (float32 on the CPU, keys of 24 lanes on values of 16, 2 and 4 KV
heads, a window of ONE page of 8, sinks: nothing here is a device number)
holds on any seed on both backends, with prompts past the window and the
ring's wrap, and each negative control fails it; the whole command
rehearses the cell; the new readers and counts; the configuration's
arithmetic.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import checks, common, costs, costs_mixed, costs_window

CELL = "mimo-v2-flash-serve-reasoning"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 29 * i for i in range(3)]


def _at_size():
    spec = importlib.util.spec_from_file_location(
        "mimo_at_size", os.path.join(
            common.ROOT, "tests", "benchmark_suite", "mimo_at_size.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


AT_SIZE = _at_size()


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed, backend):
    """A whole prefill of 20, a chunked one of 110 (chunks of 32 on a ring
    of 6 pages of 8: past the window inside the first chunk, past the
    ring's 48 positions in the second, past window + ring in the same) and
    ten decode steps each across a page edge, against a reference whose
    window is a band in a mask and whose sink a column of the softmax."""
    out = checks.logits_check(FAM, SZ, {**ENG, "attention_kernel": backend},
                              CHK, seed)
    assert out["ok"] and out["routing"]["ok"] and out["backend"] == backend
    # five routed layers at depth 6
    assert out["routing"]["decisions"] == 5 * (20 + 110 + 2 * 10)
    assert out["depth"] == 6 and out["max_abs_err"] < 0.1 * CHK["tolerance"]


# (control, the limits that must refuse it, its max_abs_err over the limit)
CONTROLS = [
    ("window_short", "all", 100), ("window_long", "all", 100),
    ("no_sink", "all", 100), ("sink_in_full_layers", "all", 100),
    ("no_value_scale", "all", 100), ("every_lane_rotated", "all", 100),
    ("thetas_swapped", "all", 100), ("no_selection_bias", "routing", 0),
    ("int8_weights", "all", 20), ("short_ring_1", "all", 20),
    ("short_ring_pairs", "all", 20)]


@pytest.mark.parametrize("control,refused_by,factor", CONTROLS)
def test_negative_control_fails_check_1(control, refused_by, factor):
    """Each is ``ok`` false, by every limit (a wrong hidden state moves the
    logits AND turns choices over in the layers after it); the selection
    bias left out by the two limits on the choice alone (the logits follow
    the forced choice)."""
    fam, kw = FAM, {}
    if control == "int8_weights":
        kw = {"mutate": AT_SIZE.int8_weights}
    elif control.startswith("short_ring"):
        # (the rehearsal's engine is the gather backend: it scatters before
        # it reads, so a ring ONE page short already loses a live token)
        fam = AT_SIZE.short_ring(FAM, {"1": 1, "s": 0}[control[-1]])
    else:
        kw = AT_SIZE.controls(SZ)[control]
    for seed in SEEDS[:2]:
        got = AT_SIZE.brief(checks.logits_check(fam, SZ, ENG, CHK, seed,
                                                **kw))
        assert not got["ok"]
        logits = {"tolerance", "rms_tolerance"}
        routing = {"routing_slack", "routing_flip_share_max"}
        want = {"all": logits | routing, "routing": routing}[refused_by]
        assert set(got["failed_by"]) == want, got
        assert got["max_abs_err"] >= factor * CHK["tolerance"], got


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("length,short,refused", [
    (49, 1, True), (49, 3, False), (49, 0, False),
    (110, 1, False), (110, 3, False), (110, 0, True)],
    ids=["49_one_short", "49_three_short", "49_pairs",
         "110_one_short", "110_three_short", "110_pairs"])
def test_where_the_prompt_ends_decides_which_ring_alias_the_kernel_shows(
        length, short, refused, seed):
    """The pallas backend writes the call's rows inside the walking kernel,
    which reads before it writes: an alias loses a live token only where
    one call writes two NEIGHBOURING logical pages that share a page and
    ends inside the later one (``mimo_at_size.short_ring``). A ring one
    page short: entries 5 and 0, a prompt of 49 (page 6, with page 5 in its
    chunk); neighbours on one page: a prompt that ends in an odd page (110:
    page 13); three short: never. The committed length at size ends as 49
    does here (``test_sizes_carry_what_the_readers_divide_by``)."""
    got = AT_SIZE.brief(checks.logits_check(
        AT_SIZE.short_ring(FAM, short), SZ,
        {**ENG, "attention_kernel": "pallas"},
        {**CHK, "chunked_prompt_tokens": length}, seed))
    assert got["backend"] == "pallas" and got["ok"] is not refused, got
    if refused:
        assert {"tolerance", "rms_tolerance"} <= set(got["failed_by"]), got


def test_the_whole_command_rehearses_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 99), "--seconds", "3", "--trace", "0",
         "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=common.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_sizes_carry_what_the_readers_divide_by():
    sz = FAM.sizes(CONFIG, False)
    assert (sz["n_layers"], sz["attn_layers"], sz["window_layers"]) \
        == (7, 7, 5)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"],
            sz["window_kv_heads"], sz["head_dim"], sz["value_dim"],
            sz["rotary_dim"]) == (4096, 64, 4, 8, 192, 128, 64)
    assert (sz["n_experts"], sz["router_experts"], sz["top_k"],
            sz["expert_dim"], sz["ffn_dim"], sz["vocab_size"], sz["window"],
            sz["prefill_chunk"]) == (16, 256, 8, 2048, 16384, 152576, 128,
                                     512)
    assert costs_mixed.layer_shape(sz, False) == (4, 192, 128, 64)
    assert costs_mixed.layer_shape(sz, True) == (8, 192, 128, 64)
    cfg = FAM.model_config(sz)
    assert (cfg.n_experts, cfg.experts_held, cfg.value_scale, cfg.pattern,
            cfg.moe_freq) == (256, 16, 0.707, (0, 1, 1, 1, 1, 0, 1),
                              (0, 1, 1, 1, 1, 1, 1))
    assert FAM.num_params(cfg) == 4_523_620_160       # 9.05 GB in bf16
    # check 1's model: the dense full layer, four routed window layers and
    # the routed full layer
    six = FAM.model_config(sz, n_layers=6)
    assert FAM.num_params(six) == 4_523_620_160 - 498_082_112 \
        == 4_025_538_048                               # 8.05 GB in bf16
    assert FAM.reference_kwargs(cfg)["held"] == (0, 16)
    assert {"router", "experts", "attn"} <= set(FAM.MODEL_SCOPES)
    assert not {"shared_expert", "gate"} & set(FAM.MODEL_SCOPES)
    # a configuration the program cannot run is refused by the adapter
    with pytest.raises(ValueError, match="no shared expert"):
        FAM.sizes({**CONFIG, "n_shared_experts": 1}, False)


def test_configuration_states_source_cut_deployment_and_assumptions():
    pub = CONFIG["published"]
    cut = {"num_hidden_layers": (48, 7), "n_routed_experts": (256, 16)}
    for key, value in pub.items():
        if key in cut:
            assert (value, CONFIG[key]) == cut[key]
        elif key in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert CONFIG[key] == value[:7] and len(value) == 48
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert CONFIG["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert sum(pub["hybrid_layer_pattern"]) == 39
    assert CONFIG["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                                 "moe_layer_freq", "n_routed_experts"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert {"qk_norm", "rope_pairing", "window_edge", "sink", "value_scale",
            "routing_epsilon", "selection_bias", "groups", "mtp_layers",
            "attention_chunk_size", "max_seq_len", "vocab_size", "weights"} \
        <= set(CONFIG["assumed"])
    assert "16-chip" in CONFIG["deployment"]
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng, chk = CONFIG["engine"], CONFIG["checks"]["logits"]
    assert (eng["max_batch_size"], eng["num_pages"], eng["max_seq_len"],
            eng["max_prompt_len"], eng["page_size"], eng["prefill_chunk"],
            eng["decode_block"], eng["pipeline_depth"],
            eng["attention_kernel"]) \
        == (48, 3457, 9216, 6144, 128, 512, 8, 3, "auto")
    # check 1: past the window, past the ring, past window + ring several
    # times; a partial last chunk; decode across a page edge twice
    from ray_tpu.serve.llm import kv_cache as kvc
    page, chunk = eng["page_size"], eng["prefill_chunk"]
    ring = kvc.ring_pages(CONFIG["sliding_window"], page, chunk)
    assert ring == 6 and eng["num_pages"] == 48 * 72 + 1
    assert CONFIG["sliding_window"] == page          # a window of ONE page
    assert chk["depth"] == 6
    assert chk["chunked_prompt_tokens"] \
        > 3 * (CONFIG["sliding_window"] + ring * page)
    for n in (chk["whole_prompt_tokens"], chk["chunked_prompt_tokens"]):
        assert n // page < (n + chk["decode_steps"]) // page
    assert chk["chunked_prompt_tokens"] % chunk not in (0, chunk)
    # it ends INSIDE a page of ring entry 0 whose chunk also wrote entry
    # 5's page: where a ring one page short loses live rows
    last = chk["chunked_prompt_tokens"] // page
    assert last % ring == 0 and chk["chunked_prompt_tokens"] % page
    assert (last - 1) * page >= chk["chunked_prompt_tokens"] // chunk * chunk
    for key in ("tolerance", "rms_tolerance", "routing_slack",
                "routing_flip_share_max"):
        assert "my chip runs, PR 55" in chk[f"{key}_why"], key
    assert "my chip runs, PR 55" in CONFIG["checks"]["served_tokens"][
        "margin_why"]
    traffic = CELL_FILE["traffic"]
    assert (traffic["clients"], traffic["cooldown_s"],
            traffic["schedule_seed"], traffic["pool"]) == (96, 24, 24, 512)
    assert 24 <= traffic["ramp_s"] <= 48
    assert traffic["prompt_tokens"] == {"median": 4096, "sigma": 0.15,
                                        "min": 3072, "max": 6144}
    assert traffic["output_tokens"] == {"median": 1536, "sigma": 0.25,
                                        "min": 768, "max": 3072}
    assert CELL_FILE["trace_seconds"] == 4
    # every prompt is 24 windows or more; the longest request fits the table
    assert traffic["prompt_tokens"]["min"] >= 24 * CONFIG["sliding_window"]
    assert traffic["prompt_tokens"]["max"] \
        + traffic["output_tokens"]["max"] <= 72 * page
    assert (ENTRY["traffic"], ENTRY["chips"]) == ("reasoning", 1)


def test_mixed_call_counts():
    """One decode call of 48 slots at 5,000 tokens: a full layer reads
    every token at 4 KV heads, a window layer 128 a slot at 8; K at 192
    lanes, V at 128; a chunk of 512 at 4,096."""
    full = costs_mixed.paged_read_bytes(48 * 5000, 48, 4, 192, 128, 64)
    ring = costs_mixed.paged_read_bytes(48 * 128, 48, 8, 192, 128, 64)
    assert full == (48 * 5000 * 4 + 48 * 64) * 320 * 2
    assert ring == (48 * 128 * 8 + 48 * 64) * 320 * 2
    assert 18 < full / ring < 19
    # equal widths and heads: benchmark/costs_window.py's count
    assert costs_mixed.paged_read_bytes(1000, 24, 8, 128, 128, 48) \
        == costs_window.paged_read_bytes(1000, 24, 8, 128, 48)
    assert costs_mixed.paged_chunk_flops(1000, 48, 128, 128) \
        == costs_window.paged_chunk_flops(1000, 48, 128)
    assert costs_mixed.paged_chunk_flops(512 * 128, 64, 192, 128) \
        == 2 * 512 * 128 * 64 * 320
    assert costs_mixed.layer_shape({"n_kv_heads": 8, "head_dim": 128,
                                    "n_heads": 48}, True) is None
    # the MXU bounds a full layer's chunk call on a v5e, the bandwidth a
    # decode call of either kind
    peak = common.peaks("TPU v5 lite")
    assert costs.roofline_s(
        costs_mixed.paged_chunk_flops(
            costs_window.chunk_pairs(4096, 512, 0), 64, 192, 128),
        costs_mixed.paged_read_bytes(4608, 512, 4, 192, 128, 64), peak)[1] \
        == "compute"
    assert costs.roofline_s(0.0, full, peak)[1] == "bandwidth"


def _reader(name):
    return common.load_module("metrics", name).reduce


NEW_READERS = ("paged_full_roofline_traced",
               "paged_ring_roofline_traced",
               "paged_mixed_chunk_roofline")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_of_a_parent_and_do_not_raise(name):
    """No trace; a trace of a program without the scopes or the span
    arguments, under a configuration whose sizes state one head shape; and
    Trinity's sizes (window layers of the same heads and width)."""
    from benchmark import span_reduce
    old_fam = common.load_module("models", "llama")
    run = {"stats_before": {"steps": 1}, "stats_after": {"steps": 9},
           "stats_samples": [(0.5, {"free_pages": 3})],
           "sizes": {"n_layers": 16, "dim": 4096, "n_heads": 32},
           "trace_dir": None, "family": old_fam, "kind": "serve",
           "device": {"kind": "TPU v5 lite"}}
    assert _reader(name)(run) is None
    ms = 1_000_000
    rows = [["module", "jit__lambda", 0, 10 * ms, "", 0],
            ["op", "custom-call", 0, ms, "jit(f)/decode_block/decode_step/"
             "attn/paged_decode_attention/pallas_call", 0],
            ["op", "fusion", ms, ms, "jit(f)/decode_block/decode_step/mlp/dot",
             0]]
    run = {**run, "span_trace": span_reduce.from_rows(rows), "trace_dir": "x"}
    assert _reader(name)(run) is None
    trinity = common.load_cell("trinity-large-serve-longctx")[2]
    tfam = common.family(trinity)
    assert _reader(name)({**run, "family": tfam,
                          "sizes": tfam.sizes(trinity, False)}) is None


def test_trace_readers_find_the_calls_of_each_kind_by_their_scopes():
    """A hand-made trace: one decode execution of two steps at depth 7
    (five window layers, two full) matched to its dispatch span (k = 2, 48
    slots, 240,000 cached tokens, 48 x 128 inside windows), and one chunk
    of 512 at 4,096."""
    from benchmark import span_reduce
    sz = FAM.sizes(CONFIG, False)
    ms = 1_000_000
    rows = [["module", "jit__lambda", 0, 60 * ms, "", 0],
            ["span", "decode_dispatch", 0, ms // 10,
             {"k": 2, "seq": 1, "active": 48, "ctx_tokens": 240000,
              "window_tokens": 48 * 128}, 0]]
    t = 0
    step = "jit(f)/decode_block/decode_step/"
    walk = "/jit(_gqa_walk_call)/paged_decode_attention/pallas_call"
    for _step in range(2):
        for layer in range(7):
            scope, dur = ("attn_full", 2) if layer in (0, 5) \
                else ("attn_window", 1)
            rows.append(["op", "custom-call", t, dur * ms,
                         f"{step}attn/{scope}{walk}", 0])
            t += dur * ms
            rows.append(["op", "custom-call", t, ms,
                         step + "experts/grouped_ffn/gmm/pallas_call", 0])
            t += ms
    t = 70 * ms
    rows.append(["module", "jit_impl", t, 30 * ms, "", 0])
    rows.append(["span", "chunk_prefill", t - ms, ms // 10,
                 {"rid": "r", "clen": 512, "start": 4096, "tokens": 512,
                  "last": 0}, 0])
    chunk = "jit(f)/prefill_chunk/attn/"
    for layer in range(7):
        scope = "attn_full" if layer in (0, 5) else "attn_window"
        rows.append(["op", "custom-call", t, 2 * ms,
                     f"{chunk}{scope}/jit(_gqa_walk_call)/"
                     f"paged_chunk_attention/pallas_call", 0])
        t += 3 * ms
    trace = span_reduce.from_rows(rows)
    run = {"span_trace": trace, "trace_dir": "x", "sizes": sz, "family": FAM,
           "kind": "serve", "device": {"kind": "TPU v5 lite"},
           "stats_before": {}, "stats_after": {},
           "stats_samples": [
               (0.5, {"window_pages_in_use": 48 * 6,
                      "full_pages_in_use": 48 * 40})]}
    total = 2 * (5 * 1 + 2 * 2 + 7)
    assert _reader("window_attn_share")(run) \
        == pytest.approx(100 * 2 * 5 / total)
    assert _reader("full_attn_share")(run) \
        == pytest.approx(100 * 2 * 4 / total)
    assert _reader("ring_pages_share")(run) == pytest.approx(15.0)
    peak = common.peaks("TPU v5 lite")
    need = sum(2 * costs_mixed.paged_read_bytes(
        240000 + 48 * (s + 1), 48, 4, 192, 128, 64)
        for s in range(2)) / peak["hbm_bytes_per_s"]
    roof = _reader("paged_full_roofline_traced")(run)
    assert roof == pytest.approx(100 * need / 8e-3)
    assert 0 < roof < 100
    need = 2 * 5 * costs_mixed.paged_read_bytes(
        48 * 128, 48, 8, 192, 128, 64) / peak["hbm_bytes_per_s"]
    roof = _reader("paged_ring_roofline_traced")(run)
    assert roof == pytest.approx(100 * need / 10e-3)
    assert 0 < roof < 100
    need = sum(costs.roofline_s(
        costs_mixed.paged_chunk_flops(
            costs_window.chunk_pairs(4096, 512, w), 64, 192, 128),
        costs_mixed.paged_read_bytes(
            costs_window.chunk_keys(4096, 512, w), 512, hkv, 192, 128, 64),
        peak)[0] for w, hkv in [(0, 4)] * 2 + [(128, 8)] * 5)
    roof = _reader("paged_mixed_chunk_roofline")(run)
    assert roof == pytest.approx(100 * need / 14e-3)
    assert 0 < roof < 100
