"""The Trinity-Large family in the benchmark (ISSUE 52): check 1 at the tiny
preset (float32 on the CPU, window 16, pages of 8: nothing here is a device
number) holds on any seed on both backends, with prompts past the window
and the ring's wrap, and each negative control fails it; the whole command
rehearses the cell; the new readers and counts; the configuration's
arithmetic.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import checks, common, costs, costs_window

CELL = "trinity-large-serve-longctx"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 23 * i for i in range(3)]


def _at_size():
    spec = importlib.util.spec_from_file_location(
        "trinity_at_size", os.path.join(
            common.ROOT, "tests", "benchmark_suite", "trinity_at_size.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


AT_SIZE = _at_size()


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed, backend):
    """A whole prefill of 20, a chunked one of 110 (chunks of 32 on a ring
    of 7 pages of 8: past the window from the first chunk's second half,
    past the ring's 56 positions in the second, past window + ring in the
    third) and ten decode steps each across a page edge, the second
    sequence's across a ring entry written for the third time, against a
    reference whose window is a band in a mask."""
    out = checks.logits_check(FAM, SZ, {**ENG, "attention_kernel": backend},
                              CHK, seed)
    assert out["ok"] and out["routing"]["ok"] and out["backend"] == backend
    # three routed layers at depth 4
    assert out["routing"]["decisions"] == 3 * (20 + 110 + 2 * 10)
    assert out["depth"] == 4 and out["max_abs_err"] < 0.1 * CHK["tolerance"]


# (control, the limits that must refuse it, its max_abs_err over the limit)
CONTROLS = [
    ("window_short", "all", 100), ("window_long", "all", 100),
    ("full_rotated", "all", 100), ("window_unrotated", "all", 100),
    ("no_gate", "all", 100), ("no_n2", "all", 100), ("no_n4", "all", 100),
    ("no_mup", "all", 100), ("no_shared_expert", "all", 100),
    ("no_selection_bias", "routing", 0),
    ("int8_weights", "all", 20), ("short_ring", "all", 100)]


@pytest.mark.parametrize("control,refused_by,factor", CONTROLS)
def test_negative_control_fails_check_1(control, refused_by, factor):
    """Each is ``ok`` false, by every limit (a wrong hidden state moves the
    logits AND turns choices over in the layers after it); the selection
    bias left out by the two limits on the choice alone (the logits follow
    the forced choice)."""
    fam, kw = FAM, {}
    if control == "int8_weights":
        kw = {"mutate": AT_SIZE.int8_weights}
    elif control == "short_ring":
        fam = AT_SIZE.short_ring(FAM)
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
    assert (sz["n_layers"], sz["attn_layers"], sz["n_dense"],
            sz["window_layers"]) == (5, 5, 1, 4)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]) \
        == (3072, 48, 8, 128)
    assert (sz["n_experts"], sz["router_experts"], sz["top_k"],
            sz["expert_dim"], sz["ffn_dim"], sz["vocab_size"], sz["window"],
            sz["prefill_chunk"]) == (32, 256, 4, 3072, 12288, 200192, 4096,
                                     512)
    cfg = FAM.model_config(sz)
    assert (cfg.n_experts, cfg.experts_held, cfg.scaling, cfg.mup) \
        == (256, 32, 2.448, True)
    assert FAM.num_params(cfg) == 5_398_136_064       # 10.80 GB in bf16
    # check 1's model: the dense window layer, two routed window layers
    # and the routed full layer
    four = FAM.model_config(sz, n_layers=4)
    assert FAM.num_params(four) == 4_400_141_056       # 8.80 GB in bf16
    assert FAM.reference_kwargs(cfg)["held"] == (0, 32)
    assert {"gate", "shared_expert", "router", "experts"} \
        <= set(FAM.MODEL_SCOPES)


def test_configuration_states_source_cut_deployment_and_assumptions():
    pub = CONFIG["published"]
    cut = {"num_hidden_layers": (60, 5), "num_dense_layers": (6, 1),
           "num_experts": (256, 32)}
    for key, value in pub.items():
        if key in cut:
            assert (value, CONFIG[key]) == cut[key]
        elif key == "layer_types":
            assert CONFIG[key] == value[:5] == [
                "sliding_attention"] * 3 + ["full_attention",
                                            "sliding_attention"]
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "layer_types", "num_experts"]
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert {"gate", "full_layers_unrotated", "window_edge", "qk_norm",
            "norms", "mup", "routing_epsilon", "selection_bias",
            "rope_pairing", "max_seq_len", "vocab_size", "weights"} \
        <= set(CONFIG["assumed"])
    assert "8-chip" in CONFIG["deployment"]
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng, chk = CONFIG["engine"], CONFIG["checks"]["logits"]
    assert (eng["max_batch_size"], eng["num_pages"], eng["max_seq_len"],
            eng["max_prompt_len"], eng["page_size"]) \
        == (24, 2881, 16384, 12288, 128)
    # check 1: past the window, past the ring, past window + ring; a
    # partial last chunk; decode across a page edge twice
    from ray_tpu.serve.llm import kv_cache as kvc
    page, chunk = eng["page_size"], eng["prefill_chunk"]
    ring = kvc.ring_pages(CONFIG["sliding_window"], page, chunk)
    assert ring == 37 and eng["num_pages"] == 24 * 120 + 1
    assert chk["depth"] == 4
    assert chk["chunked_prompt_tokens"] \
        > CONFIG["sliding_window"] + ring * page
    for n in (chk["whole_prompt_tokens"], chk["chunked_prompt_tokens"]):
        assert n // page < (n + chk["decode_steps"]) // page
    assert chk["chunked_prompt_tokens"] % chunk not in (0, chunk)
    for key in ("tolerance", "rms_tolerance", "routing_slack",
                "routing_flip_share_max"):
        assert "my chip runs, PR 52" in chk[f"{key}_why"], key
    traffic = CELL_FILE["traffic"]
    assert (traffic["clients"], traffic["ramp_s"], traffic["cooldown_s"],
            traffic["schedule_seed"], traffic["pool"]) == (48, 24, 24, 24, 512)
    assert traffic["prompt_tokens"] == {"median": 8192, "sigma": 0.15,
                                        "min": 6144, "max": 12288}
    assert traffic["output_tokens"] == {"median": 1536, "sigma": 0.25,
                                        "min": 768, "max": 3072}
    assert CELL_FILE["trace_seconds"] == 4
    # every prompt is past the window; the longest request fits the table
    assert traffic["prompt_tokens"]["min"] > CONFIG["sliding_window"]
    assert traffic["prompt_tokens"]["max"] \
        + traffic["output_tokens"]["max"] <= 120 * page
    assert (ENTRY["traffic"], ENTRY["chips"]) == ("longctx", 1)


def test_window_call_counts():
    """One decode call of 24 slots at 10,000 tokens: a full layer reads
    every token, a window layer 4,096 a slot; a chunk of 512 at 8,192."""
    shape = (8, 128, 48)
    full = costs_window.paged_read_bytes(24 * 10000, 24, *shape)
    ring = costs_window.paged_read_bytes(24 * 4096, 24, *shape)
    assert full == 24 * 10000 * 4096 + 24 * 48 * 128 * 2 * 2
    assert 2.4 < full / ring < 2.5
    assert full == costs.paged_decode_bytes([10000] * 24, 8, 128, 48)
    # a chunk's rows: row i of a full layer sees start + i + 1 keys
    assert costs_window.chunk_pairs(8192, 512, 0) \
        == sum(8192 + i + 1 for i in range(512))
    assert costs_window.chunk_pairs(8192, 512, 4096) == 512 * 4096
    assert costs_window.chunk_pairs(0, 512, 4096) == 512 * 513 // 2
    assert costs_window.chunk_keys(8192, 512, 0) == 8704
    assert costs_window.chunk_keys(8192, 512, 4096) == 4096 + 511
    assert costs_window.chunk_keys(0, 100, 4096) == 100
    # the MXU bounds a chunk call on a v5e, the bandwidth a decode call
    peak = common.peaks("TPU v5 lite")
    assert costs.roofline_s(
        costs_window.paged_chunk_flops(512 * 4096, 48, 128),
        costs_window.paged_read_bytes(4607, 512, *shape), peak)[1] \
        == "compute"
    assert costs.roofline_s(0.0, ring, peak)[1] == "bandwidth"


def _reader(name):
    return common.load_module("metrics", name).reduce


NEW_READERS = ("paged_window_roofline_traced",
               "paged_window_chunk_roofline",
               "window_attn_share", "full_attn_share",
               "ring_pages_share")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_of_a_parent_and_do_not_raise(name):
    """No trace; and a trace of a program without the scopes, the span
    arguments or the gauges, under a configuration without window layers."""
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


def test_trace_readers_find_the_walking_calls_by_their_scopes():
    """A hand-made trace: one decode execution of two steps at depth 5
    (four window layers, one full) matched to its dispatch span (k = 2, 24
    slots, 240,000 cached tokens, 24 x 4,096 inside windows), and one chunk
    of 512 at 8,192."""
    from benchmark import span_reduce
    sz = FAM.sizes(CONFIG, False)
    ms = 1_000_000
    rows = [["module", "jit__lambda", 0, 40 * ms, "", 0],
            ["span", "decode_dispatch", 0, ms // 10,
             {"k": 2, "seq": 1, "active": 24, "ctx_tokens": 240000,
              "window_tokens": 24 * 4096}, 0]]
    t = 0
    step = "jit(f)/decode_block/decode_step/"
    walk = "/jit(_gqa_walk_call)/paged_decode_attention/pallas_call"
    for _step in range(2):
        for layer in range(5):
            scope, dur = ("attn_full", 2) if layer == 3 else ("attn_window",
                                                              1)
            rows.append(["op", "custom-call", t, dur * ms,
                         f"{step}attn/{scope}{walk}", 0])
            t += dur * ms
            rows.append(["op", "fusion", t, ms, step + "gate/mul", 0])
            t += ms
            rows.append(["op", "custom-call", t, ms,
                         step + "experts/grouped_ffn/gmm/pallas_call", 0])
            t += ms
    t = 50 * ms
    rows.append(["module", "jit_impl", t, 20 * ms, "", 0])
    rows.append(["span", "chunk_prefill", t - ms, ms // 10,
                 {"rid": "r", "clen": 512, "start": 8192, "tokens": 512,
                  "last": 0}, 0])
    chunk = "jit(f)/prefill_chunk/attn/"
    for layer in range(5):
        scope = "attn_full" if layer == 3 else "attn_window"
        rows.append(["op", "custom-call", t, 2 * ms,
                     f"{chunk}{scope}/jit(_gqa_walk_call)/"
                     f"paged_chunk_attention/pallas_call", 0])
        t += 3 * ms
    trace = span_reduce.from_rows(rows)
    run = {"span_trace": trace, "trace_dir": "x", "sizes": sz, "family": FAM,
           "kind": "serve", "device": {"kind": "TPU v5 lite"},
           "stats_before": {}, "stats_after": {},
           "stats_samples": [
               (0.5, {"window_pages_in_use": 24 * 37,
                      "full_pages_in_use": 24 * 74}),
               (1.5, {"window_pages_in_use": 12 * 37,
                      "full_pages_in_use": 12 * 74})]}
    total = 2 * (4 * 1 + 2 + 5 + 5)
    assert _reader("window_attn_share")(run) \
        == pytest.approx(100 * 2 * 4 / total)
    assert _reader("full_attn_share")(run) \
        == pytest.approx(100 * 2 * 2 / total)
    assert _reader("decode_step_traced_ms")(run) \
        == pytest.approx(20.0)
    assert _reader("ring_pages_share")(run) == pytest.approx(50.0)
    peak = common.peaks("TPU v5 lite")
    shape = (8, 128, 48)
    need = sum(
        costs_window.paged_read_bytes(240000 + 24 * (s + 1), 24, *shape)
        + 4 * costs_window.paged_read_bytes(24 * 4096, 24, *shape)
        for s in range(2)) / peak["hbm_bytes_per_s"]
    roof = _reader("paged_window_roofline_traced")(run)
    assert roof == pytest.approx(100 * need / 12e-3)
    assert 0 < roof < 100
    need = 4 * costs_window.paged_chunk_flops(512 * 4096, 48, 128) \
        / peak["bf16_flops_per_s"] + costs_window.paged_chunk_flops(
            costs_window.chunk_pairs(8192, 512, 0), 48, 128) \
        / peak["bf16_flops_per_s"]
    roof = _reader("paged_window_chunk_roofline")(run)
    assert roof == pytest.approx(100 * need / 10e-3)
    assert 0 < roof < 100
