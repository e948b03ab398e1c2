"""The LFM2-MoE family in the benchmark (ISSUE 35): check 1 at the tiny
preset (float32 on the CPU: nothing here is a device number) holds on any
seed and each negative control fails it by a wide factor; the whole
command rehearses cell 1; the new readers and counts; the configuration's
arithmetic.
"""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import checks, common, costs_routed

CELL = "lfm2-8b-a1b-serve-decode"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 17 * i for i in range(6)]


@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed):
    out = checks.logits_check(FAM, SZ, ENG, CHK, seed)
    assert out["ok"] and out["routing"]["ok"], out
    assert out["routing"]["decisions"] == 2 * (20 + 70 + 2 * 4)
    assert out["depth"] == 4 and out["max_abs_err"] < 0.1 * CHK["tolerance"]


def _state_not_carried():
    """The family with ONE fault in its programs: a chunk that does not
    start its sequence finds zeros where its predecessor's state was."""
    def paged_programs(cfg, page, backend):
        init, prefill, chunk, decode = FAM.paged_programs(cfg, page, backend)

        def forgetful(params, cache, table, tokens, start, total):
            cache = {**cache, "state": tuple(
                s.at[table[0]].set(0) for s in cache["state"])}
            return chunk(params, cache, table, tokens, start, total)
        return init, prefill, forgetful, decode
    fam = types.SimpleNamespace(**{k: getattr(FAM, k) for k in dir(FAM)
                                   if not k.startswith("_")})
    fam.paged_programs = paged_programs
    fam.__file__ = FAM.__file__
    return fam


@pytest.mark.parametrize("control,factor", [
    ("state_not_carried", 20), ("bias_weighs", 10), ("norm_topk", 100),
    ("qk_norm", 100)])
def test_negative_control_fails_check_1_by_a_wide_factor(control, factor):
    fam, override = FAM, {}
    if control == "state_not_carried":
        fam = _state_not_carried()
    elif control == "bias_weighs":
        override = {"bias_weighs": True}
    else:
        override = {control: False}
    for seed in SEEDS[:2]:
        out = checks.logits_check(fam, SZ, ENG, CHK, seed, **override)
        assert not out["ok"]
        assert out["max_abs_err"] > factor * CHK["tolerance"], out
        if control == "state_not_carried":
            # only the chunked sequence is wrong
            assert out["errors"]["whole_prefill+decode"]["max_abs_err"] \
                < CHK["tolerance"]


def test_the_whole_command_rehearses_cell_1():
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
    assert (sz["n_layers"], sz["attn_layers"], sz["n_dense"]) == (16, 4, 2)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]) \
        == (2048, 32, 8, 64)
    assert (sz["n_experts"], sz["top_k"], sz["expert_dim"]) == (32, 4, 1792)
    cfg = FAM.model_config(sz)
    assert FAM.num_params(cfg) == 5_399_129_024      # 10.80 GB in bf16
    assert FAM.model_config(sz, n_layers=4).layer_types == (
        "conv", "conv", "full_attention", "conv")
    assert {"conv", "router", "experts"} <= set(FAM.MODEL_SCOPES)


def test_configuration_states_source_cut_and_assumptions():
    pub = CONFIG["published"]
    assert CONFIG["layer_types"] == pub["layer_types"][:16]
    assert pub["num_hidden_layers"] == 24 and len(pub["layer_types"]) == 24
    assert sorted(CONFIG["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert {"head_dim", "tie_word_embeddings", "rope_pairing", "qk_norm",
            "conv", "expert_bias"} <= set(CONFIG["assumed"])
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng = CONFIG["engine"]
    assert eng["num_pages"] >= eng["max_batch_size"] * 12 + 1


def test_grouped_call_counts():
    d, f = 2048, 1792
    assert costs_routed.grouped_ffn_flops(256, d, f) == 6.0 * 256 * d * f
    # all 32 experts touched: their three matrices once, 704.6 MB
    weights = costs_routed.grouped_ffn_bytes(0, 32, d, f)
    assert weights == 32 * 3 * d * f * 2 == 704_643_072
    assert costs_routed.grouped_ffn_bytes(256, 32, d, f) \
        == weights + 256 * 2 * d * 2
    assert costs_routed.grouped_ffn_bytes(256, 1, d, f) < weights / 16


def _reader(name):
    return common.load_module("metrics", name).reduce


def test_counter_readers_read_the_engines_counters_and_nothing_of_a_parent():
    before = {"experts_touched_total": 100, "routed_layer_steps_total": 10,
              "expert_rows_total": 500}
    after = {"experts_touched_total": 100 + 14 * 8 * 30,
             "routed_layer_steps_total": 10 + 14 * 8,
             "expert_rows_total": 500 + 14 * 8 * 256}
    run = {"stats_before": before, "stats_after": after,
           "sizes": FAM.sizes(CONFIG, False), "trace_dir": None}
    assert _reader("experts_touched_share")(run) \
        == pytest.approx(100 * 30 / 32)
    assert _reader("expert_ffn_roofline")(run) is None   # no trace
    assert _reader("routed_ffn_share")(run) is None
    # a program without the counters (the parent): nothing, and no raise
    old = {"stats_before": {"steps": 1}, "stats_after": {"steps": 9},
           "sizes": {"n_layers": 16, "dim": 4096}, "trace_dir": None}
    for name in ("experts_touched_share", "expert_ffn_roofline",
                 "routed_ffn_share"):
        assert _reader(name)(old) is None


def test_trace_readers_find_the_grouped_product_and_the_routed_scopes():
    """A hand-made trace: one decode execution of two steps, four kernel
    calls a step, with the grouped product's ops and the routed scopes."""
    from benchmark import span_reduce
    sz = FAM.sizes(CONFIG, False)
    ms = 1_000_000
    rows = [["module", "jit__lambda", 0, 40 * ms, "", 0]]
    t = 0
    for _step in range(2):
        for _layer in range(4):
            rows.append(["op", "custom-call", t, ms // 2,
                         "jit(f)/decode_block/decode_step/attn/"
                         "paged_decode_attention/pallas_call", 0])
            t += ms
        for _layer in range(14):
            rows.append(["op", "fusion", t, ms // 10,
                         "jit(f)/decode_block/decode_step/router/dot", 0])
            rows.append(["op", "ragged-dot", t + ms // 10, 9 * ms // 10,
                         "jit(f)/decode_block/decode_step/experts/"
                         "grouped_ffn/ragged_dot", 0])
            t += ms
    trace = span_reduce.from_rows(rows)
    counters = {"experts_touched_total": 28 * 32,
                "routed_layer_steps_total": 28,
                "expert_rows_total": 28 * 256}
    run = {"span_trace": trace, "trace_dir": "x", "sizes": sz,
           "family": FAM, "kind": "serve",
           "device": {"kind": "TPU v5 lite"},
           "stats_before": {k: 0 for k in counters}, "stats_after": counters}
    share = _reader("routed_ffn_share")(run)
    assert share == pytest.approx(100 * 28 / (28 + 4))
    roof = _reader("expert_ffn_roofline")(run)
    peak = common.peaks("TPU v5 lite")
    need = costs_routed.grouped_ffn_bytes(256, 32, 2048, 1792) \
        / peak["hbm_bytes_per_s"]
    assert roof == pytest.approx(100 * 28 * need / (28 * 0.9e-3))
    assert 0 < roof < 100
