"""The Falcon-H1 family in the benchmark (ISSUE 60): check 1 at the tiny
preset (float32 on the CPU: five query heads a KV head, 4 state heads of 16
in 2 groups, 16 state columns, a scan chunk and a page of 8; nothing here
is a device number) holds on any seed on both backends, and each negative
control of tests/benchmark_suite/falcon_h1_at_size.py fails it; the whole
command rehearses the cell; the new readers and counts; the
configuration's arithmetic.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import checks, common, costs, costs_ssm

CELL = "falcon-h1-34b-serve-decode"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 31 * i for i in range(3)]


def _at_size():
    spec = importlib.util.spec_from_file_location(
        "falcon_h1_at_size", os.path.join(
            common.ROOT, "tests", "benchmark_suite", "falcon_h1_at_size.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


AT_SIZE = _at_size()
CONTROLS = AT_SIZE.controls(FAM.sizes(CONFIG, False), ENG["prefill_chunk"])


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed, backend):
    """A whole prefill of 27, a chunked one of 77 (two chunks of 32, four
    scan chunks each, and 13 tokens that end inside a scan chunk) and six
    decode steps each across a page edge, the second sequence's state row
    beside the first's, two idle lanes in the trash row."""
    out = checks.logits_check(FAM, SZ, {**ENG, "attention_kernel": backend},
                              CHK, seed)
    assert out["ok"] and out["backend"] == backend and out["depth"] == 2
    assert out["max_abs_err"] < 0.5 * CHK["tolerance"]
    assert "routing" not in out           # ONE limit: the block does not route


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_reference_with_a_rule_left_out_fails_check_1(control):
    for seed in SEEDS[:2]:
        got = AT_SIZE.brief(checks.logits_check(FAM, SZ, ENG, CHK, seed,
                                                **CONTROLS[control]))
        assert not got["ok"] and got["max_abs_err"] > 100 * CHK["tolerance"]


@pytest.mark.parametrize("fault,factor", [
    ("padding_updates", 100), ("shared_row", 100), ("state_bf16", 5)])
def test_a_program_with_a_fault_in_its_state_fails_check_1(fault, factor):
    """In float32 at the tiny size even a state rounded to bfloat16 shows
    (at size, beside bfloat16 activations, it may not: the configuration's
    ``tolerance_why`` says what the chip read)."""
    for seed in SEEDS[:2]:
        with AT_SIZE.faulty(FAM, fault) as planted:
            got = AT_SIZE.brief(checks.logits_check(planted, SZ, ENG, CHK,
                                                    seed))
        assert not got["ok"] and got["max_abs_err"] > factor \
            * CHK["tolerance"], got
    # and the sound programs come back when the block ends
    assert checks.logits_check(FAM, SZ, ENG, CHK, SEEDS[0])["ok"]


def test_matrices_on_an_int8_grid_fail_check_1():
    for seed in SEEDS[:2]:
        got = checks.logits_check(FAM, SZ, ENG, CHK, seed,
                                  mutate=AT_SIZE.int8_weights)
        assert not got["ok"] and got["max_abs_err"] > 100 * CHK["tolerance"]


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
    assert (sz["n_layers"], sz["attn_layers"]) == (6, 6)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"],
            sz["ffn_dim"], sz["vocab_size"]) \
        == (5120, 20, 4, 128, 21504, 261120)
    assert (sz["ssm_heads"], sz["ssm_head_dim"], sz["ssm_state"],
            sz["ssm_groups"], sz["ssm_conv"], sz["ssm_chunk"]) \
        == (32, 128, 256, 2, 4, 128)
    cfg = FAM.model_config(sz)
    assert (cfg.ssm_inner, cfg.conv_dim, sum(cfg.segments)) \
        == (4096, 5120, 9248)
    assert cfg.ssm_multipliers == tuple(CONFIG["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(CONFIG["mlp_multipliers"])
    assert FAM.num_params(cfg) == 5_254_594_112          # 10.51 GB in bf16
    two = FAM.model_config(sz, n_layers=2)
    assert FAM.num_params(two) == 5_254_594_112 - 4 * 430_120_032
    kw = FAM.reference_kwargs(cfg)
    assert (kw["groups"], kw["state"], kw["head_p"], kw["theta"]) \
        == (2, 256, 128, 1e11)
    assert {"ssm", "ssm_in", "ssm_conv", "ssm_update", "ssm_scan",
            "ssm_out", "attn", "mlp", "lm_head"} <= set(FAM.MODEL_SCOPES)
    assert not hasattr(FAM, "routing_taken")
    # a configuration the block is not written for is refused by the adapter
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        FAM.sizes({**CONFIG, "mamba_norm_before_gate": True}, False)


def test_configuration_states_source_cut_deployment_and_assumptions():
    pub = CONFIG["published"]
    for key, value in pub.items():
        if key == "num_hidden_layers":
            assert (value, CONFIG[key]) == (72, 6)
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers"] \
        == list(CONFIG["reduced_why"])
    assert "10.51 GB" in CONFIG["reduced_why"]["num_hidden_layers"]
    assert {"multipliers", "ssm_segments", "rope_pairing", "gated_norm",
            "ssm_state_dtype", "weights", "max_seq_len", "vocab_size",
            "lm_head_on_stage_one", "num_logits_to_keep"} \
        <= set(CONFIG["assumed"])
    assert "twelve-stage" in CONFIG["deployment"]
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng, chk = CONFIG["engine"], CONFIG["checks"]["logits"]
    assert (eng["max_seq_len"], eng["max_prompt_len"], eng["page_size"],
            eng["prefill_chunk"], eng["decode_block"], eng["pipeline_depth"],
            eng["attention_kernel"]) == (2048, 1024, 128, 512, 8, 3, "auto")
    # a page table of 12 pages a slot for the longest request, and the
    # trash page; 192, 160 or 128 callers on 96, 80 or 64 slots
    slots = eng["max_batch_size"]
    assert slots in (96, 80, 64)
    assert eng["num_pages"] == slots * 12 + 1
    assert CELL_FILE["traffic"]["clients"] == 2 * slots
    # check 1: a whole prompt under a chunk, a chunked one that ends
    # inside a scan chunk, decode across a page edge
    assert chk["whole_prompt_tokens"] <= eng["prefill_chunk"] \
        < chk["chunked_prompt_tokens"] <= eng["max_prompt_len"]
    assert chk["chunked_prompt_tokens"] % CONFIG["mamba_chunk_size"]
    assert chk["whole_prompt_tokens"] // eng["page_size"] \
        < (chk["whole_prompt_tokens"] + chk["decode_steps"]) \
        // eng["page_size"]


def test_the_cell_is_the_decode_mix_to_the_letter():
    """One traffic reads three architectures: the LFM2 and SDAR decode
    cells' lengths, pool, seed, ramp bounds and cool-down."""
    mine = CELL_FILE["traffic"]
    for other in ("lfm2-8b-a1b-serve-decode", "sdar-30b-a3b-serve-decode"):
        theirs = common.load_cell(other)[1]["traffic"]
        for key in ("generator", "prompt_tokens", "output_tokens", "pool",
                    "schedule_seed", "cooldown_s"):
            assert mine[key] == theirs[key], (other, key)
    assert 12 <= mine["ramp_s"] <= 24 and mine["ramp_why"]
    assert (ENTRY["chips"], ENTRY["traffic"]) == (1, "decode")


def test_one_move_of_the_state_is_what_the_update_is_held_to():
    """96 slots x 6 layers: 4.83 GB of state in and out a step, 5.9 ms at
    819 GB/s; the few operations far under the bytes."""
    shape = (32, 128, 256)
    one = costs_ssm.ssm_update_bytes(1, *shape, 2)
    assert one == 2 * 4_194_304 + (4096 + 1024) * 2 + 128 + 16_384
    step = 6 * costs_ssm.ssm_update_bytes(96, *shape, 2)
    assert 4.83e9 < step < 4.86e9
    peak = common.peaks("TPU v5 lite")
    need, bound = costs.roofline_s(
        6 * costs_ssm.ssm_update_flops(96, *shape), step, peak)
    assert bound == "bandwidth" and 5.8e-3 < need < 6.0e-3


def test_the_new_readers_read_nothing_where_there_is_nothing_to_read():
    """A run of another family, or one without a trace: no number and no
    exception (the parent's programs have no such scope)."""
    for name in ("ssm_update_roofline", "ssm_mixer_share", "lm_head_share"):
        reduce = common.load_module("metrics", name).reduce
        assert reduce({"sizes": {"n_layers": 4}, "trace": None,
                       "device": {"kind": "TPU v5 lite"}}) is None


def test_the_manifest_names_the_cell_where_its_readers_read():
    man = common.manifest()
    per_layer = {m["name"] for m in common.cell_metrics(man, CELL,
                                                        "per_layer")}
    assert {"ssm_update_roofline", "ssm_mixer_share", "lm_head_share",
            "decode_step_traced_ms", "model_op_share", "device_idle_share",
            "prefill_program_share", "kv_pages_in_use_share"} <= per_layer
    # dim / n_heads is 256 where this block's heads are 128: the accepted
    # reader would count its K and V bytes twice
    assert "paged_decode_roofline_traced" not in per_layer
    e2e = {m["name"] for m in common.cell_metrics(man, CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
