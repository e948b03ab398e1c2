"""The JoyAI-LLM-Flash family in the benchmark (ISSUE 44): check 1 at the
tiny preset (float32 on the CPU: nothing here is a device number) holds on
any seed on both backends, and each negative control fails it; the whole
command rehearses the cell; the new readers and counts; the
configuration's arithmetic.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import checks, common, costs, costs_latent

CELL = "joyai-llm-flash-serve-decode"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 19 * i for i in range(3)]


def _at_size():
    spec = importlib.util.spec_from_file_location(
        "joyai_at_size", os.path.join(common.ROOT, "tests", "benchmark_suite",
                                      "joyai_at_size.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


AT_SIZE = _at_size()


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed, backend):
    """Whole prefill (expanded), chunked prefill and ten decode steps
    across a page edge (absorbed, off the latent pool), against the
    reference's expanded form: every comparison checks the absorption."""
    out = checks.logits_check(FAM, SZ, {**ENG, "attention_kernel": backend},
                              CHK, seed)
    assert out["ok"] and out["routing"]["ok"] and out["backend"] == backend
    assert out["routing"]["decisions"] == 20 + 70 + 2 * 10   # one routed layer
    assert out["depth"] == 2 and out["max_abs_err"] < 0.1 * CHK["tolerance"]


# (control, the limits that must refuse it, its max_abs_err over the limit)
CONTROLS = [
    ("scale_nope", "all", 100), ("scale_latent", "all", 100),
    ("k_unrotated", "all", 100), ("no_kv_norm", "all", 100),
    ("no_q_norm", "all", 100), ("values_from_rope_lanes", "all", 100),
    ("no_shared_expert", "logits", 100), ("no_scaling", "logits", 100),
    ("no_selection_bias", "routing", 0),
    ("int8_weights", "all", 20), ("int8_pool", "logits+", 5)]


@pytest.mark.parametrize("control,refused_by,factor", CONTROLS)
def test_negative_control_fails_check_1(control, refused_by, factor):
    """Each is ``ok`` false: by every limit; by the two limits on the
    logits alone where the choice of experts is untouched (the shared
    expert, the factor); by the two on the choice alone where only the
    choice is (the selection bias: the logits follow the forced choice);
    the latent pool on an int8 grid by both limits on the logits at
    least."""
    fam, kw = FAM, {}
    if control == "int8_weights":
        kw = {"mutate": AT_SIZE.int8_weights}
    elif control == "int8_pool":
        fam = AT_SIZE.int8_pool(FAM)
    else:
        kw = AT_SIZE.controls(SZ)[control]
    for seed in SEEDS[:2]:
        got = AT_SIZE.brief(checks.logits_check(fam, SZ, ENG, CHK, seed,
                                                **kw))
        assert not got["ok"]
        logits = {"tolerance", "rms_tolerance"}
        routing = {"routing_slack", "routing_flip_share_max"}
        want = {"all": logits | routing, "logits": logits, "logits+": logits,
                "routing": routing}[refused_by]
        if refused_by == "logits+":      # and the choice on some seeds
            assert want <= set(got["failed_by"]), got
        else:
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
    assert (sz["n_layers"], sz["attn_layers"], sz["n_dense"]) == (5, 5, 1)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"]) == (2048, 32, 1)
    assert (sz["q_rank"], sz["kv_rank"], sz["nope_dim"], sz["rope_dim"],
            sz["v_dim"]) == (1536, 512, 128, 64, 128)
    assert (sz["latent_dim"], sz["value_dim"]) == (576, 512)
    assert (sz["n_experts"], sz["top_k"], sz["expert_dim"], sz["n_shared"],
            sz["ffn_dim"], sz["vocab_size"]) == (256, 8, 768, 1, 7168, 129280)
    cfg = FAM.model_config(sz)
    assert cfg.head_dim == 192 and cfg.scaling == 2.5
    assert FAM.num_params(cfg) == 5_558_141_952       # 11.12 GB in bf16
    # check 1's model: the dense layer and one routed layer
    two = FAM.model_config(sz, n_layers=2)
    assert FAM.num_params(two) == 1_839_479_040        # 3.68 GB in bf16
    assert {"q_proj", "kv_latent", "absorb", "shared_expert", "router",
            "experts"} <= set(FAM.MODEL_SCOPES)


def test_configuration_states_source_cut_and_assumptions():
    pub = CONFIG["published"]
    for key, value in pub.items():
        if key == "num_hidden_layers":
            assert (value, CONFIG[key]) == (40, 5)
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert pub["kv_lora_rank"] == 512 and pub["qk_head_dim"] == 192
    assert {"head_dim", "kv_b_proj", "rope_pairing", "routing_epsilon",
            "num_nextn_predict_layers", "topk_group",
            "e_score_correction_bias", "shared_expert"} <= set(
        CONFIG["assumed"])
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng, chk = CONFIG["engine"], CONFIG["checks"]["logits"]
    assert (eng["max_batch_size"], eng["num_pages"], eng["max_seq_len"]) \
        == (128, 2560, 3072)
    # check 1: a partial last chunk, and decode across a page edge twice
    page, chunk = eng["page_size"], eng["prefill_chunk"]
    for n in (chk["whole_prompt_tokens"], chk["chunked_prompt_tokens"]):
        assert n // page < (n + chk["decode_steps"]) // page
    assert chk["chunked_prompt_tokens"] % chunk not in (0, chunk)
    traffic = CELL_FILE["traffic"]
    assert (traffic["clients"], traffic["ramp_s"], traffic["cooldown_s"],
            traffic["schedule_seed"]) == (256, 24, 24, 24)
    assert traffic["prompt_tokens"] == {"median": 256, "sigma": 0.8,
                                        "min": 33, "max": 1024}
    assert traffic["output_tokens"]["median"] == 1024
    assert (traffic["output_tokens"]["min"],
            traffic["output_tokens"]["max"]) == (256, 2048)


def test_latent_call_counts():
    """One call, 128 slots of 1,050 live tokens: the rows once (a row is
    key AND value), where K and V per head would be 17.8 x the bytes."""
    ctx = 128 * 1050
    rows = ctx * 576 * 2
    assert costs_latent.paged_latent_bytes(ctx, 128, 32, 576, 512) \
        == rows + 128 * 32 * (576 + 512) * 2
    assert costs_latent.paged_latent_flops(ctx, 32, 576, 512) \
        == 2.0 * ctx * 32 * 1088
    per_head = costs.paged_decode_bytes([1050] * 128, 32, 160, 32)
    assert 17 < per_head / rows < 18.5
    # bandwidth-bound on a v5e: 60 operations a byte against a ridge of 240
    peak = common.peaks("TPU v5 lite")
    assert costs.roofline_s(
        costs_latent.paged_latent_flops(ctx, 32, 576, 512),
        costs_latent.paged_latent_bytes(ctx, 128, 32, 576, 512),
        peak)[1] == "bandwidth"


def _reader(name):
    return common.load_module("metrics", name).reduce


NEW_READERS = ("paged_latent_roofline_traced", "latent_attn_share",
               "latent_proj_share", "shared_expert_share")


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_read_nothing_of_a_parent_and_do_not_raise(name):
    """No trace; and a trace of a program without the scopes, under a
    configuration without a latent cache."""
    from benchmark import span_reduce
    old_fam = common.load_module("models", "llama")
    run = {"stats_before": {"steps": 1}, "stats_after": {"steps": 9},
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


def test_trace_readers_find_the_latent_kernel_and_the_new_scopes():
    """A hand-made trace: one decode execution of two steps at depth 5,
    matched to its dispatch span (k = 2, 128 slots, 134,400 cached tokens)."""
    from benchmark import span_reduce
    sz = FAM.sizes(CONFIG, False)
    ms = 1_000_000
    rows = [["module", "jit__lambda", 0, 60 * ms, "", 0],
            ["span", "decode_dispatch", 0, ms // 10,
             {"k": 2, "seq": 1, "active": 128, "ctx_tokens": 134400}, 0]]
    t = 0
    step = "jit(f)/decode_block/decode_step/"
    for _step in range(2):
        for _layer in range(5):
            for scope, dur in (("q_proj/dot", 2), ("kv_latent/scatter", 1),
                               ("absorb/dot", 1), ("shared_expert/dot", 1)):
                rows.append(["op", "fusion", t, dur * ms // 10, step + scope,
                             0])
                t += dur * ms // 10
            rows.append(["op", "custom-call", t, ms,
                         step + "attn/paged_decode_attention/pallas_call", 0])
            t += ms
            rows.append(["op", "custom-call", t, 3 * ms,
                         step + "experts/grouped_ffn/gmm/pallas_call", 0])
            t += 3 * ms
    trace = span_reduce.from_rows(rows)
    run = {"span_trace": trace, "trace_dir": "x", "sizes": sz, "family": FAM,
           "kind": "serve", "device": {"kind": "TPU v5 lite"},
           "stats_before": {}, "stats_after": {}}
    total = 10 * 4.5
    assert _reader("latent_attn_share")(run) \
        == pytest.approx(100 * 10 / total)
    assert _reader("latent_proj_share")(run) \
        == pytest.approx(100 * 10 * 0.4 / total)
    assert _reader("shared_expert_share")(run) \
        == pytest.approx(100 * 10 * 0.1 / total)
    assert _reader("decode_step_traced_ms")(run) == pytest.approx(30.0)
    peak = common.peaks("TPU v5 lite")
    need = sum(5 * costs_latent.paged_latent_bytes(
        134400 + 128 * (s + 1), 128, 32, 576, 512) for s in range(2)) \
        / peak["hbm_bytes_per_s"]
    roof = _reader("paged_latent_roofline_traced")(run)
    assert roof == pytest.approx(100 * need / 10e-3)
    assert 0 < roof < 100
