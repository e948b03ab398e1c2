"""The SDAR-MoE family in the benchmark (ISSUE 37: generation by diffusion
over blocks): check 1 at the tiny preset (float32 on the CPU: nothing here
is a device number) holds on any seed and each negative control fails it by
a wide factor; check 2 takes the reference's own generation and refuses a
planted token and a token revealed from the wrong state; the whole command
rehearses the cell; the new readers and counts; the configuration's
arithmetic.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import block_reduce, checks, common, costs_block, costs_routed

CELL = "sdar-30b-a3b-serve-decode"
ENTRY, CELL_FILE, CONFIG = common.load_cell(CELL)
FAM = common.family(CONFIG)
REF = common.reference(FAM)
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)["logits"]
SEEDS = [2**31 + 17 * i for i in range(6)]


@pytest.mark.parametrize("seed", SEEDS)
def test_check_1_holds_on_any_seed(seed):
    out = checks.logits_check(FAM, SZ, ENG, CHK, seed)
    assert out["ok"] and out["routing"]["ok"], out
    # two layers x (committed positions + 11 denoise passes of 4 rows): the
    # second sequence's last two tokens never fill a block
    assert out["routing"]["decisions"] == 2 * (32 + 44 + 80 + 44)
    assert out["depth"] == 2 and out["max_abs_err"] < 0.1 * CHK["tolerance"]


@pytest.mark.parametrize("control,factor", [
    ("block_mask", 100), ("norm_topk", 100), ("qk_norm", 100)])
def test_negative_control_fails_check_1_by_a_wide_factor(control, factor):
    """The reference with one rule left out: the causal mask in place of
    the block mask, softmax weights not renormalised, q/k norm left out."""
    for seed in SEEDS[:2]:
        out = checks.logits_check(FAM, SZ, ENG, CHK, seed,
                                  **{control: False})
        assert not out["ok"]
        assert out["max_abs_err"] > factor * CHK["tolerance"], out


@pytest.fixture(scope="module")
def served():
    """Streams as the reference itself generates them at the tiny preset,
    for prompts that leave 0-3 tokens in their first block; ``max_tokens``
    11 cuts the last block."""
    cfg = FAM.model_config(SZ)
    params = FAM.init_params(jax.random.PRNGKey(0), cfg)
    kw = FAM.reference_kwargs(cfg)
    rs = np.random.RandomState(0)
    samples = []
    for left in range(4):
        prompt = [int(t) for t in rs.randint(0, 250, size=24 + left)]
        samples.append({"prompt_ids": prompt, "max_tokens": 11,
                        "tokens": REF.generate(params, prompt, 11, **kw)[0]})
    return cfg, params, kw, samples


def _check_2(served, samples):
    _cfg, params, kw, _ = served
    return checks.served_tokens_check(REF, kw, params, samples, 1e-3,
                                      eos=None, width=32, out_width=13)


def test_check_2_takes_the_references_own_generation(served):
    got = _check_2(served, served[3])
    assert got["ok"] and got["tokens_checked"] == 44, got


@pytest.mark.parametrize("at", [0, 5, 10], ids=["first", "middle", "last"])
def test_check_2_refuses_a_planted_token(served, at):
    cfg = served[0]
    bad = [dict(s, tokens=list(s["tokens"])) for s in served[3]]
    for s in bad:
        s["tokens"][at] = (s["tokens"][at] + 7) % (cfg.vocab_size - 1)
    got = _check_2(served, bad)
    assert not got["ok"] and got["max_deficit"] > 0.1, got


def test_check_2_refuses_a_token_revealed_from_the_wrong_state(served):
    """Every position of a block taken from the all-masked state (one pass
    a block): positions the sampler reveals second were conditioned on
    nothing, and at least one stream shows it."""
    _cfg, params, kw, samples = served
    wrong = [dict(s, tokens=REF.generate(
        params, s["prompt_ids"], 11, **{**kw, "denoise": 1})[0])
        for s in samples]
    assert any(w["tokens"] != s["tokens"] for w, s in zip(wrong, samples))
    got = _check_2(served, wrong)
    assert not got["ok"] and got["max_deficit"] > 0.01, got


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
    assert (sz["n_layers"], sz["attn_layers"], sz["n_dense"]) == (7, 7, 0)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]) \
        == (2048, 32, 4, 128)
    assert (sz["n_experts"], sz["top_k"], sz["expert_dim"]) == (128, 8, 768)
    assert (sz["block_length"], sz["mask_token_id"], sz["denoise_passes"]) \
        == (4, 151669, 2)
    cfg = FAM.model_config(sz)
    assert FAM.num_params(cfg) == 4_984_176_384       # 9.97 GB in bf16
    assert FAM.model_config(sz, n_layers=2).n_layers == 2
    assert {"router", "experts", "unmask"} <= set(FAM.MODEL_SCOPES)


def test_configuration_states_source_cut_and_assumptions():
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 48 and CONFIG["num_hidden_layers"] == 7
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert all(CONFIG[k] == v for k, v in pub.items()
               if k != "num_hidden_layers")
    assert {"generation", "mask_token", "logits", "qk_norm",
            "rope_pairing"} <= set(CONFIG["assumed"])
    assert "backend" not in CONFIG["checks"]["logits"]     # pallas
    eng = CONFIG["engine"]
    block = CONFIG["assumed"]["generation"]["block_length"]
    assert all(eng[k] % block == 0 for k in (
        "page_size", "prefill_chunk", "max_seq_len", "decode_block",
        "pressure_decode_block"))
    assert eng["num_pages"] >= eng["max_batch_size"] * 12 + 1
    # the pending block starts half known in check 1's first sequence
    assert CONFIG["checks"]["logits"]["whole_prompt_tokens"] % block == 2


def test_block_kernel_byte_count():
    # 64 slots with 25,600 committed tokens, a block of 4 each: K and V of
    # 25,856 positions x 4 heads x 128 x 2 B, queries and outputs of 256 x
    # 32 heads x 128 x 2 B
    got = costs_block.paged_block_bytes(25_600, 64, 4, 4, 128, 32)
    assert got == 25_856 * 4 * 128 * 2 * 2 + 256 * 32 * 128 * 2 * 2
    # every expert touched: its three matrices once, 1.208 GB a layer
    assert costs_routed.grouped_ffn_bytes(0, 128, 2048, 768) == 1_207_959_552


def _reader(name):
    return common.load_module("metrics", name).reduce


NEW_READERS = ("block_pass_traced_ms", "passes_per_token",
               "unmask_share", "expert_ffn_block_roofline",
               "paged_block_roofline_traced")


def test_counter_readers_read_the_engines_counters_and_nothing_of_a_parent():
    run = {"stats_before": {"slot_passes_total": 10, "tokens_out": 100},
           "stats_after": {"slot_passes_total": 10 + 6 * 64 * 50,
                           "tokens_out": 100 + 8 * 64 * 50 - 600},
           "sizes": FAM.sizes(CONFIG, False), "trace_dir": None}
    assert _reader("passes_per_token")(run) \
        == pytest.approx(19_200 / 25_000)
    # a program without the counters, the spans or the kernel (the parent),
    # and a run without a trace: nothing, and no raise
    old = {"stats_before": {"steps": 1, "tokens_out": 5},
           "stats_after": {"steps": 9, "tokens_out": 50},
           "sizes": {"n_layers": 16, "dim": 4096, "n_heads": 32},
           "trace_dir": None, "device": {"kind": "TPU v5 lite"}}
    for name in NEW_READERS:
        assert _reader(name)(old) is None
    from benchmark import span_reduce
    decode_only = span_reduce.from_rows([
        ["module", "jit__lambda", 0, 10_000_000, "", 0],
        ["op", "custom-call", 0, 1_000_000, "jit(f)/decode_block/"
         "decode_step/attn/paged_decode_attention/pallas_call", 0],
        ["span", "decode_dispatch", 0, 1000, {"k": 1, "seq": 1}, 0]])
    old.update(span_trace=decode_only, trace_dir="x", family=FAM)
    for name in NEW_READERS:
        assert _reader(name)(old) is None


def _block_trace(executions=3, lead=1):
    """A hand-made trace: block programs of two blocks (six passes, seven
    kernel calls a pass), the first ``lead`` dispatched before the capture
    began, with the scopes the readers look for."""
    from benchmark import span_reduce
    ms, rows, t = 1_000_000, [], 0
    scope = "jit(f)/block_program/block_step/"
    for x in range(executions):
        start = t
        for p in range(6):
            kind = "commit" if p % 3 == 2 else "denoise"
            for _layer in range(7):
                rows.append(["op", "custom-call", t, ms // 2, scope + kind
                             + "/attn/paged_block_attention/pallas_call", 0])
                rows.append(["op", "fusion", t + ms // 2, ms // 10,
                             scope + kind + "/router/dot", 0])
                rows.append(["op", "custom-call", t + ms, ms,
                             scope + kind + "/experts/grouped_ffn/gmm", 0])
                t += 2 * ms
            if kind == "denoise":
                rows.append(["op", "fusion", t, ms, scope + kind
                             + "/lm_head/dot", 0])
                rows.append(["op", "fusion", t + ms, ms, scope + kind
                             + "/unmask/reduce", 0])
                t += 2 * ms
        rows.append(["module", "jit__lambda", start, t - start, "", 0])
        t += ms
        if x >= lead:
            rows.append(["span", "block_dispatch", start - 5 * ms, ms, {
                "seq": x, "blocks": 2, "passes": 6, "w": 64, "active": 64,
                "ctx_tokens": 25_600, "inflight": 2, "trimmed": 0}, 0])
    return span_reduce.from_rows(rows)


def test_trace_readers_count_passes_and_find_the_scopes():
    trace = _block_trace()
    runs = block_reduce.executions(trace, 7)
    assert [x["passes"] for x in runs] == [6, 6, 6]
    assert len(block_reduce.matched(trace, 7)) == 2         # one led
    sz = FAM.sizes(CONFIG, False)
    counters = {"experts_touched_total": 42 * 128,
                "routed_layer_steps_total": 42, "expert_rows_total": 42 * 2048}
    run = {"span_trace": trace, "trace_dir": "x", "sizes": sz, "family": FAM,
           "kind": "serve", "device": {"kind": "TPU v5 lite"},
           "stats_before": {k: 0 for k in counters}, "stats_after": counters}
    # an execution: 6 passes x 7 layers x 2 ms + 4 denoise passes x 2 ms
    assert _reader("block_pass_traced_ms")(run) \
        == pytest.approx((6 * 7 * 2 + 4 * 2) / 6)
    per = 6 * 7 * (0.5 + 0.1 + 1.0) + 4 * 2.0
    assert _reader("unmask_share")(run) == pytest.approx(100 * 8 / per)
    assert _reader("routed_ffn_share")(run) \
        == pytest.approx(100 * 6 * 7 * 1.1 / per)
    peak = common.peaks("TPU v5 lite")
    need = costs_routed.grouped_ffn_bytes(2048, 128, 2048, 768) \
        / peak["hbm_bytes_per_s"]
    assert _reader("expert_ffn_block_roofline")(run) \
        == pytest.approx(100 * need / 1e-3)
    block = sum(costs_block.paged_block_bytes(
        25_600 + done * 256, 64, 4, 4, 128, 32) for done in range(2)) * 3 * 7
    assert _reader("paged_block_roofline_traced")(run) \
        == pytest.approx(100 * block / peak["hbm_bytes_per_s"]
                         / (6 * 7 * 0.5e-3))
    # the accepted readers see decode executions of 0 steps: no number
    assert _reader("decode_step_traced_ms")(run) is None
    assert _reader("paged_decode_roofline_traced")(run) is None
