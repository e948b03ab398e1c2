"""``correct`` is the same function of code and seed everywhere: the CPU
rehearsal of each cell at the tiny preset over 20 seeds (a REHEARSAL:
platform cpu, nothing here is a device number) gives ``correct`` true and
no compile in the window for every one; the negative controls of checks 1
and 2 turn it false.

The serve cells are driven in-process here (the engine the replica wraps,
fed the cell's own traffic); the whole command, runtime and HTTP included,
is rehearsed once in test_manifest.py and test_fourchip_rehearsal.py.
"""

import jax
import jax.numpy as jnp
import pytest

from benchmark import checks, common, train_cell

SEEDS = [2**31 + 1009 * i + i * i for i in range(20)]
SERVE_CELLS = ["mistral7b-serve-chat", "mistral7b-serve-peak"]
_, _, SERVE_CONFIG = common.load_cell("mistral7b-serve-chat")
FAM = common.family(SERVE_CONFIG)
REF = common.reference(FAM)
SZ = FAM.sizes(SERVE_CONFIG, True)
ENG = common.section(SERVE_CONFIG, "engine", True)
CHK = common.section(SERVE_CONFIG, "checks", True)


@pytest.fixture(scope="module")
def engine():
    """The tiny engine, warmed with the closed set of the cells' files."""
    from ray_tpu.serve.llm import LLMConfig, LLMEngine
    eng = LLMEngine(LLMConfig(model_config=FAM.model_config(SZ), **ENG))
    eng.start()
    for cell in SERVE_CELLS:
        t = common.load_cell(cell)[1]["rehearsal"]["traffic"]["prompt_tokens"]
        for _n, text in common.warm_prompts(t["min"], t["max"], ENG):
            eng.generate(text, max_tokens=2)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def served_params():
    return FAM.init_params(jax.random.PRNGKey(0), FAM.model_config(SZ))


def _served_check(params, samples):
    return checks.served_tokens_check(
        REF, FAM.reference_kwargs(FAM.model_config(SZ)), params, samples,
        CHK["served_tokens"]["margin"], eos=common.BYTE_EOS)


def _serve(engine, cell, seed):
    traffic = common.load_cell(cell)[1]["rehearsal"]["traffic"]
    plan = common.load_module("traffic", traffic["generator"]).plan(
        traffic, seed, 3.0)
    reqs = plan["requests"][:24]
    before = engine.engine_stats()["mid_traffic_compiles"]
    rids = [engine.submit(r["prompt"], max_tokens=r["max_tokens"],
                          temperature=0.0) for r in reqs]
    outs = [engine.result(rid, timeout=60.0) for rid in rids]
    compiles = engine.engine_stats()["mid_traffic_compiles"] - before
    records = [{"index": r["index"], "max_tokens": r["max_tokens"],
                "completion_tokens": o["num_generated_tokens"]}
               for r, o in zip(reqs, outs)]
    samples = [{"prompt_ids": common.byte_encode(r["prompt"]),
                "tokens": [int(t) for t in o["tokens"]],
                "max_tokens": r["max_tokens"]}
               for r, o in list(zip(reqs, outs))[:CHK["served_tokens"]["sample"]]]
    assert all(o["error"] is None for o in outs)
    return records, samples, compiles


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_serve_rehearsal_is_correct_and_compiles_nothing(engine,
                                                         served_params, cell,
                                                         seed):
    assert jax.devices()[0].platform == "cpu"      # a rehearsal, flagged
    records, samples, compiles = _serve(engine, cell, seed)
    assert compiles == 0
    served = _served_check(served_params, samples)
    structure = checks.structure_check(records, samples, SZ["vocab_size"])
    assert served["ok"], served
    assert structure["ok"], structure


@pytest.mark.parametrize("seed", SEEDS)
def test_logits_check_holds_on_any_seed(seed):
    out = checks.logits_check(FAM, SZ, ENG, CHK["logits"], seed)
    assert out["ok"], out


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_warm_set_is_closed_over_every_length_the_file_allows(seed):
    """By construction, not by a choice of seed: every prompt length the
    cell's clamps allow uses only programs the warm set used."""
    for cell in SERVE_CELLS:
        _, cell_file, config = common.load_cell(cell)
        for rehearsal in (False, True):
            eng = common.section(config, "engine", rehearsal)
            p = (cell_file["rehearsal"] if rehearsal else cell_file)[
                "traffic"]["prompt_tokens"]
            warmed = {prog for n in common.warm_prompt_lengths(
                p["min"], p["max"], eng)
                for prog in common.programs_for_prompt(n, eng)}
            for n in range(p["min"], p["max"] + 1):
                assert set(common.programs_for_prompt(n, eng)) <= warmed


def _int8_mlp(params):
    def q(w):
        w32 = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(w32)) / 127.0
        return (jnp.round(w32 / s).clip(-127, 127) * s).astype(w.dtype)
    out = jax.tree.map(lambda a: a, params)
    out["layers"] = dict(params["layers"], mlp=jax.tree.map(
        q, params["layers"]["mlp"]))
    return out


def test_negative_control_int8_mlp_fails_the_logits_check():
    out = checks.logits_check(FAM, SZ, ENG, CHK["logits"], SEEDS[0],
                              mutate=_int8_mlp)
    assert not out["ok"] and out["max_abs_err"] > 5 * out["tolerance"]


def test_negative_control_dropped_rope_fails_the_logits_check():
    out = checks.logits_check(FAM, SZ, ENG, CHK["logits"], SEEDS[0],
                              use_rope=False)
    assert not out["ok"] and out["max_abs_err"] > 5 * out["tolerance"]


def test_negative_control_one_replaced_token_fails_the_served_check(
        engine, served_params):
    _r, samples, _c = _serve(engine, SERVE_CELLS[0], SEEDS[1])
    assert _served_check(served_params, samples)["ok"]
    bad = [dict(s, tokens=list(s["tokens"])) for s in samples]
    bad[0]["tokens"][1] = (bad[0]["tokens"][1] + 7) % SZ["vocab_size"]
    out = _served_check(served_params, bad)
    assert not out["ok"] and out["max_deficit"] > 10 * out["margin"]


def test_structure_check_takes_an_early_stop_and_refuses_an_overrun():
    ok = checks.structure_check(
        [{"index": 0, "max_tokens": 8, "completion_tokens": 5}],
        [{"tokens": [1, 2], "max_tokens": 4}], 512)
    assert ok["ok"] and ok["stopped_early"] == 1
    assert not checks.structure_check(
        [{"index": 0, "max_tokens": 8, "completion_tokens": 9}], [], 512)["ok"]
    assert not checks.structure_check(
        [], [{"tokens": [1, 999], "max_tokens": 4}], 512)["ok"]
    # a prompt the engine cut (max_prompt_len) is not the work that was asked
    rec = {"index": 0, "max_tokens": 8, "completion_tokens": 8,
           "prompt_tokens_asked": 1500}
    assert checks.structure_check([dict(rec, prompt_tokens=1500)], [], 512)["ok"]
    cut = checks.structure_check([dict(rec, prompt_tokens=1024)], [], 512)
    assert not cut["ok"] and "1500 tokens served as 1024" in cut["problems"][0]


TRAIN_CONFIG = common.load_cell("mistral7b-train-fsdp4")[2]


@pytest.fixture(scope="module")
def built_train():
    """Mesh, shardings and jitted programs of the tiny train cell on four
    of the virtual CPU devices: what does not depend on the seed."""
    return train_cell.build(
        TRAIN_CONFIG["model_family"],
        common.family(TRAIN_CONFIG).sizes(TRAIN_CONFIG, True),
        common.section(TRAIN_CONFIG, "trainer", True), 4, True)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_rehearsal_is_correct_on_any_seed(built_train, seed):
    config = TRAIN_CONFIG
    out = train_cell.train_steps({
        "rehearsal": True, "seed": seed, "seconds": 0.2, "trace_dir": None,
        "trace_steps": 0, "plan": {"distinct_batches": 1}}, built_train)
    assert out["device"]["platform"] == "cpu" and out["mesh"] == {"fsdp": 4}
    res = checks.train_structure_check(
        out["losses"], out["reference_first_loss"],
        config["rehearsal"]["checks"]["first_loss"]["tolerance"],
        out["loss_last_same_batch"])
    assert res["ok"], res


def test_train_check_refuses_a_loss_that_does_not_fall_or_is_off():
    assert not checks.train_structure_check([6.0, 6.1], 6.0, 0.001, 6.1)["ok"]
    assert not checks.train_structure_check([6.0, 5.9], 6.1, 0.001, 5.9)["ok"]
    assert not checks.train_structure_check(
        [6.0, float("nan"), 5.9], 6.0, 0.001, 5.9)["ok"]
    assert checks.train_structure_check([6.0, 5.9], 6.0004, 0.001, 5.9)["ok"]
