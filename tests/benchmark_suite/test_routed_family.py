"""Check 1 for a family with routed experts (ISSUE 29), on the toy family
of tests/benchmark_suite/routed_toy (an adapter with its programs, a plain
reference and a configuration, loaded from there and never imported by
benchmark/). All on the CPU at the tiny preset, bfloat16 programs against
the float32 reference: nothing here is a device number.

What is held: on 16 seeds the routing-aware check passes, some seed turns
a decision over, and the strict comparison (the dense family's: the
reference routes by its own scores) fails only where one was turned over;
three negative controls fail by at least three times a limit, and int8
experts, which fail none by that much, raise the root-mean-square error
by a steady factor under a maximum that stays inside its tolerance; the
backend gate reads the configuration's file; the family dropped into a
copy of benchmark/ goes through the checks child with no edit of a file
that was there; the three places that state the adapter's contract name
the same things.
"""

import hashlib
import inspect
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "routed_toy"))
import at_size  # noqa: E402
import drive  # noqa: E402

FAM, CONFIG = drive.FAM, drive.CONFIG
SZ = FAM.sizes(CONFIG, True)
ENG = common.section(CONFIG, "engine", True)
CHK = common.section(CONFIG, "checks", True)
SEEDS = [2**31 + 7 * i for i in range(16)]


@pytest.fixture(scope="module")
def both():
    """seed -> (routing-aware result, strict result), computed once."""
    return {s: (checks.logits_check(FAM, SZ, ENG, CHK["logits"], s),
                checks.logits_check(drive.STRICT, SZ, ENG, CHK["logits"], s))
            for s in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_routing_aware_check_holds_on_any_seed(both, seed):
    forced, strict = both[seed]
    assert forced["ok"], forced
    assert 0 < forced["rms_err"] <= forced["rms_tolerance"]
    assert forced["rms_err"] < 0.5 * forced["max_abs_err"]
    assert "rms_err" not in strict
    r = forced["routing"]
    assert r["decisions"] == 2 * (20 + 70 + 2 * 4) and r["ok"]
    assert "routing" not in strict
    if r["flipped"] == 0:
        # the programs chose what the reference chooses: one computation
        assert strict["max_abs_err"] == forced["max_abs_err"]
    if not strict["ok"]:
        assert r["flipped"] > 0         # only a turned-over choice fails it


def test_a_decision_turns_over_and_the_strict_comparison_fails_there(both):
    """Not every turned-over decision reaches a compared logit (one in the
    last routing layer at a prompt position reaches none, one further
    down reaches the compared positions through attention alone), so the
    strict comparison fails on some of those seeds, not on all; where it
    fails, it fails by a swapped expert's worth and not by noise."""
    flipped = [s for s in SEEDS if both[s][0]["routing"]["flipped"]]
    failed = [s for s in SEEDS if not both[s][1]["ok"]]
    assert len(flipped) >= 8 and set(failed) <= set(flipped)
    far = [s for s in failed if both[s][1]["max_abs_err"]
           > 3 * CHK["logits"]["tolerance"]]
    assert len(far) >= 4, (failed, far)
    assert all(both[s][0]["max_abs_err"] <= CHK["logits"]["tolerance"]
               for s in SEEDS)


CONTROLS = {
    # fault -> the number it has to fail, by three times its limit
    "wrong_expert": "max_slack", "no_norm": "max_abs_err",
    "softmax": "max_slack"}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("seed", SEEDS[:3])
def test_negative_control_fails_by_three_times_a_limit(control, seed):
    chk = CHK["logits"]
    out = checks.logits_check(FAM, dict(SZ, fault=control), ENG, chk, seed)
    assert not out["ok"]
    if CONTROLS[control] == "max_slack":
        assert out["routing"]["max_slack"] > 3 * chk["routing_slack"]
        assert not out["routing"]["ok"]
    else:
        assert out["max_abs_err"] > 3 * chk["tolerance"]
        assert out["rms_err"] > 3 * chk["rms_tolerance"]
    if control == "wrong_expert":
        # the wrong expert is forced on the reference too: the logits agree
        # and only the slack shows it, at one position a sequence
        assert out["max_abs_err"] <= chk["tolerance"]
        assert out["rms_err"] <= chk["rms_tolerance"]


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_int8_experts_move_the_rms_error_and_not_the_maximum(seed):
    """The lower precision, in the configuration's own dtype. It does NOT
    fail by three times a limit, at any size: bfloat16 activations and an
    int8 grid on the experts are both about 1 % of the logits' spread. What
    it does is raise the root-mean-square error by a factor that is the
    same on every seed, while the maximum stays inside its tolerance. So a
    limit between a seed's two readings fails it and nothing else does.
    Across seeds such a limit stands only at size (on the chip, width 2048,
    20 seeds: sound 0.00929-0.01028, int8 0.01197-0.01305, the toy's
    ``rms_tolerance`` between them); at width 64 the sound runs spread by
    more than the effect, and the tiny preset's limit is for gross faults."""
    chk = CHK["logits"]
    sound = checks.logits_check(FAM, SZ, ENG, chk, seed)
    int8 = checks.logits_check(FAM, SZ, ENG, chk, seed,
                               mutate=drive.int8_experts)
    assert sound["ok"] and int8["ok"]         # the tiny preset does not see it
    assert 1.15 < int8["rms_err"] / sound["rms_err"] < 1.7
    assert int8["max_abs_err"] < 3 * chk["tolerance"]
    between = dict(chk, rms_tolerance=(sound["rms_err"]
                                       * int8["rms_err"]) ** 0.5)
    assert checks.logits_check(FAM, SZ, ENG, between, seed)["ok"]
    out = checks.logits_check(FAM, SZ, ENG, between, seed,
                              mutate=drive.int8_experts)
    assert not out["ok"] and out["routing"]["ok"]
    assert out["max_abs_err"] <= out["tolerance"]
    assert out["rms_err"] > out["rms_tolerance"] == between["rms_tolerance"]


def test_reference_scores_the_choice_it_is_given():
    cfg = FAM.model_config(SZ)
    params = FAM.init_params(jax.random.PRNGKey(3), cfg)
    ref, kw = common.reference(FAM), FAM.reference_kwargs(cfg)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (1, 24), 0,
                                         SZ["vocab_size"]), np.int32)
    own = ref.logits_at(params, toks, np.arange(24), **kw)
    # the reference's own choice, layer by layer through its own pieces
    # (it hands out logits, not choices)
    sel = []
    x = ref._embed(params["embed"], jnp.asarray(toks[0]))
    for lp in params["layers"]:
        x = ref._attention(x, lp, kw["theta"], kw["eps"])
        if "mlp" in lp:
            x = ref._dense(x, lp, kw["eps"])
            continue
        g = ref._rms_norm(x, lp["ffn_norm"], kw["eps"])
        s = jax.nn.sigmoid(jnp.dot(g, lp["moe"]["router"].astype(jnp.float32),
                                   precision="highest"))
        sel.append(np.asarray(jax.lax.top_k(s + lp["moe"]["bias"],
                                            SZ["top_k"])[1]))
        x, _ = ref._routed(x, lp, None, kw["eps"], kw["top_k"], kw["scaling"])
    choice = np.stack(sel)[:, :, ::-1].copy()          # [L_r, T, k], reversed
    assert choice.shape == (SZ["n_layers"] - SZ["n_dense"], 24, SZ["top_k"])
    slack = np.asarray(ref.routing_slack(params, toks, choice, **kw))
    assert slack.shape == choice.shape[:2] and np.all(slack == 0.0)
    np.testing.assert_allclose(
        ref.logits_at(params, toks, np.arange(24), routing=choice, **kw),
        own, atol=1e-5)
    # an expert named twice, and one that is not there
    twice = choice.copy()
    twice[0, 5] = twice[0, 5, 0]
    absent = choice.copy()
    absent[1, 7, 0] = SZ["n_experts"]
    for bad, where in ((twice, (0, 5)), (absent, (1, 7))):
        got = np.asarray(ref.routing_slack(params, toks, bad, **kw))
        assert np.isinf(got[where]) and np.isfinite(np.delete(
            got.ravel(), np.ravel_multi_index(where, got.shape))).all()
        assert not checks.routing_verdict(got, 1.0, 1.0)["ok"]
    # another expert than the k-th best: the slack is the gap between them
    worse = choice.copy()
    worse[0, 2, 0] = next(e for e in range(SZ["n_experts"])
                          if e not in choice[0, 2])
    got = np.asarray(ref.routing_slack(params, toks, worse, **kw))
    assert got[0, 2] > 0 and np.all(np.delete(got[0], 2) == 0)
    with pytest.raises(ValueError):
        ref.logits_at(params, np.repeat(toks, 2, 0), np.arange(4),
                      routing=choice, **kw)


def test_routing_verdict_counts_and_limits():
    v = checks.routing_verdict([0.0, 0.0, 0.004, 0.0], 0.01, 0.3)
    assert v["ok"] and (v["decisions"], v["flipped"]) == (4, 1)
    assert v["max_slack"] == 0.004 and v["flip_share"] == 0.25
    assert not checks.routing_verdict([0.0, 0.02], 0.01, 1.0)["ok"]
    assert not checks.routing_verdict([0.001] * 3 + [0.0], 0.01, 0.5)["ok"]
    assert not checks.routing_verdict([], 0.01, 0.5)["ok"]


def test_every_number_compared_goes_to_standard_error_beside_its_limit(both):
    """``run.compared`` has no table of names: whatever scalars a check's
    dictionary holds are printed under the check's own names."""
    from benchmark import run
    forced, _strict = both[SEEDS[1]]
    lines = run.compared({
        "logits": forced,
        "served_tokens": {"ok": True, "max_deficit": 0.03, "margin": 0.25,
                          "per_sample_max_deficit": [0.03, 0.01]},
        "a_later_check": {"ok": False, "residual": 2e-3, "residual_max": 1e-3}})
    assert len(lines) == 3 and lines[0].startswith("check logits: ok True; ")
    for v, lim in (("max_abs_err", "tolerance"), ("rms_err", "rms_tolerance")):
        assert f"{v} {forced[v]!r}; " in lines[0]
        assert f"{lim} {forced[lim]!r}; " in lines[0]
    r = forced["routing"]
    assert f"routing.max_slack {r['max_slack']!r}; routing.slack_limit 0.02" \
        in lines[0]
    assert f"routing.flip_share {r['flip_share']!r}; " in lines[0]
    assert "whole_prefill" not in lines[0]          # one level down, no more
    assert lines[1] == ("check served_tokens: ok True; max_deficit 0.03; "
                        "margin 0.25; per_sample_max_deficit [0.03, 0.01]")
    assert lines[2] == ("check a_later_check: ok False; residual 0.002; "
                        "residual_max 0.001")


# ---- the dense family is what it was ------------------------------------------

def _parent_logits_check(fam, sz, engine, spec, seed):
    """``checks.logits_check`` as commit 9ff98ca had it (the parent of
    ISSUE 29's change), kept here as the reference of what the dense
    family's check returns: it names the engine's programs itself."""
    from ray_tpu.serve.llm import kv_cache as kvc

    ref_mod = common.reference(fam)
    cfg = fam.model_config(sz, n_layers=spec["depth"])
    page, cap = engine["page_size"], engine["max_prompt_len"]
    chunk = engine["prefill_chunk"]
    max_pages = -(-engine["max_seq_len"] // page)
    backend = kvc.resolve_attention_backend(
        engine.get("attention_kernel", "auto"), cfg, page)
    k_w, k_t = jax.random.split(common.fold_seed(seed))
    params = fam.init_params(k_w, cfg)
    pa, pb, d = (spec["whole_prompt_tokens"], spec["chunked_prompt_tokens"],
                 spec["decode_steps"])
    toks = np.asarray(jax.random.randint(
        k_t, (2, max(pa, pb) + d), 0, cfg.vocab_size), np.int32)
    seq_a, seq_b = toks[0, :pa + d], toks[1, :pb + d]
    kv = kvc.init_paged_cache(cfg, 2 * max_pages + 1, page)
    tables = np.zeros((4, max_pages), np.int32)
    tables[0] = 1 + np.arange(max_pages)
    tables[1] = 1 + max_pages + np.arange(max_pages)
    prefill = jax.jit(lambda p, kv, t, x, n: kvc.paged_prefill(
        p, kv, t, x, n, cfg, page))
    chunk_fn = jax.jit(lambda p, kv, t, x, s, n: kvc.paged_prefill_chunk(
        p, kv, t, x, s, n, cfg, page, backend))
    decode = jax.jit(lambda p, kv, t, sl, x: kvc.paged_decode_step(
        p, kv, t, sl, x, cfg, page, backend))

    def padded(seg, width):
        out = np.zeros((1, width), np.int32)
        out[0, :len(seg)] = seg
        return jnp.asarray(out)

    got_a, got_b = [], []
    lg, kv = prefill(params, kv, jnp.asarray(tables[0]),
                     padded(seq_a[:pa], common.prefill_bucket(pa, cap)),
                     jnp.int32(pa))
    got_a.append(lg)
    start = 0
    while pb - start > chunk:
        _, kv = chunk_fn(params, kv, jnp.asarray(tables[1]),
                         padded(seq_b[start:start + chunk], chunk),
                         jnp.int32(start), jnp.int32(pb))
        start += chunk
    lg, kv = chunk_fn(params, kv, jnp.asarray(tables[1]),
                      padded(seq_b[start:pb],
                             common.prefill_bucket(pb - start, cap)),
                      jnp.int32(start), jnp.int32(pb))
    got_b.append(lg)
    lens = jnp.asarray([pa, pb, 0, 0], jnp.int32)
    for i in range(d):
        cur = jnp.asarray([seq_a[pa + i], seq_b[pb + i], 0, 0], jnp.int32)
        lg, kv, lens = decode(params, kv, jnp.asarray(tables), lens, cur)
        got_a.append(lg[0])
        got_b.append(lg[1])
    got_a, got_b = jnp.stack(got_a), jnp.stack(got_b)
    ref = fam.reference_kwargs(cfg)
    want_a = ref_mod.logits_at(params, seq_a[None],
                               np.arange(pa - 1, pa + d), **ref)[0]
    want_b = ref_mod.logits_at(params, seq_b[None],
                               np.arange(pb - 1, pb + d), **ref)[0]
    errs = {}
    for name, got, want in (("whole_prefill+decode", got_a, want_a),
                            ("chunked_prefill+decode", got_b, want_b)):
        diff = jnp.abs(got.astype(jnp.float32) - want)
        errs[name] = {
            "max_abs_err": float(jnp.max(diff)),
            "prefill_max_abs_err": float(jnp.max(diff[0])),
            "ref_max_abs": float(jnp.max(jnp.abs(want))),
            "finite": bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))}
    worst = max(e["max_abs_err"] for e in errs.values())
    finite = all(e["finite"] for e in errs.values())
    return {"ok": bool(finite and worst <= spec["tolerance"]),
            "max_abs_err": worst, "tolerance": spec["tolerance"],
            "depth": spec["depth"], "backend": backend, "errors": errs}


_, _, DENSE = common.load_cell("mistral7b-serve-chat")
DENSE_FAM = common.family(DENSE)
# the seeds of test_correct_any_seed.py
DENSE_SEEDS = [2**31 + 1009 * i + i * i for i in range(20)]


@pytest.mark.parametrize("seed", DENSE_SEEDS)
def test_dense_logits_check_returns_the_parents_dictionary(seed):
    sz, eng = DENSE_FAM.sizes(DENSE, True), common.section(DENSE, "engine", True)
    spec = common.section(DENSE, "checks", True)["logits"]
    now = checks.logits_check(DENSE_FAM, sz, eng, spec, seed)
    assert list(now) == ["ok", "max_abs_err", "tolerance", "depth", "backend",
                         "errors", "program_s", "reference_s"]
    for key in ("program_s", "reference_s"):
        assert now.pop(key) > 0
    assert now == _parent_logits_check(DENSE_FAM, sz, eng, spec, seed)
    assert not hasattr(DENSE_FAM, "routing_taken")


def test_served_tokens_check_is_the_parents_function():
    """Check 2 is left as it is (ISSUE 29, C): the function's text is the
    parent's, so are its dictionaries. A `benchmark` PR that changes check
    2 on purpose changes this digest with it."""
    text = inspect.getsource(checks.served_tokens_check)
    assert hashlib.sha256(text.encode()).hexdigest() == SERVED_CHECK_SHA256


SERVED_CHECK_SHA256 = "aa8ccb2e34dcc83f080dd9dca95e524cbe228eb9f42d73d14c663ac7b348f788"


# ---- the backend gate reads the configuration's file --------------------------

WHY = {"backend": "gather", "backend_why": "heads of 64"}


@pytest.mark.parametrize("stated,want", [
    ({}, "pallas"), ({"backend": "pallas"}, "pallas"), (WHY, "gather"),
    ({"backend": "gather"}, None), ({"backend": "interpret"}, None)])
def test_stated_backend_is_the_files(stated, want):
    spec = dict(CHK["logits"], **stated)
    if want is None:
        with pytest.raises(common.BenchError):
            checks.stated_backend(spec)
    else:
        assert checks.stated_backend(spec) == want


@pytest.mark.parametrize("stated,resolved,interpret,passes", [
    ({}, "pallas", False, True), ({}, "gather", False, False),
    (WHY, "gather", False, True), (WHY, "pallas", False, False),
    ({}, "pallas", True, False), (WHY, "gather", True, False)])
def test_serve_child_holds_a_chip_run_to_the_stated_backend(
        monkeypatch, stated, resolved, interpret, passes):
    """Not a rehearsal: the child exits with 3 unless the backend check 1
    resolved to is the one the file states (absent: the compiled Pallas
    kernel), and never runs the interpreter."""
    from ray_tpu.ops import paged_attention as paged_ops
    monkeypatch.setattr(checks, "require_device", lambda chips, rehearsal: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    monkeypatch.setattr(paged_ops, "interpret_default", lambda: interpret)
    monkeypatch.setattr(checks, "logits_check", lambda *a, **k: {
        "ok": True, "backend": resolved})
    monkeypatch.setattr(checks, "served_tokens_check", lambda *a, **k: {
        "ok": True})
    spec = {"rehearsal": False, "chips": 1, "seed": 5,
            "family": DENSE["model_family"],
            "sizes": DENSE_FAM.sizes(DENSE, True),
            "engine": common.section(DENSE, "engine", True), "samples": [],
            "checks": {"logits": dict(CHK["logits"], **stated),
                       "served_tokens": {"margin": 1.0}}}
    if passes:
        out = checks.serve_child(spec)
        assert out["logits"]["backend"] == resolved and out["served_tokens"]
    else:
        with pytest.raises(SystemExit) as exc:
            checks.serve_child(spec)
        assert exc.value.code == 3


# ---- dropped in, with no edit of a file that was there ------------------------

def test_dropped_in_routed_family_goes_through_the_checks_child(tmp_path):
    """A later PR's move for a routed family: its adapter, reference and
    configuration as new files under benchmark/. The checks child (what
    holds the chip after the replica has gone) runs check 1 with the
    programs' choice forced and check 2 on tokens the family's programs
    decoded, as a rehearsal on the CPU."""
    copy = str(tmp_path / "checkout")
    before = {}
    for d, _dirs, files in os.walk(os.path.join(common.ROOT, "benchmark")):
        if "__pycache__" not in d:
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), common.ROOT)
                with open(os.path.join(d, f), "rb") as fh:
                    before[rel] = fh.read()
    at_size.drop_in(copy)
    cfg = FAM.model_config(SZ)
    params = FAM.init_params(jax.random.PRNGKey(0), cfg)
    samples = drive.served_samples(cfg, params, ENG, 2**31 + 5, [12, 30, 70],
                                   24)
    out = at_size.checks_child(copy, {
        "rehearsal": True, "chips": 1, "seed": 2**31 + 11,
        "family": CONFIG["model_family"], "sizes": SZ, "engine": ENG,
        "samples": samples, "checks": CHK}, "spec")
    assert out["rc"] == 0, out["stderr_tail"]
    line = out["line"]
    assert line["device"]["platform"] == "cpu"
    assert line["logits"]["ok"] and line["logits"]["backend"] == "gather"
    assert line["logits"]["routing"]["decisions"] == 196
    assert line["served_tokens"]["ok"]
    assert line["served_tokens"]["tokens_checked"] == 3 * 24
    for rel, data in before.items():
        with open(os.path.join(copy, rel), "rb") as fh:
            assert fh.read() == data, f"{rel} was edited"
    for kind, name in (("models", CONFIG["model_family"]),
                       ("reference", FAM.REFERENCE)):
        assert f"benchmark/{kind}/{name}.py" not in before
        assert os.path.exists(os.path.join(copy, "benchmark", kind,
                                           f"{name}.py"))
    assert not common.descendants()


def test_nothing_of_the_toy_is_imported_by_the_benchmark():
    for d, _dirs, files in os.walk(os.path.join(common.ROOT, "benchmark")):
        for f in files:
            if f.endswith((".py", ".json")):
                with open(os.path.join(d, f), errors="replace") as fh:
                    assert "routed_toy" not in fh.read(), os.path.join(d, f)


# ---- one contract, said three times --------------------------------------------

NAMES = ["paged_programs", "routing_taken", "routing=", "rms_tolerance",
         "routing_slack", "routing_flip_share_max", "checks.logits.backend",
         "backend_why", "attn_layers", "n_layers=depth"]


@pytest.mark.parametrize("where", ["reference/__init__.py", "run.py",
                                   "models"])
def test_the_contract_is_stated_alike_in_its_three_places(where):
    if where == "models":
        text = inspect.getdoc(DENSE_FAM)
    else:
        with open(os.path.join(common.HERE, where)) as f:
            text = f.read().split('"""')[1]
    flat = " ".join(text.split())
    for name in NAMES:
        assert name in flat, (where, name)
