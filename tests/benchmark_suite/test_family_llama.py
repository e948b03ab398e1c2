"""The one model family the benchmark has, and its plain reference:
benchmark/models/llama.py and benchmark/reference/llama_f32.py. The only
test file that names either; everything else under benchmark/ and
tests/benchmark_suite/ reaches them through the configuration's
``model_family`` (the grep test below holds that line)."""

import inspect
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common

MAN = common.manifest()
ALLOWED = {"benchmark/models/llama.py", "benchmark/reference/llama_f32.py",
           "tests/benchmark_suite/test_family_llama.py"}
NAMES = re.compile(r"models.llama|llama_f32|llama_config")
CONFIGS = {c["name"]: common.load_json(common.ROOT, c["file"])
           for c in MAN["configs"]}
SERVE = CONFIGS["mistral-7b-v0.3-serve-1chip"]
FAM = common.family(SERVE)
REF = common.reference(FAM)
TINY = FAM.sizes(SERVE, True)


def test_only_the_family_and_its_reference_name_the_block():
    """ISSUE 27's line: ``grep -rn "models.llama\\|llama_f32\\|llama_config"
    benchmark tests/benchmark_suite`` names the adapter, its reference and
    this file, and nothing else."""
    hits = set()
    for top in ("benchmark", "tests/benchmark_suite"):
        for d, _dirs, files in os.walk(os.path.join(common.ROOT, top)):
            if "__pycache__" in d:
                continue
            for f in files:
                path = os.path.join(d, f)
                with open(path, errors="replace") as fh:
                    if NAMES.search(fh.read()):
                        hits.add(os.path.relpath(path, common.ROOT))
    assert hits <= ALLOWED, sorted(hits - ALLOWED)
    serve_cell = open(os.path.join(common.HERE, "serve_cell.py")).read()
    assert not re.search(r"tp_degree\s*=\s*\d", serve_cell)   # no literal


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_configuration_names_a_family_that_resolves(config):
    cfg = CONFIGS[config]
    assert cfg["model_family"] == "llama"          # stated, no default
    fam = common.family(cfg)
    assert fam.__file__.endswith("benchmark/models/llama.py")
    assert common.reference(fam).__file__.endswith(
        "benchmark/reference/llama_f32.py")
    for name in ("sizes", "model_config", "init_params", "reference_kwargs",
                 "logical_axes", "loss_fn", "num_params", "params",
                 "train_flops_per_token"):
        assert callable(getattr(fam, name)), name
    assert isinstance(fam.MODEL_SCOPES, tuple) and "attn" in fam.MODEL_SCOPES
    with pytest.raises(common.BenchError):
        common.family({k: v for k, v in cfg.items() if k != "model_family"})


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_sizes_keep_the_keys_the_readers_use(config):
    cfg = CONFIGS[config]
    for rehearsal in (False, True):
        sz = FAM.sizes(cfg, rehearsal)
        assert {"n_layers", "n_heads", "n_kv_heads", "dim",
                "vocab_size"} <= set(sz)
    sz = FAM.sizes(cfg, False)
    assert (sz["dim"], sz["n_heads"], sz["n_kv_heads"], sz["ffn_dim"],
            sz["vocab_size"]) == (4096, 32, 8, 14336, 32768)
    assert sz["n_layers"] == cfg["num_hidden_layers"]
    assert sz["max_seq_len"] == 2048 and sz["dtype"] == "bfloat16"


def test_model_config_is_the_programs_and_takes_the_trainers_recipe():
    from ray_tpu.models import llama
    cfg = FAM.model_config(TINY)
    assert isinstance(cfg, llama.LlamaConfig)
    assert (cfg.dim, cfg.n_layers, cfg.dtype) == (64, 2, jnp.float32)
    assert FAM.model_config(TINY, n_layers=1).n_layers == 1
    tr = common.section(CONFIGS["mistral-7b-v0.3-train-fsdp4"], "trainer", True)
    got = FAM.model_config(TINY, trainer=tr)
    assert (got.remat_policy, got.attn_impl, got.ce_chunk) == (
        "dots", "flash", 64)
    assert FAM.reference_kwargs(cfg) == {"theta": 1e6, "eps": 1e-5}
    assert FAM.reference_kwargs(cfg, use_rope=False)["use_rope"] is False
    assert FAM.num_params(cfg) == FAM.params(TINY)


def test_parameter_and_flop_counts_against_hand_counts():
    sz = {"vocab_size": 32768, "dim": 4096, "n_layers": 24, "n_heads": 32,
          "n_kv_heads": 8, "ffn_dim": 14336}
    per_layer = (4096 * (32 + 16) * 128 + 32 * 128 * 4096
                 + 3 * 4096 * 14336 + 2 * 4096)
    assert per_layer == 218_112_000
    assert FAM.params(sz) == 2 * 32768 * 4096 + 4096 + 24 * per_layer
    assert FAM.params(sz) == 5_503_127_552            # PR 23's chip report
    assert FAM.train_flops_per_token(sz, 2048) == pytest.approx(
        6 * (5_503_127_552 - 32768 * 4096) + 6 * 24 * 2048 * 4096)


def test_train_mfu_reads_the_familys_count():
    run = {"family": FAM, "sizes": FAM.sizes(
               CONFIGS["mistral-7b-v0.3-train-fsdp4"], False),
           "device": {"kind": "TPU v5 lite"},
           "train": {"window_step_s": [1.3097] * 3, "tokens_per_step": 16384,
                     "seq_len": 2048, "chips": 4}}
    # 16384 / 1.3097 s = 12,510 tokens/s over 4 x 197 TFLOP/s (PR 25: 53.0)
    assert common.load_module("metrics", "train_mfu").reduce(run) \
        == pytest.approx(53.0, abs=0.1)


@pytest.mark.parametrize("fn", ["hidden", "logits_at", "deficits", "loss"])
def test_reference_keeps_the_contract_of_its_package(fn):
    """benchmark/reference/__init__.py: the four functions, each taking
    the adapter's keywords by name."""
    sig = inspect.signature(getattr(REF, fn))
    assert list(sig.parameters)[0] == "params"
    for kw in FAM.reference_kwargs(FAM.model_config(TINY), use_rope=True):
        assert sig.parameters[kw].kind is inspect.Parameter.KEYWORD_ONLY
    doc = common.load_module("reference", "__init__").__doc__
    assert f"{fn}(params" in doc and "highest" in doc


def test_reference_is_float32_and_its_parts_agree():
    """hidden -> head == logits_at; deficits of the reference's own argmax
    are 0 and of another token positive; loss == the mean NLL of logits_at."""
    cfg = FAM.model_config(TINY)
    params = FAM.init_params(jax.random.PRNGKey(3), cfg)
    kw = FAM.reference_kwargs(cfg)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, 33), 0, TINY["vocab_size"]), np.int32)
    hid = REF.hidden(params, toks[:, :-1], **kw)
    assert hid.dtype == jnp.float32 and hid.shape == (2, 32, TINY["dim"])
    lg = REF.logits_at(params, toks[:, :-1], np.arange(32), **kw)
    assert lg.dtype == jnp.float32 and lg.shape == (2, 32, TINY["vocab_size"])
    best = np.asarray(jnp.argmax(lg[0], axis=-1), np.int32)
    d, ok = REF.deficits(params, hid[0], 4, best[4:12], 8, **kw)
    assert float(d) == 0.0 and bool(ok)
    other = (best[4:12] + 1) % TINY["vocab_size"]
    d, ok = REF.deficits(params, hid[0], 4, other, 8, **kw)
    assert float(d) > 0.0 and bool(ok)
    # only the first n of the padded width count
    d, _ = REF.deficits(params, hid[0], 4, np.concatenate(
        [best[4:8], other[4:]]).astype(np.int32), 4, **kw)
    assert float(d) == 0.0
    nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, jnp.asarray(toks[:, 1:])[..., None], axis=-1)[..., 0]
    assert REF.loss(params, toks, **kw) == pytest.approx(
        float(jnp.mean(nll)), rel=1e-6)
    # the negative control's override reaches the mathematics
    assert not np.allclose(REF.logits_at(params, toks[:, :-1], np.arange(32),
                                         **dict(kw, use_rope=False)), lg)
