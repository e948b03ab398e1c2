"""BENCHMARK.json against the contract's letter, every name resolving to a
file, and the proof that the harness is driven by data: a configuration, a
cell, a generator and a metric dropped into a copy run with no edit of a
file that was there."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import common

MAN = common.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def _cells_of(metric):
    return metric.get("workloads") or [w["name"] for w in MAN["workloads"]]


def test_manifest_has_exactly_the_contract_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) < 65536
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 4)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_units_and_keys_use_only_what_the_contract_allows(group):
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source",
                              "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves", "workloads"}}[group]
    names = [e["name"] for e in MAN[group]]
    assert len(names) == len(set(names))
    for e in MAN[group]:
        assert set(e) <= allowed, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
        if group == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.1
        if group == "workloads":
            assert NAME.match(e["traffic"]) and e["chips"] in (1, 4)


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_resolves_to_files_and_reports_what_it_must(cell):
    entry, cell_file, config = common.load_cell(cell)
    assert cell_file["config"] == entry["config"]
    assert cell_file["chips"] == entry["chips"] == config["chips"]
    for part in (cell_file, cell_file["rehearsal"]):
        gen = common.load_module("traffic", part["traffic"]["generator"])
        assert callable(gen.plan)
    cfg_entry = next(c for c in MAN["configs"] if c["name"] == entry["config"])
    assert cfg_entry["file"].startswith("benchmark/configs/")
    assert sorted(cfg_entry["reduced"]) == sorted(config["reduced"])
    assert config["source"] == cfg_entry["source"]
    e2e = [m["name"] for m in common.cell_metrics(MAN, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert common.cell_metrics(MAN, cell, "per_layer")
    for group in ("end_to_end", "per_layer"):
        for m in common.cell_metrics(MAN, cell, group):
            assert callable(common.load_module("metrics", m["name"]).reduce)


WIDTH = re.compile(r"(_size$|_dim$|_rank$|head|experts_per|window|state|lora)")
# what may be the chip's share of a stated deployment, and so is no width:
# how many experts live here, how many heads (never the size of one)
SHARE = re.compile(r"^(num_experts|n_routed_experts|num_local_experts"
                   r"|num_attention_heads|num_key_value_heads)$")


def width_problems(entry: dict, cfg: dict) -> list[str]:
    """What the contract refuses in a configuration's file, for any model
    family: a key of ``published`` (the source's shape keys, verbatim) that
    is not listed in ``reduced`` and differs at the top level of the file
    or is left out, and a key of ``reduced`` that names a width."""
    out = [f"{k}: reduced names a width" for k in entry["reduced"]
           if WIDTH.search(k) and not SHARE.match(k)]
    if not isinstance(cfg.get("published"), dict) or not cfg["published"]:
        return out + ["the file has no `published`"]
    for k, v in cfg["published"].items():
        if k not in entry["reduced"] and k not in cfg:
            out.append(f"{k}: left out of the file")
        elif k not in entry["reduced"] and cfg[k] != v:
            out.append(f"{k}: {cfg[k]!r} is not the published {v!r}")
    return out + [f"{k}: reduced, but not a published key"
                  for k in entry["reduced"] if k not in cfg["published"]]


@pytest.mark.parametrize("config", [c["name"] for c in MAN["configs"]])
def test_no_width_is_reduced(config):
    entry = next(c for c in MAN["configs"] if c["name"] == config)
    cfg = common.load_json(common.ROOT, entry["file"])
    assert width_problems(entry, cfg) == []
    assert callable(common.family(cfg).sizes)      # the family resolves


def test_the_mistral_configurations_keep_their_six_widths():
    for c in MAN["configs"]:
        cfg = common.load_json(common.ROOT, c["file"])
        if "Mistral-7B-v0.3" not in cfg["source"]:
            continue
        assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["vocab_size"]) == (4096, 14336, 128, 32, 8, 32768)


@pytest.mark.parametrize("change,reduced,problem", [
    ({"hidden_size": 2048}, ["num_hidden_layers"], "hidden_size: 2048"),
    ({"hidden_size": None}, ["num_hidden_layers"], "hidden_size: left out"),
    ({}, ["num_hidden_layers", "hidden_size"], "hidden_size: reduced names"),
    ({}, ["num_hidden_layers", "kv_lora_rank"], "kv_lora_rank: reduced names"),
    ({}, ["num_hidden_layers", "sliding_window"], "sliding_window: reduced"),
    ({}, ["num_hidden_layers", "num_experts_per_tok"], "num_experts_per_tok"),
    ({}, ["num_hidden_layers", "depth"], "depth: reduced, but not a publ"),
    ({"published": None}, ["num_hidden_layers"], "no `published`"),
])
def test_width_test_refuses_a_file_that_departs_from_its_source(
        change, reduced, problem):
    entry = dict(MAN["configs"][0], reduced=reduced)
    cfg = dict(common.load_json(common.ROOT, entry["file"]), **change)
    if change.get("hidden_size", 0) is None:
        del cfg["hidden_size"]                     # a key left out
    assert any(problem in p for p in width_problems(entry, cfg))


def test_a_count_of_experts_or_heads_may_be_the_chips_share():
    """OLMoE served as one chip's share of a four-way expert-parallel
    deployment: 16 of 64 experts here, every width as published."""
    pub = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
           "num_experts_per_tok": 8, "num_attention_heads": 16,
           "num_hidden_layers": 16}
    cfg = dict(pub, num_experts=16, published=pub)
    assert width_problems({"reduced": ["num_experts"]}, cfg) == []
    assert width_problems({"reduced": []}, cfg) == [
        "num_experts: 16 is not the published 64"]


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_per_layer_metric_moves_an_end_to_end_metric_of_each_of_its_cells(
        metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    assert m["moves"] in E2E
    for cell in _cells_of(m):
        assert cell in _cells_of(E2E[m["moves"]]), (metric, cell)
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


def test_every_file_under_paths_is_named_from_allowed_characters():
    for path in MAN["paths"]:
        for d, _dirs, files in os.walk(os.path.join(common.ROOT, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(d, f)


# what the dropped-in family calls the source's shape keys
RENAMED = {"hidden_size": "d_model", "intermediate_size": "d_ff",
           "num_hidden_layers": "n_layer", "num_attention_heads": "n_head",
           "num_key_value_heads": "n_head_kv", "vocab_size": "n_vocab",
           "rope_theta": "rotary_base", "rms_norm_eps": "norm_epsilon",
           "torch_dtype": "dtype"}


def _checkout(tmp_path):
    """A copy of the benchmark beside the program, and what it held."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(common.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(common.ROOT, "ray_tpu"), root / "ray_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    return root, root / "benchmark", before


def _run_py(root, *args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), *args], env=env,
        cwd=root, capture_output=True, text=True, timeout=timeout)


def _drop_family(b, config: dict) -> dict:
    """A second model family as a later PR would add it: an adapter that
    reads the source's shape keys under other names, a reference file of
    its own, and a configuration written in those names. (The block is the
    one the engine serves, so both are copies with the names changed.)"""
    fam = common.family(config)
    src = (b / "models" / f"{config['model_family']}.py").read_text()
    for old, new in RENAMED.items():
        assert f'config["{old}"]' in src
        src = src.replace(f'config["{old}"]', f'config["{new}"]')
    assert f'REFERENCE = "{fam.REFERENCE}"' in src
    (b / "models" / "dropped_family.py").write_text(src.replace(
        f'REFERENCE = "{fam.REFERENCE}"', 'REFERENCE = "dropped_f32"'))
    shutil.copy(b / "reference" / f"{fam.REFERENCE}.py",
                b / "reference" / "dropped_f32.py")
    out = {RENAMED.get(k, k): v for k, v in config.items()}
    out["published"] = {RENAMED.get(k, k): v
                        for k, v in config["published"].items()}
    out["reduced"] = [RENAMED.get(k, k) for k in config["reduced"]]
    out["model_family"] = "dropped_family"
    return out


def test_dropped_in_files_run_with_no_edit_of_an_existing_file(tmp_path):
    """A later PR's move: add a model family (adapter and reference), a
    configuration that names it, a cell on it, a generator and a metric as
    new files and new entries; the harness lists and runs them (tiny
    preset, CPU rehearsal) to a ``correct`` result line."""
    root, b, before = _checkout(tmp_path)
    config = _drop_family(b, common.load_json(
        b / "configs" / "mistral-7b-v0.3-serve-1chip.json"))
    (b / "configs" / "dropped-config.json").write_text(json.dumps(config))
    shutil.copy(b / "traffic" / "open_loop.py", b / "traffic" / "dropped_gen.py")
    cell = json.loads((b / "workloads" / "mistral7b-serve-chat.json").read_text())
    cell["config"] = "dropped-config"
    cell["rehearsal"]["traffic"]["generator"] = "dropped_gen"
    cell["traffic"]["generator"] = "dropped_gen"
    (b / "workloads" / "dropped-cell.json").write_text(json.dumps(cell))
    (b / "metrics" / "dropped_metric.py").write_text(
        "def reduce(run):\n    return float(len(run['records']))\n")
    man = json.loads(json.dumps(MAN))
    man["configs"].append(dict(
        man["configs"][0], name="dropped-config", reduced=config["reduced"],
        file="benchmark/configs/dropped-config.json"))
    man["workloads"].append(dict(man["workloads"][0], name="dropped-cell",
                                 config="dropped-config", traffic="dropped"))
    man["per_layer"].append({
        "name": "dropped_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "setup_s", "workloads": ["dropped-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    # the new family's adapter reads its own names and gives the harness
    # the sizes it knows; the generic width test holds for its file
    real = common.load_json(common.ROOT, MAN["configs"][0]["file"])
    dropped = common.load_module("models", "dropped_family", str(root))
    assert dropped.sizes(config, False) == common.family(real).sizes(real, False)
    assert "hidden_size" not in config and config["d_model"] == 4096
    assert width_problems(man["configs"][-1], config) == []
    listed = _run_py(root, "--list", timeout=60)
    assert "dropped-cell dropped-config dropped 1" in listed.stdout
    proc = _run_py(root, "--workload", "dropped-cell", "--seed",
                   str(2**31 + 17), "--seconds", "2", "--trace", "1",
                   "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, result = (json.loads(x) for x in
                      proc.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["dropped_metric"]["value"] == result["attempted"]
    # only setup_s is shared: the dropped cell reports just its own metric
    assert set(result["metrics"]) == {"dropped_metric"}
    # both checks ran against the dropped family's own reference
    assert all(report["report"]["checks"][k]["ok"]
               for k in ("logits", "served_tokens", "structure"))
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    assert not common.descendants()


def _drop_tp2(b, root, chips: int) -> dict:
    """A serve configuration whose engine spans two chips, and a cell on
    it that asks for ``chips``."""
    config = common.load_json(b / "configs" / "mistral-7b-v0.3-serve-1chip.json")
    config["chips"] = chips
    config["engine"]["tp_degree"] = config["rehearsal"]["engine"][
        "tp_degree"] = 2
    (b / "configs" / "dropped-tp2.json").write_text(json.dumps(config))
    cell = json.loads((b / "workloads" / "mistral7b-serve-chat.json").read_text())
    cell.update(config="dropped-tp2", chips=chips)
    (b / "workloads" / "dropped-tp2-cell.json").write_text(json.dumps(cell))
    man = json.loads(json.dumps(MAN))
    man["configs"].append(dict(man["configs"][0], name="dropped-tp2",
                               file="benchmark/configs/dropped-tp2.json"))
    man["workloads"].append(dict(man["workloads"][0], name="dropped-tp2-cell",
                                 config="dropped-tp2", chips=chips))
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return config


def test_engine_layout_is_the_files_a_replica_over_two_devices(tmp_path):
    """``engine.tp_degree`` 2 in a dropped-in configuration and a cell of
    two chips: the rehearsal runs one replica over two forced host
    devices, with no edit of a file that was there."""
    root, b, before = _checkout(tmp_path)
    _drop_tp2(b, root, chips=2)
    proc = _run_py(root, "--workload", "dropped-tp2-cell", "--seed",
                   str(2**31 + 29), "--seconds", "2", "--trace", "0",
                   "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 2,
                                "memory_peak_bytes": 0}
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    assert not common.descendants()


def test_a_replica_that_spans_other_chips_than_the_cell_is_refused(tmp_path):
    """tp_degree 2 under a cell of one chip: refused before any process
    starts (no runtime, no replica), with no result line."""
    from benchmark import serve_cell
    root, b, _before = _checkout(tmp_path)
    config = _drop_tp2(b, root, chips=1)
    # the function serve_cell.run calls ahead of ray_tpu.init
    with pytest.raises(common.BenchError, match="tp_degree=2"):
        serve_cell._engine_config(config, True, 1)
    proc = _run_py(root, "--workload", "dropped-tp2-cell", "--seed", "5",
                   "--seconds", "2", "--trace", "0", "--rehearsal", timeout=60)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "tp_degree=2" in proc.stderr and "asks for 1" in proc.stderr
    assert not list(root.rglob("checks_spec.json"))
    assert not common.descendants()
