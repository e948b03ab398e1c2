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


def test_no_width_is_reduced():
    for c in MAN["configs"]:
        for key in c["reduced"]:
            assert not re.search(r"(_size$|_dim$|_rank$|head|experts_per)",
                                 key), key
        cfg = common.load_json(common.ROOT, c["file"])
        assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["vocab_size"]) == (4096, 14336, 128, 32, 8, 32768)


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


def test_dropped_in_files_run_with_no_edit_of_an_existing_file(tmp_path):
    """A later PR's move: add a configuration, a cell, a generator and a
    metric as new files and new entries; the harness lists and runs them
    (tiny preset, CPU rehearsal)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(common.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(common.ROOT, "ray_tpu"), root / "ray_tpu")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    b = root / "benchmark"
    shutil.copy(b / "configs" / "mistral-7b-v0.3-serve-1chip.json",
                b / "configs" / "dropped-config.json")
    shutil.copy(b / "traffic" / "open_loop.py", b / "traffic" / "dropped_gen.py")
    cell = json.loads((b / "workloads" / "mistral7b-serve-chat.json").read_text())
    cell["config"] = "dropped-config"
    cell["rehearsal"]["traffic"]["generator"] = "dropped_gen"
    cell["traffic"]["generator"] = "dropped_gen"
    (b / "workloads" / "dropped-cell.json").write_text(json.dumps(cell))
    (b / "metrics" / "dropped_metric.py").write_text(
        "def reduce(run):\n    return float(len(run['records']))\n")
    man = json.loads(json.dumps(MAN))
    man["configs"].append(dict(man["configs"][0], name="dropped-config",
                               file="benchmark/configs/dropped-config.json"))
    man["workloads"].append(dict(man["workloads"][0], name="dropped-cell",
                                 config="dropped-config", traffic="dropped"))
    man["per_layer"].append({
        "name": "dropped_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine loop",
        "moves": "setup_s", "workloads": ["dropped-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    listed = subprocess.run(
        [sys.executable, str(b / "run.py"), "--list"], env=env, cwd=root,
        capture_output=True, text=True, timeout=60)
    assert "dropped-cell dropped-config dropped 1" in listed.stdout
    proc = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", "dropped-cell",
         "--seed", str(2**31 + 17), "--seconds", "2", "--trace", "1",
         "--rehearsal"], env=env, cwd=root, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"]["dropped_metric"]["value"] == result["attempted"]
    # only setup_s is shared: the dropped cell reports just its own metric
    assert set(result["metrics"]) == {"dropped_metric"}
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
    assert not common.descendants()
