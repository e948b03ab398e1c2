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


@pytest.mark.parametrize("metric,cell", [
    (m["name"], cell) for m in MAN["per_layer"] for cell in _cells_of(m)])
def test_per_layer_metric_moves_an_end_to_end_metric_of_each_of_its_cells(
        metric, cell):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    assert m["moves"] in E2E
    assert cell in _cells_of(E2E[m["moves"]]), (metric, cell)
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


# ---- one entry a (reader, moves); a cell joins a reader by its list --------

CELLS = {"chat": "mistral7b-serve-chat", "peak": "mistral7b-serve-peak",
         "train": "mistral7b-train-fsdp4", "lfm2": "lfm2-8b-a1b-serve-decode",
         "sdar": "sdar-30b-a3b-serve-decode",
         "joyai": "joyai-llm-flash-serve-decode",
         "trinity": "trinity-large-serve-longctx",
         "mimo": "mimo-v2-flash-serve-reasoning"}

# (entry, unit, better, source, layer, moves, cells) as PR 59 left the
# section: the 146 (reader file, cell) pairs of the 128 suffixed entries
# before it, each with the fields it had there, the 14 of the two
# start-up readers listed since, and chat's first_token_p90_ms. A later PR
# appends a cell to a list or an entry to the section; one that drops or
# changes a pair fails here. ONE field differs from the parent's: chat's
# five readers that moved ttft_p90_ms move ttft_p50_ms, because the check
# of PR 59 found no admissible bound for the 90th percentile end to end
# (PERF.md section 2); it is read per layer as first_token_p90_ms.
READ_SINCE_PR59 = [
    ("stream_gap_p95_ms", "ms", "lower", "host_clock",
     "client, ingress", "tpot_p90_ms", "chat"),
    ("queue_wait_p95_ms", "ms", "lower", "program_span",
     "engine loop", "ttft_p50_ms", "chat"),
    ("compiles_in_window.chat", "count", "lower", "program_counter",
     "engine loop", "ttft_p50_ms", "chat"),
    ("compiles_in_window", "count", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("engine_tokens_per_step", "tokens/step", "higher", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("kv_pages_in_use_share", "%", "higher", "program_counter",
     "KV manager", "serve_tokens_per_s", "peak joyai"),
    ("flash_roofline", "%", "higher", "device_trace",
     "kernels", "train_tokens_per_s_per_chip", "train"),
    ("train_step_ms", "ms", "lower", "host_clock",
     "train step", "train_tokens_per_s_per_chip", "train"),
    ("train_mfu", "%", "higher", "host_clock",
     "train step", "train_tokens_per_s_per_chip", "train"),
    ("collective_exposed_share", "%", "lower", "device_trace",
     "collectives", "train_tokens_per_s_per_chip", "train"),
    ("device_idle_share.chat", "%", "lower", "device_trace",
     "device", "tpot_p90_ms", "chat"),
    ("device_idle_share", "%", "lower", "device_trace",
     "device", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("device_idle_share.train", "%", "lower", "device_trace",
     "device", "train_tokens_per_s_per_chip", "train"),
    ("decode_step_traced_ms.chat", "ms", "lower", "device_trace",
     "model step", "tpot_p90_ms", "chat"),
    ("decode_step_traced_ms", "ms", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "peak lfm2 joyai trinity mimo"),
    ("prefill_traced_ms_per_ktok", "ms/ktok", "lower", "device_trace",
     "model step", "ttft_p50_ms", "chat"),
    ("model_op_share.chat", "%", "higher", "device_trace",
     "model step", "tpot_p90_ms", "chat"),
    ("model_op_share", "%", "higher", "device_trace",
     "model step", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("model_op_share.train", "%", "higher", "device_trace",
     "train step", "train_tokens_per_s_per_chip", "train"),
    ("idle_host_busy_share.chat", "%", "lower", "device_trace",
     "engine loop", "tpot_p90_ms", "chat"),
    ("idle_host_busy_share", "%", "lower", "device_trace",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("engine_loop_busy_share.chat", "%", "lower", "program_counter",
     "engine loop", "ttft_p50_ms", "chat"),
    ("engine_loop_busy_share", "%", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak"),
    ("paged_decode_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "peak lfm2"),
    ("prefill_program_share.chat", "%", "lower", "device_trace",
     "model step", "ttft_p50_ms", "chat"),
    ("prefill_program_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("expert_ffn_roofline", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "lfm2 joyai trinity mimo"),
    ("routed_ffn_share", "%", "higher", "device_trace",
     "model step", "serve_tokens_per_s", "lfm2 sdar joyai trinity mimo"),
    ("experts_touched_share", "%", "higher", "program_counter",
     "engine loop", "serve_tokens_per_s", "lfm2 sdar joyai trinity mimo"),
    ("block_pass_traced_ms", "ms", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "sdar"),
    ("passes_per_token", "passes/token", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "sdar"),
    ("unmask_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "sdar"),
    ("expert_ffn_block_roofline", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "sdar"),
    ("paged_block_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "sdar"),
    ("gc_pause_share.chat", "%", "lower", "program_counter",
     "engine loop", "tpot_p90_ms", "chat"),
    ("gc_pause_share", "%", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("pipeline_dry_share.chat", "%", "lower", "program_counter",
     "engine loop", "tpot_p90_ms", "chat"),
    ("pipeline_dry_share", "%", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("host_stall_share.chat", "%", "lower", "program_counter",
     "engine loop", "tpot_p90_ms", "chat"),
    ("host_stall_share", "%", "lower", "program_counter",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("idle_gc_share.chat", "%", "lower", "device_trace",
     "engine loop", "tpot_p90_ms", "chat"),
    ("idle_gc_share", "%", "lower", "device_trace",
     "engine loop", "serve_tokens_per_s", "peak lfm2 sdar joyai trinity mimo"),
    ("paged_latent_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "joyai"),
    ("latent_attn_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "joyai"),
    ("latent_proj_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "joyai"),
    ("shared_expert_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "joyai trinity"),
    ("paged_window_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "trinity"),
    ("paged_window_chunk_roofline", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "trinity"),
    ("window_attn_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "trinity mimo"),
    ("full_attn_share", "%", "lower", "device_trace",
     "model step", "serve_tokens_per_s", "trinity mimo"),
    ("ring_pages_share", "%", "lower", "program_counter",
     "KV manager", "serve_tokens_per_s", "trinity mimo"),
    ("paged_full_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "mimo"),
    ("paged_ring_roofline_traced", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "mimo"),
    ("paged_mixed_chunk_roofline", "%", "higher", "device_trace",
     "kernels", "serve_tokens_per_s", "mimo"),
    ("setup_programs_share", "%", "lower", "program_span",
     "engine start-up", "setup_s", "chat peak lfm2 sdar joyai trinity mimo"),
    ("setup_cache_misses", "count", "lower", "program_counter",
     "engine start-up", "setup_s", "chat peak lfm2 sdar joyai trinity mimo"),
    ("setup_untraced_share", "%", "lower", "program_span",
     "engine start-up", "setup_s", "chat peak lfm2 sdar joyai trinity mimo"),
    ("setup_load_ms_per_program", "ms", "lower", "program_span",
     "engine start-up", "setup_s", "chat peak lfm2 sdar joyai trinity mimo"),
    ("setup_lower_ms_per_program", "ms", "lower", "program_span",
     "engine start-up", "setup_s", "chat peak lfm2 sdar joyai trinity mimo"),
    ("first_token_p90_ms", "ms", "lower", "host_clock",
     "client, ingress", "ttft_p50_ms", "chat"),
]


def _reader_file(name: str) -> str:
    """The file under benchmark/metrics/ that ``common.load_module`` loads
    for an entry: its own, or for ``a.b`` without one the reader ``a``."""
    own = os.path.join(common.bench_dir(), "metrics", f"{name}.py")
    return name if os.path.exists(own) else name.split(".")[0]


def _pairs(per_layer: list) -> dict:
    """{(reader file, cell): [(unit, better, source, layer, moves), ...]}"""
    out: dict = {}
    for m in per_layer:
        for cell in m["workloads"]:
            out.setdefault((_reader_file(m["name"]), cell), []).append(
                (m["unit"], m["better"], m["source"], m["layer"], m["moves"]))
    return out


READ = _pairs(MAN["per_layer"])


def test_the_written_list_holds_the_parents_146_pairs_and_the_15_new():
    pairs = [(_reader_file(row[0]), cell) for row in READ_SINCE_PR59
             for cell in row[6].split()]
    assert len(pairs) == len(set(pairs)) == 146 + 14 + 1
    assert len(READ_SINCE_PR59) == 57 + 2 + 1


def _run_of(first_token_ms: list, t1: float = 51.0) -> dict:
    """A run's records as http_client leaves them: due at 1.0, the first
    chunk ``ms`` later (None: the stream never began)."""
    return {"window": {"t1": t1}, "records": [
        {"due": 1.0, "first": None if ms is None else 1.0 + ms / 1e3}
        for ms in first_token_ms]}


@pytest.mark.parametrize("reader,rank", [("ttft_p50_ms", 51),
                                         ("first_token_p90_ms", 92)])
def test_first_token_readers_take_the_nearest_rank_of_every_request(
        reader, rank):
    """Chat's window holds 102 requests: the median is the 51st smallest
    first-token time and the 90th percentile the 92nd, one request's time
    each and no mean; a request cut by the closed loop's cool-down is not
    the window's, one that never began counts as given up at t1 + 60 s."""
    reduce = common.load_module("metrics", reader).reduce
    times = [float(ms) for ms in range(102, 0, -1)]       # 102 ... 1 ms
    assert reduce(_run_of(times)) == pytest.approx(rank)
    run = _run_of(times[:-1] + [None])                    # the quickest lost
    assert reduce(run) == pytest.approx(rank + 1)
    assert reduce(_run_of([None])) == pytest.approx((51.0 + 60.0 - 1.0) * 1e3)
    run = _run_of(times)
    run["records"].append({"due": 1.0, "first": 9.0, "abandoned": True})
    assert reduce(run) == pytest.approx(rank)
    assert reduce({"window": {"t1": 51.0}, "records": []}) is None


def test_chat_is_judged_by_the_median_first_token_and_reads_the_tail():
    """ttft_p90_ms is no end-to-end metric since PR 59's check (its runs
    spread by half of the widest bound the contract has): chat reports the
    median end to end and the 90th percentile per layer, moving it."""
    assert "ttft_p90_ms" not in E2E
    assert E2E["ttft_p50_ms"]["workloads"] == [CELLS["chat"]]
    assert E2E["ttft_p50_ms"]["better"] == "lower"
    tail = next(m for m in MAN["per_layer"] if m["name"] == "first_token_p90_ms")
    assert (tail["moves"], tail["workloads"]) == ("ttft_p50_ms", [CELLS["chat"]])


@pytest.mark.parametrize("row", READ_SINCE_PR59, ids=lambda row: row[0])
def test_every_pair_read_since_pr59_is_still_read_with_its_fields(row):
    """No cell loses a reader, and no reader's unit, direction, source,
    layer or end-to-end metric changes under a cell that reads it."""
    entry, *fields, cells = row
    for cell in cells.split():
        assert READ.get((_reader_file(entry), CELLS[cell])) == [
            tuple(fields)], (entry, cell)


def test_no_two_entries_share_a_reader_and_the_metric_it_moves():
    """One entry a (reader file, ``moves``): a further cell joins the
    entry's ``workloads`` list, which costs none of the 128 places. Every
    entry has such a list (a metric without one is read in every cell
    that reports what it moves, those of later PRs too)."""
    keys = [(_reader_file(m["name"]), m["moves"]) for m in MAN["per_layer"]]
    assert len(keys) == len(set(keys))
    assert all(m.get("workloads") for m in MAN["per_layer"])
    # and no cell reads one reader under two entries
    assert all(len(v) == 1 for v in READ.values())


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
