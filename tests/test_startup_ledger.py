"""The process's start-up ledger (observability/profiling.py ``startup()``):
stages from the process's creation to ready, every first dispatch split
into tracing, lowering and compile-or-load with its cache hit, what
compiled under no scope, the thread that built the engine. Tiny Llama on
the CPU; the cold / warm pair runs in two fresh subprocesses on one
persistent cache of its own."""

import json
import os
import subprocess
import sys
import threading

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("trace_s", "lower_s", "compile_s", "load_s")
# a stage is stamped where its work happens: what lies between two (the
# tokenizer, the allocators) is milliseconds once the modules the
# constructor imports are loaded (`_START` loads them first); the slack
# is for a loaded test machine
TILE_SLACK_S = 3.0

_START = """
import json, sys, threading
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from ray_tpu.models import llama
from ray_tpu.serve.llm import LLMConfig
from ray_tpu.serve.llm.llm_server import LLMServer
# (what the engine's constructor imports between two stages, before the
# start is timed: under a loaded test machine pallas alone takes seconds)
import ray_tpu.ops.paged_attention, ray_tpu.serve.llm.kv_cache
import ray_tpu.serve.llm.kv_tier, ray_tpu.parallel.expert

cfg = LLMConfig(model_config=llama.llama_tiny(vocab_size=512),
                max_batch_size=8, page_size=16, num_pages=64,
                max_prompt_len=64, max_seq_len=128, max_tokens=8)
box = {}
th = threading.Thread(target=lambda: box.update(srv=LLMServer(cfg)),
                      name="builder")
th.start(); th.join()
srv = box["srv"]
srv.engine.generate("hello there", max_tokens=3)
stats = srv.engine.engine_stats()
srv.engine.shutdown()
print("LEDGER " + json.dumps(
    {k: v for k, v in stats.items()
     if k.startswith("startup") or k == "decode_programs"}))
"""


def _start(cache_dir) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", _START, str(cache_dir)],
                          env=env, cwd=ROOT, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("LEDGER "))
    return json.loads(line[len("LEDGER "):])


@pytest.fixture(scope="module")
def starts(tmp_path_factory):
    """(cold, warm): the same start twice, each in a fresh process."""
    cache = tmp_path_factory.mktemp("compile_cache")
    return {"cold": _start(cache), "warm": _start(cache)}


def test_cold_start_compiles_every_program(starts):
    cold = starts["cold"]
    programs = cold["startup"]["programs"]
    assert {p["kind"] for p in programs} >= {"decode", "patch", "split_key",
                                             "prefill"}
    for p in programs:
        assert p["hit"] == 0 and p["compile_s"] > 0 and p["load_s"] == 0, p
    assert cold["startup_cache_hits"] == 0
    assert cold["startup_cache_misses"] >= len(programs)
    assert cold["startup_backend_compile_s"] > 0
    assert cold["startup_load_s"] == 0


def test_warm_start_loads_every_program(starts):
    warm = starts["warm"]
    programs = warm["startup"]["programs"]
    assert [p["sig"] for p in programs] == \
        [p["sig"] for p in starts["cold"]["startup"]["programs"]]
    for p in programs:
        assert p["hit"] == 1 and p["load_s"] > 0 and p["compile_s"] == 0, p
        assert 0 < p["retrieve_s"] <= p["load_s"]
    assert warm["startup_cache_misses"] == 0
    assert warm["startup_cache_hits"] == starts["cold"]["startup_cache_misses"]
    assert warm["startup_backend_compile_s"] == 0
    un = warm["startup"]["unscoped"]
    assert un["misses"] == 0 and un["hits"] == un["n"] > 0


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_the_ledger_lists_one_decode_program_a_width(starts, which):
    """ISSUE 58: the steps of a dispatch are an operand of the decode
    program, so a start warms one a bucket width (4 and 8 for eight slots),
    and the request's decode (k = 1, then what the lead picks) adds none."""
    st = starts[which]
    decode = [p for p in st["startup"]["programs"] if p["kind"] == "decode"]
    assert [p["sig"] for p in decode] == [["decode", 4], ["decode", 8]]
    assert all(p["mid_traffic"] == 0 for p in decode)
    assert st["decode_programs"] == 2


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_parts_never_exceed_the_wall(starts, which):
    st = starts[which]
    for p in st["startup"]["programs"]:
        parts = sum(p[k] for k in PARTS)
        assert parts <= p["wall_s"] + 1e-4, p
        assert p["rest_s"] >= 0
        assert abs(p["wall_s"] - parts - p["rest_s"]) < 1e-4, p
        assert p["trace_s"] > 0 and p["lower_s"] > 0
        assert p["mid_traffic"] == (p["kind"] == "prefill")
    assert st["startup_programs"] == len(st["startup"]["programs"])
    assert st["startup_trace_s"] > 0 and st["startup_lower_s"] > 0


@pytest.mark.parametrize("which", ["cold", "warm"])
def test_stages_are_ordered_and_tile_the_start(starts, which):
    from ray_tpu.observability import profiling

    su = starts[which]["startup"]
    stages = su["stages"]
    names = [n for n, _s, _d in stages]
    assert set(names) <= set(profiling.STARTUP_STAGES)
    # an engine built outside a worker: no worker_boot, no actor_wait
    assert names == ["backend", "weights", "serve_form", "pool", "pool",
                     "warm_decode", "ready"]
    end = su["created"]
    for _name, start, seconds in stages:
        assert start >= end - 1e-6 and seconds >= 0      # no overlap
        end = start + seconds
    assert stages[-1][1] == su["ready"] and stages[-1][2] == 0
    first = stages[0][1]
    covered = sum(d for _n, _s, d in stages)
    gaps = (su["ready"] - first) - covered
    assert 0 <= gaps <= TILE_SLACK_S, (gaps, stages)
    assert su["created"] < first
    assert starts[which]["startup_s"] == pytest.approx(
        su["ready"] - su["created"], abs=2e-3)
    # every program of the start-up lies after created, the warmed ones
    # inside warm_decode
    warm = next(s for s in stages if s[0] == "warm_decode")
    for p in su["programs"]:
        if not p["mid_traffic"]:
            assert warm[1] <= p["t"] and \
                p["t"] + p["wall_s"] <= warm[1] + warm[2] + 1e-3


def test_built_on_names_the_constructing_thread(starts):
    assert starts["cold"]["startup"]["built_on"] == ["builder", False]
    for p in starts["cold"]["startup"]["programs"]:
        assert p["thread"] == ("llm-engine" if p["mid_traffic"]
                               else "builder")


# ---- in this process ---------------------------------------------------


@pytest.fixture
def led(monkeypatch):
    """The process's ledger, listening, with room for this test's records
    (a test worker that lived long may have filled `MAX_PROGRAMS`)."""
    from ray_tpu.observability import profiling

    ledger = profiling.startup()
    ledger.listen()
    monkeypatch.setattr(ledger, "MAX_PROGRAMS", len(ledger.programs) + 64)
    return ledger


def _fresh_jit():
    """A jitted function no process has traced: a closure of its own."""
    import jax

    return jax.jit(lambda x: x * 3 + 1)


def test_unscoped_jit_lands_in_unscoped_and_not_in_another_threads_scope(led):
    import jax.numpy as jnp

    from ray_tpu.observability import profiling

    x = jnp.arange(8)                  # (its own fill compiles here)
    before = led.unscoped()
    _fresh_jit()(x).block_until_ready()
    after = led.unscoped()
    assert after["n"] == before["n"] + 1
    assert after["hits"] + after["misses"] == after["n"]
    # by name too, while this thread's record has room for the name (a
    # test worker that lived long holds 64 functions already)
    mine = led.loose().names
    if "jit(<lambda>)" in mine or len(mine) < 64:
        assert after["names"].get("jit(<lambda>)", 0) == \
            before["names"].get("jit(<lambda>)", 0) + 1
    assert after["trace_s"] > before["trace_s"]
    assert after["lower_s"] > before["lower_s"]

    # a scope open on THIS thread takes nothing of what another compiles
    prof = profiling.EngineProfiler()
    n0 = len(led.programs)
    with prof.compile_scope("test", ("test", "other_thread")):
        th = threading.Thread(
            target=lambda: _fresh_jit()(x).block_until_ready())
        th.start()
        th.join()
    rec = led.programs[-1]
    assert len(led.programs) == n0 + 1 and rec["sig"] == ["test",
                                                           "other_thread"]
    assert all(rec[k] == 0 for k in PARTS) and rec["hit"] == 0
    assert led.unscoped()["n"] == after["n"] + 1
    # and what it compiles itself is its own, not unscoped
    with prof.compile_scope("test", ("test", "own")):
        _fresh_jit()(x).block_until_ready()
    rec = led.programs[-1]
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["compile_s"] + rec["load_s"] > 0
    assert led.unscoped()["n"] == after["n"] + 1


def test_unscoped_names_are_bounded_and_known_names_keep_counting():
    from ray_tpu.observability import profiling

    parts = profiling._Parts(named=True)
    for i in range(70):
        parts.add("backend", 0.0, f"jit(f{i})")
    assert len(parts.names) == 64 and parts.misses == 70
    parts.add("backend", 0.0, "jit(f3)")           # known: counted
    parts.add("backend", 0.0, "jit(f69)")          # the 65th: not named
    assert parts.names["jit(f3)"] == 2 and "jit(f69)" not in parts.names
    assert parts.misses == 72 and parts.hits == 0


def test_two_engines_register_the_listeners_once():
    from jax._src import monitoring

    from ray_tpu.models import llama
    from ray_tpu.observability import profiling
    from ray_tpu.serve.llm import LLMConfig, LLMEngine

    cfg = LLMConfig(model_config=llama.llama_tiny(vocab_size=512),
                    max_batch_size=2, page_size=16, num_pages=16,
                    max_prompt_len=32, max_seq_len=64, warmup_compile=False)
    engines = [LLMEngine(cfg), LLMEngine(cfg)]
    assert monitoring.get_event_duration_listeners().count(
        profiling._on_duration) == 1
    assert monitoring.get_event_listeners().count(profiling._on_event) == 1
    # one ledger a process: both report the same object
    a, b = (e.engine_stats()["startup"] for e in engines)
    assert a is b and a["programs"] is profiling.startup().programs
    assert a["built_on"] == [threading.current_thread().name,
                             threading.current_thread()
                             is threading.main_thread()]


def test_second_dispatch_of_a_signature_adds_nothing(led):
    import jax.numpy as jnp

    from ray_tpu.observability import profiling

    prof = profiling.EngineProfiler()
    fn, x = _fresh_jit(), jnp.arange(4)
    with prof.compile_scope("test", ("test", "twice")):
        fn(x)
    n, events, seconds = len(led.programs), prof.compile_events, prof.compile_s
    totals, un = led.totals(), led.unscoped()
    scope = prof.compile_scope("test", ("test", "twice"))
    assert scope is profiling._NOOP
    with scope:
        fn(x).block_until_ready()
    assert len(led.programs) == n and prof.compile_events == events
    assert prof.compile_s == seconds
    # a call of a compiled program fires none of jax's events
    assert led.totals() == totals and led.unscoped() == un


def test_first_dispatch_is_a_span_under_a_capture(tmp_path):
    import jax.numpy as jnp

    from ray_tpu.observability import profiling
    from test_profiling import _host_spans

    profiling.startup().listen()
    prof = profiling.EngineProfiler()
    x = jnp.arange(16)
    profiling.start_capture(str(tmp_path))
    try:
        with prof.span("prefill", bucket=16), \
                prof.compile_scope("prefill", ("prefill", 16)):
            _fresh_jit()(x).block_until_ready()
    finally:
        profiling.stop_capture()
    spans = {n: (s, e, args) for n, s, e, args, _l in
             _host_spans(str(tmp_path))}
    s, e, args = spans["rt/compile"]
    ps, pe, _ = spans["rt/prefill"]
    assert ps <= s and e <= pe                 # the innermost span
    assert args["kind"] == "prefill" and args["sig"] == "('prefill', 16)"
    assert float(args["trace_s"]) > 0 and float(args["lower_s"]) > 0
    assert int(args["hit"]) in (0, 1)
    assert ("load_s" in args) == bool(int(args["hit"]))
    assert ("compile_s" in args) != ("load_s" in args)


def test_stage_names_and_totals_are_the_documented_ones():
    from ray_tpu.observability import profiling

    assert profiling.STARTUP_STAGES == (
        "worker_boot", "actor_wait", "backend", "weights", "serve_form",
        "pool", "warm_decode", "ready")
    led = profiling.startup()
    assert tuple(led.totals()) == profiling.STARTUP_TOTALS
    assert led.created <= led.view["created"] + 1e-6 < \
        __import__("time").monotonic()
