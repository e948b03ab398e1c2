"""Performance-introspection tests (observability/profiling.py): engine
phase timers, compile-event tracking, device-memory accounting, and the
cluster-wide XProf capture path — all on the cpu backend."""

import os
import re
import time

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_start_regular(ray_start_module):
    yield ray_start_module


def _tiny_cfg(**kw):
    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMConfig

    d = dict(model_config=llama.llama_tiny(vocab_size=512),
             max_batch_size=4, page_size=16, num_pages=64,
             max_prompt_len=64, max_seq_len=128, max_tokens=8)
    d.update(kw)
    return LLMConfig(**d)


def _mk_engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_tiny_cfg(**kw))
    eng.start()
    return eng


# ---- phase timers -----------------------------------------------------


def test_phase_timers_record_after_traffic():
    eng = _mk_engine()
    try:
        out = eng.generate("the quick brown fox jumps over", max_tokens=6)
        assert out["num_generated_tokens"] >= 1
        stats = eng.engine_stats()
        # every decode path phase must have samples; verify is spec-only
        for phase in ("admit", "prefill", "decode_dispatch", "harvest"):
            p50 = stats[f"phase_{phase}_p50_ms"]
            p95 = stats[f"phase_{phase}_p95_ms"]
            assert p50 is not None and p50 >= 0.0, phase
            assert p95 is not None and p95 >= p50, phase
        assert stats["phase_verify_dispatch_p50_ms"] is None
    finally:
        eng.shutdown()


def test_phase_timers_disabled_stay_empty():
    eng = _mk_engine(profiling_enabled=False)
    try:
        eng.generate("hello world one two three", max_tokens=4)
        stats = eng.engine_stats()
        for phase in ("admit", "prefill", "chunk_prefill",
                      "decode_dispatch", "verify_dispatch", "harvest"):
            assert stats[f"phase_{phase}_p50_ms"] is None, phase
        # compile tracking is NOT gated by profiling_enabled
        assert stats["compile_events"] >= 1
    finally:
        eng.shutdown()


def test_itl_recorded_per_request():
    eng = _mk_engine()
    try:
        out = eng.generate("a b c d e f g h", max_tokens=8)
        assert out["num_generated_tokens"] >= 2
        # per-request median ITL (host record-time gaps)
        assert out["itl_s"] is not None and out["itl_s"] >= 0.0
        assert eng.engine_stats()["itl_s"] is not None
    finally:
        eng.shutdown()


# ---- compile-event tracking -------------------------------------------


def test_compile_once_and_mid_traffic_counter():
    # prefix cache off: a cache hit would route the repeat through the
    # chunked suffix-prefill path and compile a chunk program — this test
    # wants shape-for-shape repeats
    eng = _mk_engine(prefix_cache_enabled=False)
    try:
        stats0 = eng.engine_stats()
        # warmup compiles (decode/verify tiers) are NOT mid-traffic
        assert stats0["compile_events"] >= 1
        assert stats0["mid_traffic_compiles"] == 0

        prompt = "one two three four five six"
        eng.generate(prompt, max_tokens=4)
        stats1 = eng.engine_stats()
        # first prompt hits an unwarmed prefill bucket -> mid-traffic
        assert stats1["mid_traffic_compiles"] >= 1
        assert stats1["compile_s"] > 0.0

        # repeating the same shapes must not compile again
        eng.generate(prompt, max_tokens=4)
        stats2 = eng.engine_stats()
        assert stats2["compile_events"] == stats1["compile_events"]
        assert stats2["mid_traffic_compiles"] == stats1["mid_traffic_compiles"]

        # a NEW prompt bucket mid-traffic is flagged (regression guard)
        long_prompt = " ".join(["tok"] * 40)  # 159 bytes -> bucket 64
        eng.generate(long_prompt, max_tokens=4)
        stats3 = eng.engine_stats()
        assert stats3["mid_traffic_compiles"] > stats2["mid_traffic_compiles"]
        assert stats3["compile_events"] > stats2["compile_events"]
    finally:
        eng.shutdown()


# ---- device-memory accounting -----------------------------------------


def test_memory_gauges_sane():
    from ray_tpu.observability import profiling as prof

    eng = _mk_engine()
    try:
        stats = eng.engine_stats()
        assert stats["weights_bytes"] == prof.tree_bytes(eng.params)
        assert stats["kv_pool_bytes"] == prof.tree_bytes(eng.kv)
        assert stats["weights_bytes"] > 0
        assert stats["kv_pool_bytes"] > 0
        assert 0.0 <= stats["kv_page_occupancy"] <= 1.0
        eng.generate("occupy some pages please now", max_tokens=4)
        # finished requests free their pages; occupancy stays a fraction
        assert 0.0 <= eng.engine_stats()["kv_page_occupancy"] <= 1.0
    finally:
        eng.shutdown()


def test_save_device_memory_profile_local(tmp_path):
    from ray_tpu.observability import profiling as prof

    path = str(tmp_path / "mem.prof")
    out = prof.save_device_memory_profile(path)
    assert out == path
    assert os.path.getsize(path) > 0


# ---- XProf capture ----------------------------------------------------


def test_capture_round_trip_local(tmp_path):
    """start/stop produce a non-empty XPlane trace dir on cpu backend."""
    import jax.numpy as jnp

    from ray_tpu.observability import profiling as prof

    given = str(tmp_path / "xprof")
    info = prof.start_capture(given)
    # each process writes under a subdirectory of its own
    logdir = os.path.join(given, str(os.getpid()))
    assert info["logdir"] == logdir
    assert prof._capture.active
    # double-start is refused while a capture is live
    with pytest.raises(RuntimeError):
        prof.start_capture(str(tmp_path / "other"))
    (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    out = prof.stop_capture()
    assert out["logdir"] == logdir
    assert out["duration_s"] > 0.0
    assert not prof._capture.active
    # the profiler writes <logdir>/plugins/profile/<run>/...
    plugin_dir = os.path.join(logdir, "plugins", "profile")
    assert os.path.isdir(plugin_dir)
    runs = os.listdir(plugin_dir)
    assert runs and os.listdir(os.path.join(plugin_dir, runs[0]))


def test_cluster_capture_end_to_end(ray_start_regular, tmp_path):
    """state.capture_xprof drives CP -> node agent -> worker and registers
    a downloadable artifact."""
    from ray_tpu.util import state

    @ray_tpu.remote
    def burn():
        import jax.numpy as jnp
        return float((jnp.ones((32, 32)) @ jnp.ones((32, 32))).sum())

    assert ray_tpu.get(burn.remote()) > 0  # a worker exists and runs jax

    # default logdir: per-worker /tmp/ray_tpu_xprof/<ts>-<pid> (an explicit
    # shared dir would collide when several workers share a host)
    out = state.capture_xprof(duration=1.0)
    assert out["nodes"], "no nodes reached"
    arts = out["artifacts"]
    assert arts, f"no artifacts registered: {out}"
    for art in arts:
        assert art["kind"] == "xplane"
        assert art["duration_s"] > 0.0
        assert os.path.isdir(art["logdir"])

    listed = state.list_profile_artifacts()
    ids = {a["id"] for a in listed}
    assert all(a["id"] in ids for a in arts)

    # second capture works (per-process controller resets cleanly)
    out2 = state.capture_xprof(duration=0.5)
    assert out2["artifacts"]


def test_cluster_memory_profile(ray_start_regular, tmp_path):
    from ray_tpu.util import state

    @ray_tpu.remote
    def touch():
        return 1

    assert ray_tpu.get(touch.remote()) == 1
    out = state.save_device_memory_profile(
        path=str(tmp_path / "cluster-mem.prof"))
    workers = [w for n in out["nodes"].values() if isinstance(n, dict)
               for w in (n.get("workers") or {}).values()]
    assert workers
    assert any(isinstance(w, dict) and w.get("ok") for w in workers), out


def test_two_captures_in_one_directory_keep_both_files(tmp_path, monkeypatch):
    """The profiler names its file by host and second, so two workers told
    to capture into one directory used to overwrite each other's trace.
    Each process now writes under <logdir>/<pid>."""
    import glob

    import jax.numpy as jnp

    from ray_tpu.observability import profiling as prof

    given = str(tmp_path / "shared")
    for pid in (os.getpid(), os.getpid() + 1):   # a second worker
        monkeypatch.setattr(prof.os, "getpid", lambda pid=pid: pid)
        info = prof.start_capture(given)
        assert info["logdir"] == os.path.join(given, str(pid))
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        prof.stop_capture()
    files = glob.glob(os.path.join(given, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 2
    assert len({os.path.relpath(f, given).split(os.sep)[0]
                for f in files}) == 2


# ---- spans ------------------------------------------------------------


def _host_spans(logdir):
    """rt/ events of a capture: [(name, start_ns, end_ns, args, line)]."""
    import glob

    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for li, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("rt/"):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats), (plane.name, li)))
    return out


def test_span_without_capture_makes_no_annotation(monkeypatch):
    """With no capture active a span is a ring sample, a histogram
    observation and two additions: no TraceAnnotation is created."""
    import jax

    from ray_tpu.observability import profiling as prof

    def boom(*a, **kw):
        raise AssertionError("TraceAnnotation created with no capture")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert not prof._capture.active
    p = prof.EngineProfiler(enabled=True)
    for i in range(3):
        with p.span("decode_dispatch", seq=i, k=8) as sp:
            time.sleep(0.002)
            sp.set(tokens=1)          # a no-op without a capture
    st = p.phase_stats()
    assert st["phase_decode_dispatch_n"] == 3
    assert st["phase_decode_dispatch_s_total"] >= 0.006
    assert st["phase_decode_dispatch_p50_ms"] >= 2.0
    assert st["phase_harvest_n"] == 0 and st["phase_harvest_p50_ms"] is None
    # disabled and no capture: the shared no-op, nothing recorded
    off = prof.EngineProfiler(enabled=False)
    with off.span("harvest", seq=1) as sp:
        sp.set(tokens=2)
    assert off.span("harvest") is off.span("emit")
    assert off.phase_stats()["phase_harvest_n"] == 0


def test_decode_dispatch_counts_live_and_table_pages(tmp_path):
    """A decode dispatch span carries ``live_pages`` (the table pages that
    hold keys of its first step, summed over its slots) and ``table_pages``
    (active x the table's width), and ``engine_stats()`` totals both: one
    stream of 60 tokens after a prompt, pages of 16, a table of 8."""
    from ray_tpu.observability import profiling as prof

    eng = _mk_engine(max_tokens=64)
    try:
        eng.generate("warm the programs up first", max_tokens=4)
        before = eng.engine_stats()
        info = prof.start_capture(str(tmp_path / "xprof"))
        out = eng.generate("the quick brown fox jumps over", max_tokens=60)
        prof.stop_capture()
        after = eng.engine_stats()
    finally:
        eng.shutdown()
    disp = [a for n, _s, _e, a, _l in _host_spans(info["logdir"])
            if n == "rt/decode_dispatch"]
    assert disp and all(a["active"] == 1 for a in disp)
    # the first step of a block reads the context and its own token
    assert all(a["live_pages"] == -(-(a["ctx_tokens"] + 1) // 16)
               and a["table_pages"] == 128 // 16 for a in disp)
    prompt = out["num_prompt_tokens"]
    assert disp[0]["ctx_tokens"] == prompt
    assert {a["live_pages"] for a in disp} == set(
        range(-(-(prompt + 1) // 16), max(a["live_pages"] for a in disp) + 1))
    assert max(a["live_pages"] for a in disp) >= -(-(prompt + 40) // 16)
    for key, arg in (("attn_live_pages_total", "live_pages"),
                     ("attn_table_pages_total", "table_pages")):
        assert after[key] - before[key] == sum(a[arg] for a in disp)
    assert 0 < after["attn_live_pages_total"] \
        < after["attn_table_pages_total"]


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
def test_counters_and_spans_say_how_often_a_programs_tail_ran(tmp_path,
                                                              temperature):
    """ISSUE 56: ``chunk_heads_skipped`` beside ``attn_chunk_dispatches``
    (every chunk of a prompt but its last) and ``greedy_dispatches`` beside
    ``attn_decode_dispatches`` (no row of the dispatch asked for a
    temperature); ``head`` on every ``rt/chunk_prefill`` span and ``draws``
    on every ``rt/decode_dispatch`` span say the same, dispatch by
    dispatch. A prompt of 40 tokens in chunks of 16, 16 and 8."""
    from ray_tpu.observability import profiling as prof
    from ray_tpu.serve.llm import llm_server

    eng = _mk_engine(prefill_chunk=16, prefix_cache_enabled=False)
    try:
        eng.generate(list(range(1, 20)), max_tokens=4)       # the programs
        before = eng.engine_stats()
        info = prof.start_capture(str(tmp_path / "xprof"))
        eng.generate(list(range(100, 140)), max_tokens=10,
                     temperature=temperature)
        prof.stop_capture()
        after = eng.engine_stats()
    finally:
        eng.shutdown()
    moved = {k: after[k] - before[k] for k in (
        "attn_chunk_dispatches", "chunk_heads_skipped",
        "attn_decode_dispatches", "greedy_dispatches")}
    assert moved["attn_chunk_dispatches"] == 3
    assert moved["chunk_heads_skipped"] == 2
    assert moved["attn_decode_dispatches"] >= 3
    assert moved["greedy_dispatches"] == (
        0 if temperature else moved["attn_decode_dispatches"])
    spans = _host_spans(info["logdir"])
    chunks = [a for n, _s, _e, a, _l in spans if n == "rt/chunk_prefill"]
    assert [(a["last"], a["head"]) for a in chunks] == [(0, 0), (0, 0), (1, 1)]
    disp = [a for n, _s, _e, a, _l in spans if n == "rt/decode_dispatch"]
    assert len(disp) == moved["attn_decode_dispatches"]
    assert {a["draws"] for a in disp} == {int(temperature > 0)}
    assert {"chunk_heads_skipped", "greedy_dispatches"} <= set(
        llm_server._EXPORTED_STATS)


def test_capture_holds_engine_spans_with_args(tmp_path):
    """While a capture is active the loop's spans land in the profiler's
    host plane as rt/<phase> with their arguments, on one thread, nested
    under rt/loop_pass; a block's seq is the same in its dispatch and its
    harvest."""
    from ray_tpu.observability import profiling as prof

    eng = _mk_engine()
    try:
        eng.generate("warm the programs up first", max_tokens=4)
        info = prof.start_capture(str(tmp_path / "xprof"))
        eng.generate("the quick brown fox jumps over", max_tokens=8)
        eng.generate("a second request for good measure", max_tokens=8)
        time.sleep(0.12)              # a loop_wait or two
        prof.stop_capture()
    finally:
        eng.shutdown()
    # a full collection is a span too, on whichever thread ran it
    spans = [sp for sp in _host_spans(info["logdir"]) if sp[0] != "rt/gc"]
    names = {n for n, *_ in spans}
    assert {"rt/loop_pass", "rt/admit", "rt/prefill", "rt/decode_dispatch",
            "rt/patch_flush", "rt/harvest", "rt/fetch", "rt/emit",
            "rt/loop_wait"} <= names, names
    assert len({line for *_x, line in spans}) == 1   # the loop thread
    disp = [a for n, _s, _e, a, _l in spans if n == "rt/decode_dispatch"]
    assert disp and all(
        {"seq", "k", "w", "active", "ctx_tokens"} <= set(a) for a in disp)
    assert all(a["active"] >= 1 and a["ctx_tokens"] >= a["active"]
               and a["w"] >= a["active"] for a in disp)
    harvested = {a["seq"]: a["k"] for n, _s, _e, a, _l in spans
                 if n == "rt/harvest" and a["seq"] >= 0}
    matched = [a for a in disp if a["seq"] in harvested]
    assert matched and all(harvested[a["seq"]] == a["k"] for a in matched)
    pre = [a for n, _s, _e, a, _l in spans if n == "rt/prefill"]
    assert pre and all(a["tokens"] <= a["bucket"] and a["rid"] for a in pre)
    # harvest is the wait alone; fetch follows it as a SIBLING (neither
    # inside the other) with the same seq and k, before the entry's emit
    order = sorted((s, e, n, a) for n, s, e, a, _l in spans
                   if n in ("rt/harvest", "rt/fetch", "rt/emit"))
    waits = [i for i, sp in enumerate(order) if sp[2] == "rt/harvest"]
    assert waits
    for i in waits[:-1]:
        (hs, he, _n, ha), (fs, fe, fn, fa), (es, _ee, en, ea) = \
            order[i:i + 3]
        assert (fn, en) == ("rt/fetch", "rt/emit")
        assert hs < he <= fs < fe <= es
        assert fa["seq"] == ha["seq"] == ea["seq"] and fa["k"] == ha["k"]
    # every dispatch says whether it found the device with nothing
    # queued; the first one after the loop has parked never does
    dispatches = sorted((s, n, a) for n, s, _e, a, _l in spans if n in (
        "rt/prefill", "rt/chunk_prefill", "rt/decode_dispatch"))
    assert all(a["dry"] in (0, 1) for _s, _n, a in dispatches)
    parked = [e for n, _s, e, _a, _l in spans if n == "rt/loop_wait"]
    for end in parked:
        nxt = next((a for s, _n, a in dispatches if s >= end), None)
        assert nxt is None or nxt["dry"] == 0
    emit = [a for n, _s, _e, a, _l in spans if n == "rt/emit"]
    assert sum(a["tokens"] for a in emit) >= 8
    # nesting by containment: every other span lies inside a loop_pass
    passes = [(s, e) for n, s, e, _a, _l in spans if n == "rt/loop_pass"]
    first, last = min(s for s, _ in passes), max(e for _, e in passes)
    for n, s, e, _a, _l in spans:
        if n != "rt/loop_pass" and first <= s and e <= last:
            assert any(ps <= s and e <= pe for ps, pe in passes), n
    # patch_flush is a child of a dispatch
    dis = [(s, e) for n, s, e, _a, _l in spans
           if n in ("rt/decode_dispatch", "rt/verify_dispatch")]
    for n, s, e, _a, _l in spans:
        if n == "rt/patch_flush" and first <= s and e <= last:
            assert any(ds <= s and e <= de for ds, de in dis)


def test_phase_totals_monotone_and_add_up_to_the_loops_wall():
    """phase_<p>_s_total / _n only grow; over an interval the loop_pass
    delta is the loop's wall time, and the spans directly under it
    account for nearly all of it."""
    from ray_tpu.observability.profiling import PHASES

    eng = _mk_engine()
    try:
        eng.generate("get every program compiled", max_tokens=8)
        t0, a = time.perf_counter(), eng.engine_stats()
        for i in range(4):
            eng.generate(f"request number {i} of the interval", max_tokens=8)
        time.sleep(0.5)               # and an idle stretch
        t1, b = time.perf_counter(), eng.engine_stats()
    finally:
        eng.shutdown()
    for p in PHASES:
        assert b[f"phase_{p}_s_total"] >= a[f"phase_{p}_s_total"] >= 0.0, p
        assert b[f"phase_{p}_n"] >= a[f"phase_{p}_n"] >= 0, p
    delta = {p: b[f"phase_{p}_s_total"] - a[f"phase_{p}_s_total"]
             for p in PHASES}
    wall = t1 - t0
    # a pass under way at either reading (a 50 ms wait at most) is the slack
    assert abs(delta["loop_pass"] - wall) <= 0.06 + 0.02 * wall, (delta, wall)
    direct = sum(delta[p] for p in (
        "admit", "restore", "chunk_prefill", "decode_dispatch",
        "verify_dispatch", "harvest", "fetch", "emit", "kv_tier_flush",
        "loop_wait"))
    assert 0.9 * delta["loop_pass"] - 0.06 <= direct \
        <= delta["loop_pass"] + 0.06, (delta, wall)
    assert delta["loop_wait"] > 0.3 and b["phase_emit_n"] > a["phase_emit_n"]
    # one fetch an entry harvested, and the replica's own clock beside
    # the counters: the divisor of every delta
    assert b["phase_fetch_n"] - a["phase_fetch_n"] \
        == b["phase_harvest_n"] - a["phase_harvest_n"] > 0
    assert abs((b["clock_s"] - a["clock_s"]) - wall) < 0.05


# ---- stalls of the loop's host -----------------------------------------


def test_gc_watch_counts_by_generation_and_installs_once():
    import gc

    from ray_tpu.observability import profiling as prof

    first, second = prof.EngineProfiler(), prof.EngineProfiler()
    assert first._gc is second._gc is prof.watch_gc()
    assert gc.callbacks.count(prof.watch_gc()) == 1
    a = first.stall_stats()
    gc.collect()
    b = first.stall_stats()
    assert b["gc_pause_n"] == a["gc_pause_n"] + 1
    assert b["gc_pause_s_total"] > a["gc_pause_s_total"]
    assert b["gc_pause_max_ms"] >= a["gc_pause_max_ms"] > -1
    young = b["gc_young_n"]
    gc.collect(0)
    gc.collect(1)
    c = second.stall_stats()
    assert c["gc_young_n"] == young + 2
    assert c["gc_young_s_total"] > b["gc_young_s_total"]
    assert c["gc_pause_n"] == b["gc_pause_n"]      # no full one since


def test_two_engines_share_one_gc_watch_and_export_its_keys():
    import gc

    from ray_tpu.observability import profiling as prof

    one = _mk_engine(warmup_compile=False)
    two = _mk_engine(warmup_compile=False)
    try:
        assert gc.callbacks.count(prof.watch_gc()) == 1
        gc.collect()
        a, b = one.engine_stats(), two.engine_stats()
        # the process's collector, not an engine's: both report it
        assert a["gc_pause_n"] == b["gc_pause_n"] >= 1
        for key in ("gc_pause_s_total", "gc_pause_max_ms", "gc_young_n",
                    "gc_young_s_total", "host_stall_n", "host_stall_s_total",
                    "dry_dispatches_total", "dry_s_total", "clock_s"):
            assert key in a, key
        assert two.engine_stats()["clock_s"] >= b["clock_s"] >= a["clock_s"]
    finally:
        one.shutdown()
        two.shutdown()


def test_capture_holds_a_collection_of_another_thread(tmp_path):
    """A full collection run by a thread that is NOT the loop's is an
    rt/gc span with generation 2 on THAT thread's line of the host plane,
    and the benchmark's reader of every line finds it."""
    import gc
    import glob
    import threading

    from benchmark import stall_reduce
    from ray_tpu.observability import profiling as prof

    eng = _mk_engine()
    try:
        eng.generate("warm the programs up first", max_tokens=4)
        info = prof.start_capture(str(tmp_path / "xprof"))
        eng.generate("spans of the loop thread", max_tokens=6)
        th = threading.Thread(target=gc.collect, name="not-the-loop")
        th.start()
        th.join()
        gc.collect(0)                 # a young one leaves no span
        eng.generate("and some more of them", max_tokens=6)
        prof.stop_capture()
    finally:
        eng.shutdown()
    spans = _host_spans(info["logdir"])
    loop_lines = {line for n, *_x, line in spans if n == "rt/loop_pass"}
    gcs = [(a, line) for n, _s, _e, a, line in spans if n == "rt/gc"]
    assert len(loop_lines) == 1 and gcs
    assert all(a["generation"] == 2 and a["collected"] >= 0
               and a["uncollectable"] >= 0 for a, _l in gcs)
    assert any(line not in loop_lines and a["thread"] == "not-the-loop"
               for a, line in gcs)
    (path,) = glob.glob(os.path.join(info["logdir"], "**", "*.xplane.pb"),
                        recursive=True)
    found = stall_reduce.gc_events(path)
    assert len(found) == len(gcs)
    assert all(e > s and st["generation"] == 2 for s, e, _t, st in found)
    assert found == sorted(found)


def test_planted_sleep_in_emit_is_one_host_stall_and_a_dry_dispatch():
    """0.2 s of host work inside one emit: host_stall_n 1 with phase emit,
    one loop_stall journal event that says so, and the dispatch after it
    finds the device with nothing queued (dry_s_total covers the sleep)."""
    import gc

    from ray_tpu.observability import events
    from ray_tpu.observability import profiling as prof

    # no prefix reuse: the same prompt runs the same programs every time;
    # a token a dispatch: dispatches are left when the first emit is over
    eng = _mk_engine(prefix_cache_enabled=False, decode_block=1,
                     pressure_decode_block=1)
    prompt = "the request whose first emit sleeps"
    cap = []
    events.set_local_sink(cap.append)
    try:
        eng.generate(prompt, max_tokens=8)
        real, planted = eng._finish_requests, []

        def slow(finished):
            if not planted:
                planted.append(1)
                time.sleep(0.2)
            return real(finished)

        gc.collect()                  # no full collection soon after
        prof.watch_gc().stall = None
        time.sleep(0.15)              # the loop parks
        a = eng.engine_stats()
        eng._prof._stall_told = 0.0   # the one-a-second limit starts anew
        del cap[:]
        eng._finish_requests = slow
        eng.generate(prompt, max_tokens=8)
        b = eng.engine_stats()
    finally:
        events.clear_local_sink()
        eng.shutdown()
    assert planted
    assert b["host_stall_n"] - a["host_stall_n"] == 1
    assert 0.2 <= b["host_stall_s_total"] - a["host_stall_s_total"] < 0.4
    stalls = [e for e in cap if e["kind"] == "loop_stall"]
    assert len(stalls) == 1 and stalls[0]["severity"] == "WARNING"
    assert stalls[0]["reason"] == "emit"
    attrs = stalls[0]["attrs"]
    assert attrs["phase"] == "emit" and attrs["seconds"] >= 0.2
    assert attrs["gc_s"] == 0.0 and "seq" in attrs
    # the sleeping emit is followed by a dispatch (7 tokens to go) that
    # finds what was in flight done: dry, for at least the sleep
    assert b["dry_dispatches_total"] > a["dry_dispatches_total"]
    assert b["dry_s_total"] - a["dry_s_total"] >= 0.2


def test_a_stall_is_a_spans_own_time_and_the_gc_reports_through_the_loop():
    """A slow child is not counted again as its parent; waits are never
    stalls; a long full collection (any thread) is reported by the loop's
    next span as phase gc, at most one event a second."""
    from ray_tpu.observability import events
    from ray_tpu.observability import profiling as prof

    p = prof.EngineProfiler()
    watch = prof.watch_gc()
    watch.stall = None
    cap = []
    events.set_local_sink(cap.append)
    try:
        with p.span("loop_pass"):
            with p.span("admit"):
                with p.span("prefill", rid="r"):
                    time.sleep(0.06)
            with p.span("harvest", seq=3):
                time.sleep(0.06)
            with p.span("loop_wait"):
                time.sleep(0.06)
        st = p.stall_stats()
        assert st["host_stall_n"] == 1 and 0.06 <= st["host_stall_s_total"] < 0.12
        assert [e["reason"] for e in cap] == ["prefill"]
        # within a second of that event another stall is counted, not told
        with p.span("emit", seq=4):
            time.sleep(0.06)
        assert p.stall_stats()["host_stall_n"] == 2 and len(cap) == 1
        p._stall_told = 0.0
        watch.stall = (0.3, "http-handler")
        with p.span("admit"):
            pass
        assert watch.stall is None
        assert cap[-1]["reason"] == "gc" and cap[-1]["attrs"] == {
            "phase": "gc", "seq": None, "seconds": 0.3, "gc_s": 0.3,
            "thread": "http-handler"}
        assert p.stall_stats()["host_stall_n"] == 2   # the collector's own
    finally:
        events.clear_local_sink()


def test_stall_counters_ride_the_export_chain():
    """engine_stats() -> llm_server _EXPORTED_STATS (gauges) -> controller
    _ENGINE_KEYS (detailed_status, the dashboard); the controller's tuple
    is function-local, so it is checked in source."""
    import inspect

    from ray_tpu.serve import controller
    from ray_tpu.serve.llm import llm_server

    keys = {"host_stall_s_total", "host_stall_n", "gc_pause_s_total",
            "gc_pause_n", "gc_pause_max_ms", "gc_young_s_total",
            "gc_young_n", "dry_dispatches_total", "dry_s_total"}
    assert keys <= set(llm_server._EXPORTED_STATS)
    engine_keys = inspect.getsource(controller).split(
        "_ENGINE_KEYS = (", 1)[1]
    for k in keys | {"clock_s"}:
        assert f'"{k}"' in engine_keys, k


# ---- names on device work ----------------------------------------------


def _scopes_and_kernels(fn, *args):
    """(scope names in the lowered text's locations, names of the Pallas
    kernels in the traced program)."""
    import jax

    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    scopes = set()
    for loc in re.findall(r'loc\("([^"]+)"', text):
        # under autodiff a scope reads jvp(attn) or transpose(jvp(attn))
        scopes.update(re.sub(r"^(?:\w+\()+|\)+$", "", part)
                      for part in loc.split("/"))

    def kernels(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.add(eqn.params["name"])
            for v in eqn.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(j, "jaxpr", j)
                    if hasattr(inner, "eqns"):
                        kernels(inner, out)
        return out

    return scopes, kernels(jax.make_jaxpr(fn)(*args).jaxpr, set())


LAYER_SCOPES = {"embed", "norm", "attn", "kv_write", "mlp", "lm_head"}


def _paged_engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(_tiny_cfg(attention_kernel="pallas",
                               warmup_compile=False, **kw))


def test_decode_program_carries_scope_and_kernel_names():
    import jax.numpy as jnp

    eng = _paged_engine()
    idx = jnp.arange(4, dtype=jnp.int32)
    scopes, kernels = _scopes_and_kernels(
        lambda *a: eng._decode_impl(*a, 2), eng.params, eng.kv, eng._pt_dev,
        eng._sl_dev, jnp.zeros((5,), jnp.int32), eng._rng, eng._temps_dev,
        idx)
    # the decode call walks its slots' pages and writes their rows (ISSUE
    # 61): the scope ``kv_write`` holds no operation of this program
    assert LAYER_SCOPES - {"kv_write"} | {
        "decode_block", "gather_state", "decode_step", "scatter_state",
        "sample"} <= scopes
    assert "kv_write" not in scopes
    assert kernels == {"paged_decode_attention"}
    # the jitted functions keep their Python names: the benchmark's
    # accepted readers find programs as jit__lambda / jit_impl / jit_step
    assert eng._decode.__name__ == "<lambda>"
    assert eng._chunk_fn(16).__name__ == "impl"
    assert eng._prefill_fn(16).__name__ == "impl"


def test_prefill_chunk_and_verify_programs_carry_scope_and_kernel_names():
    import jax.numpy as jnp

    eng = _paged_engine(spec_decode_enabled=True, spec_draft_len=2)
    mc, ps = eng.model_cfg, eng.cfg.page_size
    table = jnp.zeros((eng.max_pages_per_seq,), jnp.int32)
    toks = jnp.zeros((1, 16), jnp.int32)
    scopes, kernels = _scopes_and_kernels(
        lambda p, kv: eng._kvc.paged_prefill_chunk(
            p, kv, table, toks, jnp.int32(16), jnp.int32(30), mc, ps,
            "pallas"), eng.params, eng.kv)
    assert LAYER_SCOPES | {"prefill_chunk"} <= scopes
    assert kernels == {"paged_chunk_attention"}
    scopes, kernels = _scopes_and_kernels(
        lambda p, kv: eng._kvc.paged_prefill(
            p, kv, table, toks, jnp.int32(12), mc, ps), eng.params, eng.kv)
    assert LAYER_SCOPES | {"prefill"} <= scopes and not kernels
    idx = jnp.arange(4, dtype=jnp.int32)
    scopes, kernels = _scopes_and_kernels(
        eng._verify_impl, eng.params, eng.kv, eng._pt_dev, eng._sl_dev,
        jnp.zeros((5,), jnp.int32), eng._rng, eng._temps_dev, idx,
        jnp.zeros((4, 2), jnp.int32))
    assert LAYER_SCOPES - {"kv_write"} | {"verify", "sample"} <= scopes
    assert "kv_write" not in scopes         # the rows ride in the kernel
    assert kernels == {"paged_verify_attention"}


def test_train_step_carries_scope_and_kernel_names():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.train import spmd

    cfg = llama.llama_tiny(attn_impl="flash", max_seq_len=32)
    mesh = spmd.make_mesh(1, devices=jax.devices()[:1])
    opt = spmd.default_optimizer()
    state, sh = spmd.sharded_create_state(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg), opt, mesh,
        llama.logical_axes(cfg))
    step = spmd.make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh), opt, mesh, sh)
    assert step.__name__ == "step"
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    scopes, kernels = _scopes_and_kernels(
        step.__wrapped__, state, batch)
    assert {"embed", "norm", "attn", "mlp", "lm_head", "loss",
            "optimizer"} <= scopes
    assert kernels == {"flash_fwd", "flash_bwd"}


# ---- README drift guard -----------------------------------------------


def test_readme_engine_stats_table_matches_live_keys():
    """Every key engine_stats() emits must be documented in README's
    engine-telemetry table, and every documented key must exist — with
    prefix cache, spec decoding, and profiling all on."""
    eng = _mk_engine(prefix_cache_enabled=True, spec_decode_enabled=True,
                     spec_draft_len=2, kv_tier_enabled=True)
    try:
        eng.generate("drift guard prompt one two three", max_tokens=6)
        live = set(eng.engine_stats().keys())
    finally:
        eng.shutdown()

    readme = open(os.path.join(os.path.dirname(__file__), "..",
                               "README.md")).read()
    section = readme.split("### Engine telemetry (`engine_stats()`)")[1]
    table = section.split("\n## ")[0]
    documented = set()
    for row in re.findall(r"^\|([^|]+)\|", table, flags=re.M):
        documented.update(re.findall(r"`([a-z0-9_]+)`", row))

    missing_docs = live - documented
    assert not missing_docs, \
        f"engine_stats keys missing from README table: {sorted(missing_docs)}"
    stale_docs = documented - live
    assert not stale_docs, \
        f"README documents keys engine_stats no longer emits: {sorted(stale_docs)}"


# ---- dashboard panel --------------------------------------------------


def test_dashboard_profiling_routes(ray_start_regular):
    import json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard

    dash = start_dashboard(port=0)
    try:
        base = f"http://127.0.0.1:{dash.port}"
        with urllib.request.urlopen(base + "/profiling", timeout=30) as r:
            assert r.status == 200
            assert b"engine profiling" in r.read()
        with urllib.request.urlopen(base + "/api/profile/artifacts",
                                    timeout=30) as r:
            assert isinstance(json.loads(r.read()), list)
        # unknown artifact id -> 404, not a crash
        try:
            urllib.request.urlopen(
                base + "/api/profile/download/nope", timeout=30)
            raised = False
        except urllib.error.HTTPError as e:
            raised = e.code == 404
        assert raised
    finally:
        dash.stop()
