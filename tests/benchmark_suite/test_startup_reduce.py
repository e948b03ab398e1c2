"""benchmark/startup_reduce.py and the five readers of a replica's start-up
ledger (setup_programs_share, setup_load_ms_per_program,
setup_lower_ms_per_program, setup_cache_misses, setup_untraced_share): on a
run dictionary built by hand (every number below is a hand count), and on
a program without the ledger (the parent commit: every reader returns
None, none raises). The ledger itself: tests/test_startup_ledger.py."""

import pytest

from benchmark import common, startup_reduce

READERS = ("setup_programs_share", "setup_load_ms_per_program",
           "setup_lower_ms_per_program", "setup_cache_misses",
           "setup_untraced_share")
SERVE_CELLS = [w["name"] for w in common.manifest()["workloads"]
               if w["name"] != "mistral7b-train-fsdp4"]
UNITS = {"setup_programs_share": "%", "setup_cache_misses": "count",
         "setup_untraced_share": "%", "setup_load_ms_per_program": "ms",
         "setup_lower_ms_per_program": "ms"}
T0 = 1000.0


def _program(t, wall, trace, lower, compile_s=0.0, load=0.0, kind="decode"):
    return {"kind": kind, "sig": [kind, t], "t": t, "wall_s": wall,
            "trace_s": trace, "lower_s": lower, "compile_s": compile_s,
            "load_s": load, "retrieve_s": load / 2,
            "rest_s": wall - trace - lower - compile_s - load,
            "hit": int(load > 0 and not compile_s), "thread": "task-exec",
            "mid_traffic": 0}


def _ledger():
    """A start of 100 s that the benchmark began at 900: the worker was
    created at 910; stages cover 910-916, 918-950 and, as warm_decode,
    950-970; ready at 970. Four programs before t0: two warmed inside
    warm_decode (one loaded, one compiled), two of warm-up requests after
    ready at 972-975 and 980-982 (loaded); one after t0."""
    return {
        "created": 910.0, "ready": 970.0, "built_on": ["task-exec", False],
        "stages": [["worker_boot", 910.0, 4.0], ["actor_wait", 914.0, 2.0],
                   ["backend", 918.0, 2.0], ["weights", 920.0, 20.0],
                   ["serve_form", 940.0, 6.0], ["pool", 946.0, 4.0],
                   ["warm_decode", 950.0, 20.0], ["ready", 970.0, 0.0]],
        "programs": [
            _program(950.0, 8.0, 1.0, 3.0, load=2.0),
            _program(958.0, 12.0, 1.0, 3.0, compile_s=7.0),
            _program(972.0, 3.0, 0.5, 1.5, load=1.0, kind="prefill"),
            _program(980.0, 2.0, 0.5, 0.5, load=0.6, kind="chunk"),
            _program(1003.0, 5.0, 1.0, 1.0, compile_s=3.0, kind="prefill")],
        "unscoped": {"trace_s": 0.3, "lower_s": 0.9, "compile_s": 1.1,
                     "load_s": 0.4, "retrieve_s": 0.2, "hits": 29,
                     "misses": 2, "n": 31, "names": {"jit(_normal)": 31}}}


def _run(startup=..., cell="mistral7b-serve-chat", setup_s=100.0):
    before = {"clock_s": T0, "compile_events": 4}
    if startup is not ...:
        before["startup"] = startup
    return {"cell": cell, "setup_s": setup_s, "report": {"rehearsal": False},
            "window": {"t0": T0, "t1": T0 + 51.0, "seconds": 51.0},
            "stats_before": before, "stats_after": dict(before)}


def _read(name, run):
    return common.load_module("metrics", name).reduce(run)


def test_known_answers_in_the_open_loop_cell():
    """chat has no ramp: of [900, 1000] the stages cover 910-916 and
    918-970 (58 s), the two later programs 972-975 and 980-982 (5 s), the
    window's lead 0.05 s: 36.95 s are under nothing."""
    run = _run(_ledger())
    assert _read("setup_programs_share", run) == pytest.approx(25.0)
    assert _read("setup_load_ms_per_program", run) == pytest.approx(1200.0)
    assert _read("setup_lower_ms_per_program", run) == pytest.approx(2750.0)
    assert _read("setup_cache_misses", run) == 3.0       # 1 program + 2
    assert _read("setup_untraced_share", run) == pytest.approx(36.95)
    laid = startup_reduce.lay(run)
    assert laid["begin"] == 900.0 and len(laid["programs"]) == 4
    assert laid["untraced_before_worker_s"] == pytest.approx(10.0)
    assert laid["untraced_between_stages_s"] == pytest.approx(2.0)
    assert laid["untraced_after_ready_s"] == pytest.approx(24.95)
    line = startup_reduce.detail(run)
    assert "before the worker 10.00" in line and "misses=2" in line
    assert "warm_decode=20.00" in line and "['task-exec', False]" in line


def test_the_ramp_is_not_untraced():
    """peak's closed loop runs 12 s before its window: 987.95-1000 is the
    ramp's (12.05 s), so 24.95 s after ready become 12.95."""
    run = _run(_ledger(), cell="mistral7b-serve-peak")
    assert startup_reduce.ramp_s(run) == 12.0
    assert _read("setup_untraced_share", run) == pytest.approx(24.95)
    assert startup_reduce.lay(run)["untraced_after_ready_s"] == \
        pytest.approx(12.95)
    # the other four do not look at the ramp
    assert _read("setup_programs_share", run) == pytest.approx(25.0)
    rehearsal = dict(run, report={"rehearsal": True})
    assert startup_reduce.ramp_s(rehearsal) == 1.0


def test_programs_after_t0_are_excluded():
    led = _ledger()
    led["programs"] = [p for p in led["programs"] if p["t"] > T0]
    run = _run(led)
    assert _read("setup_programs_share", run) == 0.0
    assert _read("setup_cache_misses", run) == 2.0       # unscoped alone
    assert _read("setup_load_ms_per_program", run) is None
    assert _read("setup_lower_ms_per_program", run) is None


def test_a_warm_start_reads_no_miss_and_a_cold_one_no_load():
    led = _ledger()
    led["unscoped"]["misses"] = 0
    led["programs"] = [p for p in led["programs"] if p["hit"]]
    assert _read("setup_cache_misses", _run(led)) == 0.0
    led = _ledger()
    led["programs"] = [p for p in led["programs"] if not p["hit"]]
    assert _read("setup_load_ms_per_program", _run(led)) is None
    assert _read("setup_cache_misses", _run(led)) == 3.0


def test_a_ledger_longer_than_the_benchmarks_line_is_cut_to_it():
    """A replica whose process is older than the benchmark's (setup_s
    shorter than the ledger): nothing before ``t0 - setup_s`` counts."""
    run = _run(_ledger(), setup_s=40.0)          # begins at 960
    laid = startup_reduce.lay(run)
    assert laid["untraced_before_worker_s"] == 0.0
    # 960-970 warm_decode, 972-975, 980-982, the lead: 24.95 of 40 left
    assert _read("setup_untraced_share", run) == pytest.approx(
        100 * 24.95 / 40)


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("before", [
    ..., None, {}, {"created": 1.0}, {"programs": [], "created": None}])
def test_a_program_without_the_ledger_reads_nothing(name, before):
    run = _run(before)
    assert _read(name, run) is None
    run["stats_before"] = None
    assert _read(name, run) is None
    assert startup_reduce.detail(run) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_has_a_file_and_says_where_its_number_comes_from(name):
    mod = common.load_module("metrics", name)
    assert callable(mod.reduce)
    assert mod.__doc__.rstrip().endswith(
        ("program_span.", "program_counter."))


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_every_serve_cell_lists_the_ledgers_readers(cell):
    """The manifest's entries (all five since PR 59, which made room: one
    entry a reader): each moves ``setup_s``, under the layer "engine
    start-up", read in every serve cell and not in the train cell, whose
    start is the benchmark's own code."""
    man = common.manifest()
    mine = [m for m in common.cell_metrics(man, cell, "per_layer")
            if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == [
        "setup_programs_share", "setup_cache_misses", "setup_untraced_share",
        "setup_load_ms_per_program", "setup_lower_ms_per_program"]
    for m in mine:
        assert m["layer"] == "engine start-up" and m["better"] == "lower"
        assert m["workloads"] == SERVE_CELLS
        assert m["source"] == ("program_counter" if m["name"]
                               == "setup_cache_misses" else "program_span")
        assert m["unit"] == UNITS[m["name"]]
    assert len(man["per_layer"]) <= 128
    assert not [m for m in common.cell_metrics(
        man, "mistral7b-train-fsdp4", "per_layer") if m["moves"] == "setup_s"]
