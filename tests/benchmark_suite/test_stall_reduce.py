"""benchmark/stall_reduce.py and the four metrics that read a stall of the
engine's host (gc_pause_share, pipeline_dry_share, host_stall_share,
idle_gc_share): on run dictionaries and trace rows built by hand (every
number below is a hand count), and on a program without the counters or
the span (the parent commit: every reader returns None, none raises).
The reader of ``rt/gc`` from a real capture: tests/test_profiling.py."""

import pytest

from benchmark import common, span_reduce as sr, stall_reduce

US = 1000
CELLS = ("mistral7b-serve-chat", "mistral7b-serve-peak",
         "lfm2-8b-a1b-serve-decode", "sdar-30b-a3b-serve-decode")
READERS = ("gc_pause_share", "pipeline_dry_share", "host_stall_share",
           "idle_gc_share")


def _stats(clock, **counters):
    return {"clock_s": clock, "tokens_out": 0, **counters}


def _run(before, after, samples=(), marks=None, seconds=51.0):
    """A serve run as serve_cell.run returns it, as far as the readers
    look: the window starts at t0 = 1000 on the harness's clock."""
    return {"window": {"t0": 1000.0, "t1": 1000.0 + seconds,
                       "seconds": seconds},
            "stats_before": before, "stats_after": after,
            "stats_samples": list(samples), "trace_dir": None,
            "trace_marks": marks or {k: None for k in (
                "t_start", "t_stop", "stats_start", "stats_stop")}}


def _entry(reader, cell):
    """The manifest's one entry of ``reader`` that lists ``cell``."""
    [m] = [m for m in common.cell_metrics(common.manifest(), cell,
                                          "per_layer")
           if m["name"].split(".")[0] == reader]
    return m


def _metric(reader, cell):
    """The reader as run.py loads it in a run of ``cell``."""
    return common.load_module("metrics", _entry(reader, cell)["name"])


# ---- counters over the replica's own clock ----------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_gc_pause_share_divides_by_the_replicas_clock(cell):
    """A traced run's closing read comes when the capture has been written:
    64 s after the opening one, not the 51 the harness asked for. Two
    collections of 0.32 s: 1 % of 64 s (1.25 % of 51 would be wrong)."""
    run = _run(_stats(500.0, gc_pause_s_total=1.00),
               _stats(564.0, gc_pause_s_total=1.64))
    assert _metric("gc_pause_share", cell).reduce(run) \
        == pytest.approx(1.0)


@pytest.mark.parametrize("name,key", [
    ("pipeline_dry_share", "dry_s_total"),
    ("host_stall_share", "host_stall_s_total")])
@pytest.mark.parametrize("cell", CELLS)
def test_untraced_shares_stop_at_the_last_read_before_the_capture(
        name, key, cell):
    """Once-a-second reads; the capture starts 20.4 s in. The read stamped
    20.0 s was made AFTER profiling_start (the watcher stamps, starts the
    capture, then reads): the replica's clock gives it away. So the
    interval ends with the read at 19 s: 0.19 s of the counter in 19.05 s
    of the replica's clock, whatever the later reads hold."""
    samples = [(float(i), _stats(500.05 + i, **{key: 0.01 * i}))
               for i in range(20)]
    samples.append((20.0, _stats(523.0, **{key: 5.0})))     # after the start
    samples += [(20.0 + i, _stats(523.0 + i, **{key: 5.0 + i}))
                for i in range(1, 30)]
    marks = {"t_start": 1022.4, "t_stop": 1027.4,
             "stats_start": _stats(522.4, **{key: 4.9}),
             "stats_stop": _stats(527.4, **{key: 9.0})}
    run = _run(_stats(500.0, **{key: 0.0}), _stats(564.0, **{key: 40.0}),
               samples, marks)
    assert stall_reduce.last_untraced(run) is samples[19][1]
    assert _metric(name, cell).reduce(run) \
        == pytest.approx(100.0 * 0.19 / 19.05)


def test_last_untraced_by_the_harness_clock_alone_and_with_no_capture():
    samples = [(float(i), _stats(500.0 + i)) for i in range(10)]
    # reads without a clock (the parent): the harness's stamps decide
    bare = [(t, {"tokens_out": 0}) for t, _s in samples]
    marks = {"t_start": 1004.5, "t_stop": 1009.0,
             "stats_start": {"tokens_out": 0}, "stats_stop": {}}
    run = _run({}, {}, bare, marks)
    assert stall_reduce.last_untraced(run) is bare[4][1]
    # an untraced run: the last read
    run = _run(_stats(499.0), _stats(551.0), samples)
    assert stall_reduce.last_untraced(run) is samples[-1][1]
    assert stall_reduce.last_untraced(_run({}, {})) is None


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reports_nothing(name, cell):
    """The parent's /v1/stats: no clock, no counter. None, and no raise;
    so with a clock that did not move, and with no reads at all."""
    old = {"tokens_out": 7, "phase_harvest_s_total": 1.0}
    samples = [(float(i), dict(old)) for i in range(5)]
    reduce = _metric(name, cell).reduce
    assert reduce(_run(dict(old), dict(old), samples)) is None
    assert reduce(_run(None, None)) is None
    still = _stats(500.0, gc_pause_s_total=1.0, dry_s_total=1.0,
                   host_stall_s_total=1.0)
    assert reduce(_run(dict(still), dict(still), [(1.0, dict(still))])) \
        is None


def test_every_new_metric_is_listed_for_its_cell_and_resolves():
    """Since PR 59 one entry a (reader, ``moves``): chat's under the
    suffixed name (it moves ``tpot_p90_ms``), the closed loops' under the
    reader's bare name, each cell in its ``workloads`` list."""
    for base, source in (("gc_pause_share", "program_counter"),
                         ("pipeline_dry_share", "program_counter"),
                         ("host_stall_share", "program_counter"),
                         ("idle_gc_share", "device_trace")):
        for cell in CELLS:
            m = _entry(base, cell)
            chat = cell == "mistral7b-serve-chat"
            assert m["name"] == (f"{base}.chat" if chat else base)
            assert cell in m["workloads"] and m["source"] == source
            assert (m["layer"], m["better"], m["unit"]) \
                == ("engine loop", "lower", "%")
            assert m["moves"] == ("tpot_p90_ms" if chat
                                  else "serve_tokens_per_s")
            assert callable(_metric(base, cell).reduce)


# ---- the collector's spans against the device's idle gaps -------------------

def _rows():
    """The device: three programs with idle gaps of 300 us (700-1000) and
    40 us (1500-1540). The loop thread: a harvest over the first gap, an
    emit over the second."""
    rows = []
    for name, start, end in (("jit__lambda", 0, 700),
                             ("jit_split_key", 1000, 1500),
                             ("jit__lambda", 1540, 2000)):
        rows += [["op", "fusion", start * US, (end - start) * US, "", 0],
                 ["module", name, start * US, (end - start) * US, "", 0]]

    def span(name, start, end, **args):
        return ["span", name, start * US, (end - start) * US, args, 0]

    rows += [span("loop_pass", 100, 1900),
             span("decode_dispatch", 100, 300, seq=4, k=1, dry=0),
             span("harvest", 300, 1100, seq=2, k=1),
             span("fetch", 1100, 1200, seq=2, k=1),
             span("emit", 1200, 1560, seq=2),
             span("decode_dispatch", 1560, 1800, seq=5, k=1, dry=1)]
    return rows


def test_a_gap_under_another_threads_collection_is_named_for_it():
    trace = sr.from_rows(_rows())
    # an HTTP handler's thread collected from 650 to 980 us: 280 of the
    # long gap's 300 us lie under it, none of the short gap's
    gcs = [(650 * US, 980 * US, "http-handler", {"generation": 2})]
    assert sr.name_idle_gaps(trace)[:2] == [
        ["harvest before jit_split_key", pytest.approx(300e-6)],
        ["emit before jit__lambda", pytest.approx(40e-6)]]
    assert stall_reduce.name_idle_gaps(trace, gcs)[:2] == [
        ["gc_in_harvest before jit_split_key", pytest.approx(300e-6)],
        ["emit before jit__lambda", pytest.approx(40e-6)]]
    # idle under the collection: 280 us of a traced span of 2000 us
    assert stall_reduce.idle_gc_share(trace, gcs) == pytest.approx(14.0)
    # the accepted metric keeps its meaning: idle under harvest is the
    # host WAITING; only the 40 us under emit are the host's own
    assert sr.idle_host_busy_share(trace) == pytest.approx(2.0)
    # no collection in the trace is a reading of 0, not of nothing
    assert stall_reduce.idle_gc_share(trace, []) == 0.0
    assert stall_reduce.name_idle_gaps(trace, []) \
        == sr.name_idle_gaps(trace)


def test_a_collection_on_the_loop_thread_names_the_span_around_it():
    """A collection the loop thread ran is a span on its own line too
    (``gc`` inside ``emit``): the gap says gc_in_emit, and the accepted
    reader counts that idle as the host's (``gc`` is no wait)."""
    rows = _rows() + [["span", "gc", 1505 * US, 30 * US,
                       {"generation": 2}, 0]]
    trace = sr.from_rows(rows)
    gcs = [(1505 * US, 1535 * US, "engine-loop", {"generation": 2})]
    named = stall_reduce.name_idle_gaps(trace, gcs)
    assert named[1] == ["gc_in_emit before jit__lambda",
                        pytest.approx(40e-6)]
    assert named[0][0] == "harvest before jit_split_key"
    assert stall_reduce.idle_gc_share(trace, gcs) == pytest.approx(1.5)
    assert sr.idle_host_busy_share(trace) == pytest.approx(2.0)


def test_idle_gc_share_renames_the_breakdowns_gaps_and_spares_the_parents():
    trace = sr.from_rows(_rows())
    gcs = [(650 * US, 980 * US, "http-handler", {"generation": 2})]
    run = _run(_stats(1.0), _stats(2.0, gc_pause_n=3))
    run.update(span_trace=trace, gc_events=gcs,
               trace={"breakdown": {"idle_gaps": [["x", 1.0]]}})
    reduce = _metric("idle_gc_share", "sdar-30b-a3b-serve-decode").reduce
    assert reduce(run) == pytest.approx(14.0)
    assert run["trace"]["breakdown"]["idle_gaps"][0][0] \
        == "gc_in_harvest before jit_split_key"
    # a program without the watch: nothing read, the breakdown untouched
    parent = _run({"tokens_out": 0}, {"tokens_out": 9})
    parent.update(span_trace=trace, trace_dir=None,
                  trace={"breakdown": {"idle_gaps": [["x", 1.0]]}})
    assert reduce(parent) is None
    assert parent["trace"]["breakdown"]["idle_gaps"] == [["x", 1.0]]
    # a run without a trace
    assert reduce(_run(_stats(1.0), _stats(2.0, gc_pause_n=3))) is None
