"""What the readers of a stall of the engine's host share: the engine's
counters of it in /v1/stats (``gc_pause_*``, ``dry_*``, ``host_stall_*``,
each divided by the delta of ``clock_s``, the replica's own clock at the
read, never by ``--seconds``) and the garbage collector's spans in a trace
(``rt/gc``, ray_tpu/observability/profiling.py ``_GcWatch``).

A full collection runs in whichever thread tripped the threshold and holds
the GIL, so the loop thread stands still wherever it is. The accepted
reader keeps the loop thread's line only (``span_reduce.read_xplane``);
``gc_events`` reads ``rt/gc`` from EVERY thread of the host plane.

A program without the counters or the span (the commit before they were
added) gives nothing to read: every function returns None, or an empty
list, and never raises.
"""

from __future__ import annotations

import glob
import os

from benchmark import span_reduce, trace_reduce

GC_SPAN = "gc"


# ---- counters ---------------------------------------------------------------

def counter_share(before: dict | None, after: dict | None,
                  key: str) -> float | None:
    """100 x delta ``key`` (seconds) / delta ``clock_s`` between two reads
    of /v1/stats; None where either lacks the counter or the clock."""
    if not before or not after or any(
            k not in d for d in (before, after) for k in (key, "clock_s")):
        return None
    took = after["clock_s"] - before["clock_s"]
    if took <= 0:
        return None
    return 100.0 * (after[key] - before[key]) / took


def last_untraced(run: dict) -> dict | None:
    """The last of the window's once-a-second reads that the capture has
    not touched: its time (``window.t0`` + its offset) precedes
    ``trace_marks.t_start``, and the replica's clock says it was read
    before the one that follows ``profiling_start`` (the watcher stamps a
    sample BEFORE it reads, and starts the capture between). With no
    capture in the run: the last read."""
    marks = run.get("trace_marks") or {}
    t_start, first_traced = marks.get("t_start"), marks.get("stats_start")
    t0 = run["window"]["t0"]
    best = None
    for offset, stats in run.get("stats_samples") or []:
        if t_start is not None and t0 + offset >= t_start:
            break
        if first_traced and stats.get("clock_s", 0.0) >= first_traced.get(
                "clock_s", float("inf")):
            break
        best = stats
    return best


def untraced_share(run: dict, key: str) -> float | None:
    """``counter_share`` from the window's start to ``last_untraced``."""
    return counter_share(run.get("stats_before"), last_untraced(run), key)


# ---- the collector's spans --------------------------------------------------

def gc_events(path: str) -> list[tuple[int, int, str, dict]]:
    """(start_ns, end_ns, thread, arguments) of the ``rt/gc`` events on
    every line of the host plane of one xplane file, by start."""
    name = span_reduce.SPAN_PREFIX + GC_SPAN
    for pname, raw in span_reduce._planes(path):
        if pname == "/host:CPU":
            return sorted(
                (s, s + u, thread, st)
                for thread, evs in span_reduce._read_plane(
                    raw, lambda n: n == name)
                for _n, s, u, st, _m in evs)
    return []


def _device_file(trace_dir: str) -> str | None:
    """The file ``span_reduce.read_dir`` reads: the first that holds a
    device plane (the replica's worker holds the chip)."""
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        if any(trace_reduce._DEVICE.match(pname)
               for pname, _raw in span_reduce._planes(path)):
            return path
    return None


def gc_of_run(run: dict) -> list | None:
    """The collections in the run's trace, read once; None for a run
    without a trace or a program without the watch (its /v1/stats has no
    ``gc_pause_n``: no collection in the trace and no watch are not the
    same reading)."""
    if "gc_events" not in run:
        path = _device_file(run["trace_dir"]) if run.get("trace_dir") \
            else None
        watched = "gc_pause_n" in (run.get("stats_after") or {})
        run["gc_events"] = gc_events(path) if path and watched else None
    return run["gc_events"]


def idle_gc_share(trace: dict, gcs: list) -> float:
    """Share (%) of the device's traced span in which it is idle AND a
    full collection runs on some thread of the replica."""
    gaps, t_lo, t_hi = span_reduce._gaps(trace)
    under = span_reduce._overlap_s(gaps, [(s, e) for s, e, *_ in gcs])
    return 100.0 * under / ((t_hi - t_lo) / 1e9)


def name_idle_gaps(trace: dict, gcs: list, top: int = 10) -> list[list]:
    """``span_reduce.name_idle_gaps``, and a gap more than half under a
    collection says so: ``gc_in_harvest before jit_split_key`` (the loop
    span is the one AROUND the collection: one on the loop thread's own
    line is a span there too, and is left out of the naming)."""
    around = {**trace, "spans": [sp for sp in trace["spans"]
                                 if sp[0] != GC_SPAN]}
    gaps, _lo, _hi = span_reduce._gaps(trace)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    out = []
    for (g0, g1), (name, seconds) in zip(
            longest, span_reduce.name_idle_gaps(around, top)):
        under = sum(min(e, g1) - max(s, g0) for s, e, *_ in gcs
                    if s < g1 and e > g0)
        out.append([f"gc_in_{name}" if 2 * under > g1 - g0 else name,
                    seconds])
    return out
