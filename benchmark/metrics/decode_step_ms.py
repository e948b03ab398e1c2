"""Device time of the engine's decode programs in the traced window over
the decode steps dispatched in it (``steps`` read when the trace started
and stopped). device_trace + program_counter."""

from benchmark import trace_reduce


def reduce(run):
    t, marks = run.get("trace"), run.get("trace_marks") or {}
    if not t or not marks.get("stats_start"):
        return None
    steps = marks["stats_stop"]["steps"] - marks["stats_start"]["steps"]
    _n, seconds = trace_reduce.program_time(t, trace_reduce.is_decode_program)
    return 1e3 * seconds / steps if steps else None
