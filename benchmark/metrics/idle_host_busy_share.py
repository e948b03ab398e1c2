"""Share of the device's traced span in which the device is idle AND the
engine-loop thread is inside a span other than ``loop_wait`` and
``harvest``: the idle the host's own work causes. ``device_idle_share``
minus this is idle with nothing to run, or with the host blocked on a
result. Also names the breakdown's longest idle gaps by the loop span
that covers most of each (``patch_flush before jit__lambda``).
device_trace + program_span."""

from benchmark import span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None or not trace["spans"]:
        return None
    breakdown = (run.get("trace") or {}).get("breakdown")
    if breakdown is not None:
        breakdown["idle_gaps"] = span_reduce.name_idle_gaps(trace)
    return span_reduce.idle_host_busy_share(trace)
