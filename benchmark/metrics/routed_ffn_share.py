"""Share of the decode programs' op time in ops under the ``router`` and
``experts`` scopes: how much of a decode step the routed feed-forward is.
A trace without those scopes reports nothing. device_trace."""

from benchmark import span_reduce, trace_reduce

SCOPES = ("router", "experts")


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    by = span_reduce.device_by_scope(
        trace, trace_reduce.is_decode_program, run["family"].MODEL_SCOPES)
    routed = sum(by.get(k, 0.0) for k in SCOPES)
    return 100.0 * routed / sum(by.values()) if routed else None
