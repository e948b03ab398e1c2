"""Share of the decode programs' op time in ops under the ``shared_expert``
scope: the SwiGLU every token takes beside its routed experts. A trace
without that scope reports nothing. device_trace."""

from benchmark import span_reduce, trace_reduce

SCOPE = "shared_expert"


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    by = span_reduce.device_by_scope(
        trace, trace_reduce.is_decode_program, run["family"].MODEL_SCOPES)
    if not by.get(SCOPE):
        return None
    return 100.0 * by[SCOPE] / sum(by.values())
