"""Share of the block programs' op time in ops under the ``lm_head``,
``unmask`` and ``sample`` scopes: the head over every position of every
denoise pass and the confidence over the whole vocabulary that decides
which positions a pass reveals. A trace without the ``unmask`` scope
reports nothing. device_trace."""

from benchmark import span_reduce, trace_reduce

SCOPES = ("lm_head", "unmask", "sample")


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    by = span_reduce.device_by_scope(
        trace, trace_reduce.is_decode_program, run["family"].MODEL_SCOPES)
    if not by.get("unmask"):
        return None
    return 100.0 * sum(by.get(k, 0.0) for k in SCOPES) / sum(by.values())
