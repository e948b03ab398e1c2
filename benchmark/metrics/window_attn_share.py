"""Share of the decode programs' op time in ops under the ``attn_window``
scope: the reads of the layers that keep a window of their tokens (the
paged kernel from its lower edge, and what hands it its rows). A trace
without that scope reports nothing. device_trace."""

from benchmark import span_reduce, trace_reduce

SCOPE = "attn_window"


def scope_share(run, scope: str):
    """Share (%) of the decode programs' op time under ``scope``, wherever
    in an op's path it lies."""
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    spans = [(s, e) for n, s, e in trace["modules"]
             if trace_reduce.is_decode_program(n, (e - s) / 1e9)]
    total = under = 0.0
    for (_n, s, e, tf, _c), _i in span_reduce._within(
            [o for o in trace["ops"] if not o[4]], spans):
        total += e - s
        if scope in span_reduce.scope_path(tf):
            under += e - s
    return 100.0 * under / total if under else None


def reduce(run):
    return scope_share(run, SCOPE)
