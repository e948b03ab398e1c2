"""Device time of the prefill and prefill-chunk executions in the trace per
thousand prompt tokens, the tokens being the ``tokens`` of the
``rt/prefill`` / ``rt/chunk_prefill`` spans that dispatched those very
executions (not tokens picked by the client's clock).
device_trace + program_span."""

from benchmark import span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.prefill_traced_ms_per_ktok(
        trace, span_reduce.attn_layers(run["sizes"]))
