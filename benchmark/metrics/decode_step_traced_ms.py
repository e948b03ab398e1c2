"""Device time of the decode programs in the trace over the decode steps
THOSE executions ran: calls of the kernel named ``paged_decode_attention``
inside them over the layers, cross-checked against the ``k`` of the
``rt/decode_dispatch`` spans matched to them (benchmark/span_reduce.py).
Both sides come from the one trace (a counter read over HTTP by another
process runs up to pipeline_depth x decode_block steps ahead of the
device). device_trace."""

from benchmark import span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.decode_step_traced_ms(
        trace, span_reduce.attn_layers(run["sizes"]))
