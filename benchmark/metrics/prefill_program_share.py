"""Share of the device's traced span inside prefill and prefill-chunk
executions (``jit_impl``), from the split ``span_reduce.program_split``
computes for every serve trace: span = decode programs + prefill programs
+ idle. Says how much of the device a cell's prompts take, where
``prefill_traced_ms_per_ktok`` says what a thousand of their tokens cost.
A trace without a prefill execution reads nothing. device_trace."""

from benchmark import span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.prefill_program_share(
        trace, span_reduce.attn_layers(run["sizes"]))
