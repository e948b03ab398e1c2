"""As ``paged_decode_roofline``, with the kernel found by its name and the
live context taken from ``ctx_tokens`` and ``active`` of the dispatch span
matched to each decode execution, not from client records.
device_trace + program_span."""

from benchmark import common, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.paged_decode_roofline_traced(
        trace, run["sizes"], common.peaks(run["device"]["kind"]))
