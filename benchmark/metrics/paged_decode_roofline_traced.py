"""The paged decode kernel's share of its roofline: the bytes its calls
must move (every slot's live K and V once, benchmark/costs.py) over the
chip's HBM bandwidth, over the time the calls took. Bandwidth-bound: one
query row per slot. The kernel is found by its name and the live context
taken from ``ctx_tokens`` and ``active`` of the dispatch span matched to
each decode execution. device_trace + program_span."""

from benchmark import common, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    return span_reduce.paged_decode_roofline_traced(
        trace, run["sizes"], common.peaks(run["device"]["kind"]))
