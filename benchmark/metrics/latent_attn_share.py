"""Share of the decode programs' op time in the paged attention kernel
(found by its name, ``paged_decode_attention``) where the pool is latent:
how much of a decode step reading the cache is. A configuration without a
latent cache (its sizes state no ``latent_dim``) reads nothing.
device_trace."""

from benchmark import span_reduce, trace_reduce

KERNEL = "paged_decode_attention"


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None or not run["sizes"].get("latent_dim"):
        return None
    spans = [(s, e) for n, s, e in trace["modules"]
             if trace_reduce.is_decode_program(n, (e - s) / 1e9)]
    total = kernel = 0.0
    for (_n, s, e, tf, _c), _i in span_reduce._within(
            [o for o in trace["ops"] if not o[4]], spans):
        total += e - s
        if span_reduce.kernel_of(tf) == KERNEL:
            kernel += e - s
    return 100.0 * kernel / total if kernel else None
