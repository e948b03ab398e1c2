"""Share of the decode programs' op time in ops under the ``q_proj``,
``kv_latent`` and ``absorb`` scopes: what a latent-attention mixer costs
around the kernel (the two low-rank query projections, the latent row's
projection, norm, rotation and token write, and the two per-head products
that absorb the key and value expansions). A trace without the ``absorb``
scope reports nothing. device_trace."""

from benchmark import span_reduce, trace_reduce

SCOPES = ("q_proj", "kv_latent", "absorb")


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    by = span_reduce.device_by_scope(
        trace, trace_reduce.is_decode_program, run["family"].MODEL_SCOPES)
    if not by.get("absorb"):
        return None
    return 100.0 * sum(by.get(k, 0.0) for k in SCOPES) / sum(by.values())
