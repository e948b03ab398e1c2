"""The paged decode kernel's share of its roofline on a LATENT pool: the
least time its calls could take (benchmark/costs_latent.py: every slot's
live rows once, a row being key and value at once; the queries and
outputs; the scores' and the weighted sums' operations; over peaks.json)
over the time the calls took. The kernel is found by its name
``paged_decode_attention`` and the live context taken from ``ctx_tokens``
and ``active`` of the dispatch span matched to each decode execution, one
more token a slot each step. A configuration without a latent cache (its
sizes state no ``latent_dim``) reads nothing. device_trace +
program_span."""

from benchmark import common, costs, costs_latent, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or not sz.get("latent_dim"):
        return None
    peak = common.peaks(run["device"]["kind"])
    layers = span_reduce.attn_layers(sz)
    shape = (sz["n_heads"], sz["latent_dim"], sz["value_dim"])
    need_s = took_s = 0.0
    for x, d in span_reduce.match_stream(trace, layers)["pairs"]:
        a = d["args"]
        if not (x["kind"] == "decode" == d["kind"]) \
                or x["steps"] != a["k"] or not x["kernel_ns"]:
            continue
        for step in range(a["k"]):
            ctx = a["ctx_tokens"] + a["active"] * (step + 1)
            need_s += layers * costs.roofline_s(
                costs_latent.paged_latent_flops(ctx, *shape),
                costs_latent.paged_latent_bytes(ctx, a["active"], *shape),
                peak)[0]
        took_s += x["kernel_ns"] / 1e9
    return 100.0 * need_s / took_s if took_s else None
