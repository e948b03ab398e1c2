"""The paged decode kernel's share of its roofline in a block that has
WINDOW layers (its body walks each slot's live pages from a lower edge):
the least time its calls could take (benchmark/costs_window.py: a full
layer reads every slot's whole context, a window layer ``min(context,
window)`` tokens a slot, K and V once; the queries and outputs; over the
chip's HBM bandwidth) over the time the calls took. The kernel is found by
its name ``paged_decode_attention``; the live context is ``ctx_tokens``,
``window_tokens`` and ``active`` of the dispatch span matched to each
decode execution, one more token a slot each step. A configuration without
window layers (its sizes state no ``window_layers``) or a program whose
spans lack ``window_tokens`` reads nothing. device_trace + program_span."""

from benchmark import common, costs_window, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or not sz.get("window_layers"):
        return None
    bandwidth = common.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    layers = span_reduce.attn_layers(sz)
    ringed = sz["window_layers"]
    shape = (sz["n_kv_heads"], sz["head_dim"], sz["n_heads"])
    need_s = took_s = 0.0
    for x, d in span_reduce.match_stream(trace, layers)["pairs"]:
        a = d["args"]
        if not (x["kind"] == "decode" == d["kind"]) \
                or x["steps"] != a["k"] or not x["kernel_ns"] \
                or "window_tokens" not in a:
            continue
        for step in range(a["k"]):
            more = a["active"] * (step + 1)
            seen = min(a["window_tokens"] + more, a["active"] * sz["window"])
            need_s += ((layers - ringed) * costs_window.paged_read_bytes(
                a["ctx_tokens"] + more, a["active"], *shape)
                + ringed * costs_window.paged_read_bytes(
                    seen, a["active"], *shape)) / bandwidth
        took_s += x["kernel_ns"] / 1e9
    return 100.0 * need_s / took_s if took_s else None
