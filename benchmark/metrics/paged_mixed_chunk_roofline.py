"""The paged chunk kernel's share of its roofline in a block whose layer
kinds differ in KV heads and whose key and value rows differ in width: the
least time the calls of a prompt chunk could take (benchmark/
costs_mixed.py: per layer the larger of the pairs' operations, ``2 x pairs
x heads x (key lanes + value lanes)``, over the MXU's peak and the bytes of
the keys seen at the layer kind's KV heads, the queries and the outputs
over the HBM bandwidth; a window layer's rows see at most ``window`` keys
each) over the time the calls took. The kernel is found by its name
``paged_chunk_attention`` inside the prefill executions, a window layer's
call by the scope ``attn_window``; position and real tokens of the chunk
are ``start`` and ``tokens`` of the ``rt/chunk_prefill`` span matched to
the execution. A configuration whose sizes state no ``value_dim`` /
``window_kv_heads`` reads nothing. device_trace + program_span."""

from benchmark import common, costs, costs_mixed, costs_window, span_reduce

KERNEL = "paged_chunk_attention"
SCOPE = "attn_window"


def reduce(run):
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or costs_mixed.layer_shape(sz, False) is None:
        return None
    peak = common.peaks(run["device"]["kind"])
    layers = span_reduce.attn_layers(sz)
    pairs = [(x, d["args"]) for x, d in
             span_reduce.match_stream(trace, layers)["pairs"]
             if x["kind"] == "prefill" == d["kind"] and "start" in d["args"]]
    calls = [[] for _ in pairs]
    for (_n, s, e, tf, _c), i in span_reduce._within(
            [o for o in trace["ops"] if not o[4]
             and span_reduce.kernel_of(o[3]) == KERNEL],
            [(x["start"], x["end"]) for x, _a in pairs]):
        calls[i].append((e - s, SCOPE in span_reduce.scope_path(tf)))
    need_s = took_s = 0.0
    for (_x, a), ops in zip(pairs, calls):
        if len(ops) != layers:      # cut by the capture's edge
            continue
        for ns, ringed in ops:
            window = sz["window"] if ringed else 0
            hkv, kd, vd, heads = costs_mixed.layer_shape(sz, ringed)
            need_s += costs.roofline_s(
                costs_mixed.paged_chunk_flops(
                    costs_window.chunk_pairs(a["start"], a["tokens"], window),
                    heads, kd, vd),
                costs_mixed.paged_read_bytes(
                    costs_window.chunk_keys(a["start"], a["tokens"], window),
                    a["tokens"], hkv, kd, vd, heads), peak)[0]
            took_s += ns / 1e9
    return 100.0 * need_s / took_s if took_s else None
