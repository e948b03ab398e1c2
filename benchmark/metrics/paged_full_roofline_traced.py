"""The paged decode kernel's share of its roofline in the FULL layers of a
block whose layer kinds differ in KV heads and whose key and value rows
differ in width: the least time the calls under the scope ``attn_full``
could take (benchmark/costs_mixed.py: every slot's whole context at a full
layer's KV heads, K at the published key lanes and V at the value lanes,
once; the queries and outputs; over the chip's HBM bandwidth) over the time
those calls took. The kernel is found by its name
``paged_decode_attention`` inside the decode executions, a full layer's
call by the scope; the live context is ``ctx_tokens`` and ``active`` of the
dispatch span matched to the execution, one more token a slot each step. A
configuration whose sizes state no ``value_dim`` / ``window_kv_heads``, or a
trace without the scope, reads nothing. device_trace + program_span."""

from benchmark import common, costs_mixed, span_reduce

KERNEL = "paged_decode_attention"
SCOPE = "attn_full"


def decode_calls(run, scope: str):
    """[(dispatch arguments, [ns of each call under ``scope``])] of the
    decode executions whose calls the capture holds whole, and the layers
    of that kind; None without a trace or the sizes."""
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or costs_mixed.layer_shape(sz, False) is None:
        return None
    layers = span_reduce.attn_layers(sz)
    ringed = sz["window_layers"]
    pairs = [(x, d["args"]) for x, d in
             span_reduce.match_stream(trace, layers)["pairs"]
             if x["kind"] == "decode" == d["kind"]
             and x["steps"] == d["args"]["k"]
             and "window_tokens" in d["args"]]
    calls = [[] for _ in pairs]
    for (_n, s, e, tf, _c), i in span_reduce._within(
            [o for o in trace["ops"] if not o[4]
             and span_reduce.kernel_of(o[3]) == KERNEL],
            [(x["start"], x["end"]) for x, _a in pairs]):
        calls[i].append((e - s, span_reduce.scope_path(tf)))
    kind = ringed if scope == "attn_window" else layers - ringed
    out = []
    for (_x, a), ops in zip(pairs, calls):
        if len(ops) != layers * a["k"]:     # cut by the capture's edge
            continue
        out.append((a, [ns for ns, path in ops if scope in path]))
    return out, kind


def roofline(run, scope: str, seen_tokens):
    """Share (%) of their roofline of the decode calls under ``scope``;
    ``seen_tokens(args, step)``: the tokens, over the slots, such a call
    reads at step ``step`` of its dispatch."""
    found = decode_calls(run, scope)
    if found is None:
        return None
    calls, layers = found
    shape = costs_mixed.layer_shape(run["sizes"], scope == "attn_window")
    bandwidth = common.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    need_s = took_s = 0.0
    for a, ns in calls:
        if len(ns) != layers * a["k"]:
            continue
        need_s += sum(layers * costs_mixed.paged_read_bytes(
            seen_tokens(a, step), a["active"], *shape)
            for step in range(a["k"])) / bandwidth
        took_s += sum(ns) / 1e9
    return 100.0 * need_s / took_s if took_s else None


def reduce(run):
    return roofline(run, SCOPE, lambda a, step:
                    a["ctx_tokens"] + a["active"] * (step + 1))
