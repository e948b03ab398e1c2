"""The grouped expert product's share of its roofline, in the block
programs (generation by diffusion over blocks): the least time its calls
could take (benchmark/costs_routed.py as it stands: the weights of the
experts TOUCHED once a call, and its operations, over peaks.json) over the
time the ops under the ``grouped_ffn`` scope took. Calls = passes of the
traced executions (benchmark/block_reduce.py) x routed layers; rows and
experts touched a call are the window's means from the engine's counters,
counted a pass. A program without the kernel, the scope or the counters
reports nothing. device_trace + program_counter."""

from benchmark import block_reduce, common, costs, costs_routed, span_reduce

SCOPE = "grouped_ffn"
COUNTERS = ("experts_touched_total", "expert_rows_total",
            "routed_layer_steps_total")


def reduce(run):
    trace = span_reduce.of_run(run)
    a, b = run["stats_before"], run["stats_after"]
    sz = run["sizes"]
    if trace is None or any(k not in a or k not in b for k in COUNTERS) \
            or "expert_dim" not in sz:
        return None
    layer_steps = b[COUNTERS[2]] - a[COUNTERS[2]]
    runs = block_reduce.executions(trace, span_reduce.attn_layers(sz))
    if not layer_steps or not runs:
        return None
    touched = (b[COUNTERS[0]] - a[COUNTERS[0]]) / layer_steps
    rows = (b[COUNTERS[1]] - a[COUNTERS[1]]) / layer_steps
    took_s = sum((e - s) / 1e9 for (_n, s, e, _tf, _c), _i in
                 span_reduce._within(
                     [o for o in trace["ops"] if not o[4]
                      and SCOPE in span_reduce.scope_path(o[3])],
                     [(x["start"], x["end"]) for x in runs]))
    calls = sum(x["passes"] for x in runs) * (sz["n_layers"] - sz["n_dense"])
    if not took_s or not calls:
        return None
    need_s, _bound = costs.roofline_s(
        costs_routed.grouped_ffn_flops(rows, sz["dim"], sz["expert_dim"]),
        costs_routed.grouped_ffn_bytes(rows, touched, sz["dim"],
                                       sz["expert_dim"]),
        common.peaks(run["device"]["kind"]))
    return 100.0 * calls * need_s / took_s
