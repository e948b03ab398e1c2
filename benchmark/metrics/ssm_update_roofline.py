"""The decode update of a state-space mixer's recurrent state against its
roofline: the least time its calls could take (benchmark/costs_ssm.py: ONE
move of every live slot's float32 state, in and out, and the column's
operands, over peaks.json) over the time the device spent under the
``ssm_update`` scope, whatever implements the update there (a Pallas
kernel, or a gather, an update and a scatter). Over the decode executions
matched to a dispatch span, whose ``active`` says how many slots are live
(the lanes of a bucket width beyond them meet in a trash row and count as
lost share) and whose ``k`` how many steps ran. A configuration without
such a state (its sizes state no ``ssm_heads``) or a program without the
scope reads nothing. device_trace + program_span."""

from benchmark import common, costs, costs_ssm, span_reduce

SCOPE = "ssm_update"


def reduce(run):
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or not sz.get("ssm_heads"):
        return None
    peak = common.peaks(run["device"]["kind"])
    shape = (sz["ssm_heads"], sz["ssm_head_dim"], sz["ssm_state"])
    pairs = [(x, d) for x, d in span_reduce.match_stream(
        trace, span_reduce.attn_layers(sz))["pairs"]
        if x["kind"] == "decode" == d["kind"]
        and x["steps"] == d["args"]["k"]]
    spans = sorted((x["start"], x["end"]) for x, _d in pairs)
    took_s = sum(e - s for (_n, s, e, tf, _c), _i in span_reduce._within(
        [o for o in trace["ops"] if not o[4]], spans)
        if SCOPE in span_reduce.scope_path(tf)) / 1e9
    need_s = sum(
        d["args"]["k"] * sz["n_layers"] * costs.roofline_s(
            costs_ssm.ssm_update_flops(d["args"]["active"], *shape),
            costs_ssm.ssm_update_bytes(d["args"]["active"], *shape,
                                       sz["ssm_groups"]), peak)[0]
        for _x, d in pairs)
    return 100.0 * need_s / took_s if took_s else None
