"""The paged decode kernel's share of its roofline in the WINDOW layers of
a block whose layer kinds differ in KV heads and whose key and value rows
differ in width: the least time the calls under the scope ``attn_window``
could take (benchmark/costs_mixed.py: ``min(context, window)`` tokens a
slot at a window layer's KV heads, K at the published key lanes and V at
the value lanes, once; the queries and outputs; over the chip's HBM
bandwidth) over the time those calls took. With a window of one page a
call moves two pages a slot and is bound by the cost of a grid step, not by
bytes: the share is expected LOW, and says how low. Found as
benchmark/metrics/paged_full_roofline_traced.py finds a full layer's calls;
the tokens inside the windows are ``window_tokens`` of the dispatch span. A
configuration whose sizes state no ``value_dim`` / ``window_kv_heads``, or a
trace without the scope, reads nothing. device_trace + program_span."""

from benchmark import common

SCOPE = "attn_window"


def reduce(run):
    window = run["sizes"].get("window", 0)
    return common.load_module(
        "metrics", "paged_full_roofline_traced").roofline(
            run, SCOPE, lambda a, step: min(
                a["window_tokens"] + a["active"] * (step + 1),
                a["active"] * window))
