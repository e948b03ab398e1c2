"""What the readers of a block program share (generation by diffusion over
blocks: ray_tpu/serve/llm/engine.py ``_block_impl``): its executions in a
trace and the ``rt/block_dispatch`` spans that dispatched them.

``span_reduce.KERNEL_KIND`` / ``DISPATCH_KIND`` know no block program: to
the accepted readers it is a decode execution of 0 steps with no dispatch
span. Here an execution is a ``jit__lambda`` of 0.5 ms or more that holds
calls of the kernel named ``paged_block_attention``; its passes are those
calls over the layers. A program without that kernel (the commit before it
existed) gives nothing: every function returns an empty list."""

from __future__ import annotations

from benchmark import span_reduce, trace_reduce

KERNEL = "paged_block_attention"
SPAN = "block_dispatch"


def executions(trace: dict, layers: int) -> list[dict]:
    """The block programs in the order the device ran them: start, end,
    kernel calls and their time, passes = calls / layers. One that the
    capture's start cut (calls no multiple of the layers) is left out."""
    runs = [{"start": s, "end": e, "kernel_calls": 0, "kernel_ns": 0}
            for prog, s, e in trace["modules"]
            if trace_reduce.is_decode_program(prog, (e - s) / 1e9)]
    kernels = [(s, e) for _n, s, e, tf, c in trace["ops"]
               if not c and span_reduce.kernel_of(tf) == KERNEL]
    for (s, e), i in span_reduce._within(
            kernels, [(x["start"], x["end"]) for x in runs],
            start=lambda k: k[0]):
        runs[i]["kernel_calls"] += 1
        runs[i]["kernel_ns"] += e - s
    out = [x for x in runs
           if x["kernel_calls"] and x["kernel_calls"] % layers == 0]
    for x in out:
        x["passes"] = x["kernel_calls"] // layers
    return out


def matched(trace: dict, layers: int) -> list[tuple[dict, dict]]:
    """(execution, arguments of the span that dispatched it). One ordered
    stream seen twice, ragged at the ends (span_reduce.match_stream):
    aligned by the number of leading executions without a span that leaves
    the fewest pairs whose ``passes`` differ; only pairs that agree are
    returned."""
    runs = executions(trace, layers)
    spans = [(s, a) for n, s, _e, a in trace["spans"] if n == SPAN]
    best = None
    for lead in range(0, max(1, min(len(runs), 64))):
        pairs = list(zip(runs[lead:], spans))
        if not pairs:
            break
        unfit = sum(1 for x, (_s, a) in pairs if x["passes"] != a["passes"])
        causal = all(x["start"] >= s for x, (s, _a) in pairs)
        key = (unfit, not causal, lead)
        if best is None or key < best[0]:
            best = (key, pairs)
    return [(x, a) for x, (_s, a) in (best[1] if best else [])
            if x["passes"] == a["passes"]]
