"""Device time of the block programs in the trace over the passes THOSE
executions ran (generation by diffusion over blocks: denoise and commit
passes alike): calls of the kernel named ``paged_block_attention`` inside
them over the layers, cross-checked against ``passes`` of the
``rt/block_dispatch`` spans matched to them (benchmark/block_reduce.py):
nothing unless at least half of the executions found their span. Both sides
come from the one trace. device_trace."""

from benchmark import block_reduce, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    if trace is None:
        return None
    layers = span_reduce.attn_layers(run["sizes"])
    runs = block_reduce.executions(trace, layers)
    passes = sum(x["passes"] for x in runs)
    if not passes or 2 * len(block_reduce.matched(trace, layers)) < len(runs):
        return None
    return sum(x["end"] - x["start"] for x in runs) / 1e6 / passes
