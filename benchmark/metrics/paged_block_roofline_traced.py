"""The paged block-attention kernel's share of its roofline: the bytes its
calls must move (benchmark/costs_block.py: every slot's committed K and V
and the block's own, once; the block's queries and outputs) over the chip's
HBM bandwidth, over the time the calls took. The kernel is found by its
name ``paged_block_attention``; the live context is ``ctx_tokens`` and
``active`` of the ``rt/block_dispatch`` span matched to each execution, a
block's worth more a slot for each block the dispatch has committed.
device_trace + program_span."""

from benchmark import block_reduce, common, costs_block, span_reduce


def reduce(run):
    trace = span_reduce.of_run(run)
    sz = run["sizes"]
    if trace is None or "block_length" not in sz:
        return None
    layers, block = span_reduce.attn_layers(sz), sz["block_length"]
    bandwidth = common.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    need_s = took_s = 0.0
    for x, a in block_reduce.matched(trace, layers):
        for done in range(a["blocks"]):
            need_s += a["passes"] // a["blocks"] * layers \
                * costs_block.paged_block_bytes(
                    a["ctx_tokens"] + done * a["active"] * block,
                    a["active"], block, sz["n_kv_heads"], sz["head_dim"],
                    sz["n_heads"]) / bandwidth
        took_s += x["kernel_ns"] / 1e9
    return 100.0 * need_s / took_s if took_s else None
