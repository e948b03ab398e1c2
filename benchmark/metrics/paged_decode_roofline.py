"""The paged decode kernel's share of its roofline: the bytes one call
must move (every slot's live K and V once, benchmark/costs.py) over the
chip's HBM bandwidth, over the kernel's mean time per call in the traced
window. Bandwidth-bound: one query row per slot. The live context is an
estimate from the client's records (prompt tokens plus decode_block per
chunk received) at the middle of the traced window. device_trace."""

from benchmark import common, costs, trace_reduce


def live_context(run, at: float) -> list[int]:
    block = run["engine"]["decode_block"]
    out = []
    for r in run["records"]:
        end = r.get("done") or (r["chunk_times"][-1] if r.get("chunk_times")
                                else None)
        if r.get("first") is None or end is None or not r["first"] <= at <= end:
            continue
        got = sum(1 for c in r["chunk_times"] if c <= at)
        out.append((r.get("prompt_tokens") or r["prompt_tokens_asked"])
                   + min(r["max_tokens"], 1 + block * max(0, got - 1)))
    return out


def reduce(run):
    t, marks = run.get("trace"), run.get("trace_marks") or {}
    if not t or marks.get("t_start") is None:
        return None
    calls, seconds = trace_reduce.kernel_time_within(
        t, trace_reduce.is_decode_program)
    ctx = live_context(run, 0.5 * (marks["t_start"] + marks["t_stop"]))
    if not calls or not ctx:
        return None
    sz = run["sizes"]
    need = costs.paged_decode_bytes(
        ctx, sz["n_kv_heads"], sz["dim"] // sz["n_heads"], sz["n_heads"])
    peak = common.peaks(run["device"]["kind"])
    return 100.0 * (need / peak["hbm_bytes_per_s"]) / (seconds / calls)
