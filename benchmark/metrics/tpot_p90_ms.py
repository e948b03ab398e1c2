"""Per request: (time of the last token chunk - time of the first) /
(tokens - 1); 90th percentile over the window's completed requests with
two tokens or more. Tokens leave the engine in blocks of decode_block, so
raw gaps are zeros and block times (stream_gap_p95_ms keeps those).
host_clock."""

from benchmark.common import percentile


def reduce(run):
    vals = [(r["chunk_times"][-1] - r["first"]) * 1e3
            / (r["completion_tokens"] - 1)
            for r in run["records"]
            if r.get("done") is not None and not r.get("error")
            and r["completion_tokens"] >= 2 and len(r["chunk_times"]) >= 2]
    return percentile(vals, 90) if vals else None
