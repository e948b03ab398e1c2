"""Gaps between consecutive SSE token chunks of one stream, on the
client's clock, 95th percentile over all streams: what the proxy and the
engine's block harvesting do to the rhythm a user sees."""

from benchmark.common import percentile


def reduce(run):
    gaps = [(b - a) * 1e3 for r in run["records"]
            for a, b in zip(r.get("chunk_times", []),
                            r.get("chunk_times", [])[1:])]
    return percentile(gaps, 95) if gaps else None
