"""Engine-side wait from submit to admission (``queue_wait_s`` of the
response's ray_tpu metadata), 95th percentile. program_span."""

from benchmark.common import percentile


def reduce(run):
    vals = [r["engine"]["queue_wait_s"] * 1e3 for r in run["records"]
            if (r.get("engine") or {}).get("queue_wait_s") is not None]
    return percentile(vals, 95) if vals else None
