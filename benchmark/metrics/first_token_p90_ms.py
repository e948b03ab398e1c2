"""Time from when a request was DUE to its first streamed token, 90th
percentile over every request of the window. A request that failed or
never finished counts with the time it was given up at. The tail a
chat user feels; per layer since PR 59 (it was the end-to-end metric
ttft_p90_ms: its runs spread by about 5 % of its median, which no
admissible bound holds; PERF.md section 2). host_clock."""

from benchmark.common import percentile


def reduce(run):
    vals = []
    for r in run["records"]:
        if r.get("abandoned"):
            continue
        end = r["first"] if r.get("first") is not None \
            else run["window"]["t1"] + 60.0
        vals.append((end - r["due"]) * 1e3)
    return percentile(vals, 90) if vals else None
