"""Time from when a request was DUE to its first streamed token, 90th
percentile over every request of the window. A request that failed or
never finished counts with the time it was given up at. host_clock."""

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
