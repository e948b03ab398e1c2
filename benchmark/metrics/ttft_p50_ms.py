"""Time from when a request was DUE to its first streamed token, the
MEDIAN over every request of the window (nearest rank). A request that
failed or never finished counts with the time it was given up at.
The window's tail, its 90th percentile, is ONE order statistic of a
hundred times that each carry up to a decode step of waiting: it is
read per layer as first_token_p90_ms (PERF.md section 2). host_clock."""

from benchmark.common import percentile


def reduce(run):
    vals = []
    for r in run["records"]:
        if r.get("abandoned"):
            continue
        end = r["first"] if r.get("first") is not None \
            else run["window"]["t1"] + 60.0
        vals.append((end - r["due"]) * 1e3)
    return percentile(vals, 50) if vals else None
