"""Share of the KV pool's pages held by live requests, mean of the
once-a-second samples of the window: 1 - free_pages / num_pages (cached
prefix pages nobody holds count as free, as the allocator counts them).
program_counter."""


def reduce(run):
    n = run["engine"]["num_pages"]
    vals = [1.0 - s["free_pages"] / n for _t, s in run["stats_samples"]]
    return 100.0 * sum(vals) / len(vals) if vals else None
