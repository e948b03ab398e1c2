"""Output tokens streamed to the callers inside the window, per second of
window: over all the work and all the time of the window, whichever
request a token belongs to. A client sees a stream's chunk times and, at
its end, its token count (the proxy keeps token ids and per-chunk counts
to itself), so a stream's tokens are taken as evenly spread from its
first chunk to its last and the part inside the window is counted: exact
for a stream wholly inside, an interpolation for one that straddles an
edge (a slot emits one token a decode step, so the spread is even to
within a block). A stream cut at the end of the cool-down that had begun
before the window closed counts as ``max_tokens`` ending then (the
report line says how many; 0 unless the engine stalled). host_clock."""


def window_tokens(records: list[dict], t0: float, t1: float) -> float:
    total = 0.0
    for r in records:
        first = r.get("first")
        if first is None or r.get("error") or first > t1:
            continue
        if r.get("done") is not None:
            n, last = r["completion_tokens"], r["chunk_times"][-1]
        elif r.get("abandoned"):
            n, last = r["max_tokens"], r["abandoned_at"]
        else:
            continue
        if last <= first:
            total += n if first >= t0 else 0
        else:
            total += n * max(0.0, min(last, t1) - max(first, t0)) \
                / (last - first)
    return total


def reduce(run):
    w = run["window"]
    return window_tokens(run["records"], w["t0"], w["t1"]) / w["seconds"]
