"""Open loop: requests fall due on a schedule fixed by (parameters, seed),
whether or not earlier ones have finished. Independent users.

Parameters: rate_per_s; schedule_seed (lengths.py: the gaps are the
stratified quantiles of Exp(rate) in one fixed order, so the cell replays
one schedule from the point the seed chooses); prompt_tokens /
output_tokens (log-normal: median, sigma, min, max); drain_timeout_s.

Latency is timed from the DUE time, so a stall is charged to every request
it delays; how late the generator itself sent each request is reported.
"""

from __future__ import annotations

import threading
import time

from benchmark.traffic import lengths


def plan(params: dict, seed: int, seconds: float) -> dict:
    """Pure function of (params, seed, seconds)."""
    n = max(1, round(params["rate_per_s"] * seconds))
    reqs = lengths.requests_for(params, seed, n)
    gaps = lengths.ordered(lengths.exponential_gaps(
        params["rate_per_s"], n, seconds), params, seed, 3)
    t = 0.0
    for r, g in zip(reqs, gaps):
        t += g
        r["due_s"] = t
    return {"mode": "open", "requests": reqs,
            "drain_timeout_s": float(params.get("drain_timeout_s", 60))}


def drive(plan_: dict, send, t0: float, seconds: float) -> list[dict]:
    """Send every request at t0 + due_s, each on a thread of its own; wait
    for all of them (bounded by drain_timeout_s past the window). ``send``
    (request, due) -> record blocks until the stream ends."""
    records: list[dict] = []
    lock = threading.Lock()
    threads = []

    def one(req):
        rec = send(req, t0 + req["due_s"])
        with lock:
            records.append(rec)

    for req in plan_["requests"]:
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    deadline = t0 + seconds + plan_["drain_timeout_s"]
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    with lock:
        done = {r["index"] for r in records}
        for req in plan_["requests"]:
            if req["index"] not in done:
                records.append({"index": req["index"], "due": t0 + req["due_s"],
                                "error": "not finished at the drain timeout"})
        return sorted(records, key=lambda r: r["index"])
