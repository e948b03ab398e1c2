"""Closed loop: ``clients`` callers, each sending its next request when the
previous one has completed. Callers that wait for a reply.

Parameters: clients; ramp_s (the loop runs this long before the window
opens, as set-up) and cooldown_s (and this long after it closes), so that
a request in flight at either edge of the window starts and finishes under
the window's own load; pool (requests in the schedule the clients draw
from, in order); schedule_seed / prompt_tokens / output_tokens as in
open_loop; deal_block (optional: lengths dealt in blocks of that many
requests, each holding the stratified quantiles once, so that a loop that
draws a part of its pool draws the same mix from any starting point).

The window's tokens are those streamed inside it (metrics/
serve_tokens_per_s.py); the loop around it only keeps the load steady.
"""

from __future__ import annotations

import threading
import time

from benchmark.traffic import lengths


def plan(params: dict, seed: int, seconds: float) -> dict:
    reqs = lengths.requests_for(params, seed, int(params["pool"]))
    return {"mode": "closed", "requests": reqs,
            "clients": int(params["clients"]),
            "ramp_s": float(params["ramp_s"]),
            "cooldown_s": float(params["cooldown_s"])}


def drive(plan_: dict, send, t0: float, seconds: float) -> list[dict]:
    """Clients start at t0 - ramp_s (the caller passes t0 = now + ramp_s)
    and stop taking new requests at t0 + seconds + cooldown_s; streams
    still open then are closed by ``send`` seeing the stop time and
    recorded as abandoned, not failed."""
    records: list[dict] = []
    lock = threading.Lock()
    queue = list(reversed(plan_["requests"]))
    stop_at = t0 + seconds + plan_["cooldown_s"]

    def client():
        while time.monotonic() < stop_at:
            with lock:
                if not queue:
                    return
                req = queue.pop()
            rec = send(req, time.monotonic(), stop_at=stop_at)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(plan_["clients"])]
    for th in threads:
        th.start()
    for th in threads:
        th.join(max(0.0, stop_at + 30.0 - time.monotonic()))
    with lock:
        return sorted(records, key=lambda r: r["index"])
