"""HTTP/SSE client for /v1/completions (after bench_serve._post_stream):
one streamed request, timed on this process's monotonic clock."""

from __future__ import annotations

import http.client
import json
import time

_HEADERS = {"Content-Type": "application/json",
            "X-Request-Timeout-S": "600"}


def get_json(host: str, port: int, path: str, timeout: float = 60.0) -> dict:
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def stream_completion(host: str, port: int, req: dict, due: float,
                      stop_at: float | None = None) -> dict:
    """Send one request now; return its record. ``due`` is when it should
    have been sent (monotonic); latencies are taken from it. With
    ``stop_at``, a stream still open then is closed and marked
    ``abandoned`` (closed loop at the end of its window)."""
    rec = {"index": req["index"], "due": due, "sent": time.monotonic(),
           "prompt_tokens_asked": req["prompt_tokens"],
           "max_tokens": req["max_tokens"], "chunk_times": [],
           "first": None, "done": None, "completion_tokens": 0,
           "prompt_tokens": None, "engine": {}, "error": None,
           "abandoned": False}
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0, "stream": True})
    conn = http.client.HTTPConnection(host, port, timeout=120.0)
    try:
        conn.request("POST", "/v1/completions", body=body, headers=_HEADERS)
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read(300)!r}"
            return rec
        while True:
            if stop_at is not None and time.monotonic() >= stop_at:
                rec["abandoned"], rec["abandoned_at"] = True, time.monotonic()
                return rec
            raw = resp.readline()
            if not raw:
                rec["error"] = rec["error"] or "stream ended without [DONE]"
                return rec
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            now = time.monotonic()
            payload = line[5:].strip()
            if payload == "[DONE]":
                rec["done"] = now
                return rec
            chunk = json.loads(payload)
            if chunk.get("error"):
                rec["error"] = str(chunk["error"])
            if chunk.get("usage") is not None:
                rec["completion_tokens"] = int(
                    chunk["usage"].get("completion_tokens", 0))
                rec["prompt_tokens"] = chunk["usage"].get("prompt_tokens")
                rec["engine"] = chunk.get("ray_tpu") or {}
            elif chunk.get("choices"):
                # a delta chunk: one drain of the engine's token queue
                if rec["first"] is None:
                    rec["first"] = now
                rec["chunk_times"].append(now)
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    finally:
        conn.close()
