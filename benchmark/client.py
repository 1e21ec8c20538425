"""The client side: /v1/completions over HTTP, streamed (SSE), one thread a
request in flight, every token event stamped on the client's clock.

Nothing here knows the engine: a request is a prompt and a max_tokens, a
reply is token ids arriving in events. Times are time.monotonic() seconds.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Dict, List, Optional


def stream_completion(port: int, model: str, req: Dict,
                      stop: Optional[threading.Event] = None) -> Dict:
    """Send one request now; returns {"sent", "events": [(t, n_tokens)],
    "tokens", "rid", "done", "error"}. `stop` set mid-stream drops the
    connection (the run is over; the reply is not a failure)."""
    out = {"sent": time.monotonic(), "events": [], "tokens": [], "rid": None,
           "done": False, "error": None, "aborted": False,
           "prompt": req["prompt"], "max_tokens": req["max_tokens"]}
    body = json.dumps({"model": model, "prompt": req["prompt"],
                       "max_tokens": req["max_tokens"], "stream": True})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            out["error"] = f"HTTP {resp.status}: {resp.read()[:300]!r}"
            return out
        while True:
            if stop is not None and stop.is_set():
                out["aborted"] = True
                return out
            raw = resp.readline()
            if not raw:
                break
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            if line == b"data: [DONE]":
                out["done"] = True
                break
            item = json.loads(line[6:])
            if "error" in item:
                out["error"] = str(item["error"])[:300]
                return out
            now = time.monotonic()
            if out["rid"] is None and str(item.get("id", "")).startswith(
                    "cmpl-"):
                out["rid"] = item["id"][5:]
            ids = item["choices"][0]["token_ids"]
            if ids:
                out["events"].append((now, len(ids)))
                out["tokens"] += ids
    except Exception as e:  # noqa: BLE001 — reported per request
        if stop is not None and stop.is_set():
            out["aborted"] = True
        else:
            out["error"] = repr(e)[:300]
    finally:
        conn.close()
    return out


def run_open_loop(port: int, model: str, requests: List[Dict],
                  t_open: float) -> List[Dict]:
    """Every request is sent at t_open + its `due`, whatever the system is
    doing (one sleeping thread each), and followed to its end. The result
    keeps `due` and `measured` beside what stream_completion saw."""
    results: List[Optional[Dict]] = [None] * len(requests)

    def one(i: int, req: Dict) -> None:
        due = t_open + req["due"]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        r = stream_completion(port, model, req)
        r.update(due=due, measured=req["measured"])
        results[i] = r

    threads = [threading.Thread(target=one, args=(i, r), daemon=True)
               for i, r in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results  # type: ignore[return-value]


def run_closed_loop(port: int, model: str, queues: List[List[Dict]],
                    t_close: float) -> List[Dict]:
    """One thread a client; each sends its next request when the last one
    completes, cycling through its queue, until t_close has passed; a
    request in flight then is dropped, not waited for."""
    stop = threading.Event()
    results: List[List[Dict]] = [[] for _ in queues]

    def client(j: int, queue: List[Dict]) -> None:
        k = 0
        while not stop.is_set():
            r = stream_completion(port, model, queue[k % len(queue)], stop)
            r.update(client=j, measured=True)
            results[j].append(r)
            if r["error"]:
                return
            k += 1

    threads = [threading.Thread(target=client, args=(j, q), daemon=True)
               for j, q in enumerate(queues)]
    for t in threads:
        t.start()
    while time.monotonic() < t_close:
        time.sleep(0.02)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    return [r for rs in results for r in rs]
