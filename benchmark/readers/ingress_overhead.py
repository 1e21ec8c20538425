"""What the path around the engine adds to a request's first token: per
request, (client's first token - client's send) - the engine's own TTFT
(enqueue -> first token, request log), so proxy, router, replica handler
and the stream back. args: {"percentile": 50}. Milliseconds."""

from benchmark.readers._stats import percentile


def read(data, args):
    by_rid = {rec["rid"]: rec for rec in data.get("request_log", ())}
    vals = []
    for r in data.get("measured", ()):
        rec = by_rid.get(r.get("rid"))
        if rec and rec.get("ttft") is not None and r["events"]:
            vals.append(r["events"][0][0] - r["sent"] - rec["ttft"])
    p = percentile(vals, args["percentile"])
    return None if p is None else 1e3 * p
