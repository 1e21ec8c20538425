"""A percentile of one field of the engine's flight-recorder records
(llm/request_log.py) over the requests measured in the window, joined to
the client's requests by request id.

args: {"field": "queue_wait", "percentile": 50, "scale": 1000}
"""

from benchmark.readers._stats import percentile


def read(data, args):
    by_rid = {rec["rid"]: rec for rec in data.get("request_log", ())}
    vals = [by_rid[r["rid"]][args["field"]] for r in data.get("measured", ())
            if r.get("rid") in by_rid
            and by_rid[r["rid"]].get(args["field"]) is not None]
    p = percentile(vals, args["percentile"])
    return None if p is None else p * args.get("scale", 1.0)
