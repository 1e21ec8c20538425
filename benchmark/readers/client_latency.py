"""Latencies on the client's clock over the requests DUE in the window.

args: {"quantity": "ttft" | "tpot" | "late", "percentile": 50}
  ttft  first streamed token - the request's DUE time (a stall in the
        system delays later requests; the wait counts)
  tpot  (last token - first token) / (n - 1) of one request
  late  when the generator really sent - when the request was due
Seconds in, milliseconds out. A failed request has no latency and is
counted in `failed`; a run with any is not `correct`.
"""

from benchmark.readers._stats import percentile


def values(data, quantity):
    """Seconds, one per request due in the window; None if no open loop."""
    vals = []
    for r in data.get("measured", ()):
        if "due" not in r:
            return None                      # not an open-loop run
        if quantity == "late":
            vals.append(r["sent"] - r["due"])
        elif not r["events"]:
            continue
        elif quantity == "ttft":
            vals.append(r["events"][0][0] - r["due"])
        elif quantity == "tpot":
            n = sum(k for _, k in r["events"])
            if n > 1 and r["done"]:
                vals.append((r["events"][-1][0] - r["events"][0][0])
                            / (n - 1))
    return vals


def read(data, args):
    vals = values(data, args["quantity"])
    if not vals:
        return None
    return 1e3 * percentile(vals, args["percentile"])
