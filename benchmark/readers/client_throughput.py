"""Output tokens the clients received inside the window / the window:
every token event stamped in [open, close) counts, whichever request it
belongs to and whether or not that request ended inside the window."""


def read(data, args):
    if "results" not in data:
        return None
    lo, hi = data["t_open"], data["t_close"]
    n = sum(k for r in data["results"] for t, k in r["events"]
            if lo <= t < hi)
    return n / (hi - lo)
