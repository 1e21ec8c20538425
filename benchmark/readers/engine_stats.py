"""A ratio of engine counters (InferenceEngine.stats), as deltas over the
window (read at open and at close).

args: {"num": [keys], "den": [keys], "den_times": "engine.max_batch"
       (optional: a number from the configuration file), "one_minus": bool,
       "scale": 100}
"""

from benchmark.readers._stats import lookup


def read(data, args):
    a, b = data.get("stats_open"), data.get("stats_close")
    if not a or not b:
        return None
    num = sum(b[k] - a[k] for k in args["num"])
    den = sum(b[k] - a[k] for k in args["den"])
    if "den_times" in args:
        den *= lookup(data["config"], args["den_times"])
    if den <= 0:
        return None
    x = num / den
    if args.get("one_minus"):
        x = 1.0 - x
    return x * args.get("scale", 1.0)
