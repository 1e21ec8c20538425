"""The Ouro block's counters. A constant the engine states once
(InferenceEngine.stats, set by the constructor and never moved), read from
the end probe's copy of the stats as readers/phi4flash_counters.py reads
its keys: what a token costs in the pool over EVERY page plane
(`kv_token_bytes`), how many planes there are (`kv_planes`). And a ratio of
the step programs' own counters over the window (stats at open and at
close): the mean pass at which a row's exit distribution first reaches a
cumulative 0.5 (`ut_exit_at_<pass>`, a count a pass of the valid rows of
every dispatch; nothing acts on it: every token runs every pass). None
where the program has no such key (a commit before the block was served).

args: {"key": "kv_token_bytes" | "kv_planes"}
   or {"quantity": "exit_pass_mean"}
"""

PREFIX = "ut_exit_at_"


def read(data, args):
    if "key" in args:
        stats = (data.get("device") or {}).get("stats") or {}
        return stats.get(args["key"])
    if args.get("quantity") != "exit_pass_mean":
        raise ValueError(f"unknown quantity {args.get('quantity')!r}")
    a, b = data.get("stats_open"), data.get("stats_close")
    if not a or not b:
        return None
    counted = {int(k[len(PREFIX):]): b[k] - a.get(k, 0)
               for k in b if k.startswith(PREFIX)}
    rows = sum(counted.values())
    if rows <= 0:
        return None
    return sum(u * n for u, n in counted.items()) / rows
