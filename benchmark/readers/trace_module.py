"""Device time per execution of the jitted programs whose module names
match, from the trace's module line; optionally per inner step.

args: {"patterns": [regex], "per": "engine.decode_chunk" (optional: a
       number from the configuration file to divide by)}. Milliseconds.
"""

from benchmark.readers._stats import lookup


def read(data, args):
    tr = data.get("trace_summary")
    if tr is None:
        return None
    n = tr.module_count(args["patterns"])
    if not n:
        return None
    ms = 1e3 * tr.module_time(args["patterns"]) / n
    if "per" in args:
        ms /= lookup(data["config"], args["per"])
    return ms
