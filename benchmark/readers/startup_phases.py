"""Seconds of a served replica's start-up, by phase: the program's own
start-up clocks (ray_tpu/util/startup_clocks.py), which LLMServer.__init__
writes ONCE into InferenceEngine.stats: startup_ns_<phase> for process,
import, backend, weights, pool, programs, other (they partition the
interval from the worker's spawn stamp to the end of the constructor), and
of the step programs' loads startup_ns_trace_lower,
startup_ns_backend_compile, startup_programs_cold.

No hook is needed to reach them: benchmark/replica.py's bench_probe copies
engine.stats whole, so every run's data["device"]["stats"] (the end probe;
stats_open and stats_close hold the same values, the keys never move after
the constructor) has them. This reader reads

    data["device"]["stats"][key]     for each key of args["keys"]
    data["timing"]["replica_ready_s"]  with args["of"] == "ready_remainder"

and nothing else. args:
    keys   the stats keys to sum
    of     "stats": their sum times `scale`;
           "ready_remainder": replica_ready_s, the runner's outside clock
           around serve.run, less their sum in seconds: what the replica
           cannot time itself (the lease and the spawn before the stamp,
           the controller's health poll after the constructor). keys are
           then the seven phases, whose sum is the replica's whole interval
    scale  1e-9 (nanoseconds to seconds) unless given (1 for a count)

None where a key is absent: a program from before the clocks (the parent
of the PR that brought them), and the train cell, whose worker's clocks
reach no probe yet. The harness then leaves the metric out of the line, as
engine_clocks.py does.
"""


def read(data, args):
    stats = (data.get("device") or {}).get("stats") or {}
    if any(k not in stats for k in args["keys"]):
        return None
    total = sum(stats[k] for k in args["keys"])
    if args["of"] == "stats":
        return total * args.get("scale", 1e-9)
    ready = (data.get("timing") or {}).get("replica_ready_s")
    return None if ready is None else ready - total * 1e-9
