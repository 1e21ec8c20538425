"""A ratio of the engine thread's phase clocks (InferenceEngine.stats:
wall_ns_<phase> for admit, pack, h2d, dispatch, readback, book, metrics,
other, publish, wait, and cpu_ns_host, the thread's CPU time outside
readback and wait; llm/engine.py: PhaseClocks), as deltas over the window:
engine_stats.py's arithmetic and arguments, unchanged. What this file
adds: None for a program without the clocks (engine_stats raises on a key
the counters lack), and once a run, in the notes (`engine_clocks`), every
phase's wall ms a dispatch, the host's CPU ms a dispatch and the share of
the window the ten wall clocks cover (they partition the engine thread's
time, so it reads 100 up to the two probes' own latency).

A per-layer metric is read on a --trace 1 line only, and a traced run's
host loop is a quarter to a half slower than an untraced run's (its window
holds the profiler's stop; PERF.md section 5): the ledger's values of these
metrics overstate the untraced loop by that much.

args: engine_stats.py's
"""

from benchmark.readers import engine_stats

PHASES = ("admit", "pack", "h2d", "dispatch", "readback", "book", "metrics",
          "other", "publish", "wait")
DISPATCHES = ("decode_dispatches", "ragged_dispatches")


def read(data, args):
    a, b = data.get("stats_open"), data.get("stats_close")
    if not a or not b or any(k not in a or k not in b
                             for k in args["num"] + args["den"]):
        return None
    notes = data.setdefault("notes", {})
    if "engine_clocks" not in notes and "cpu_ns_host" in b and all(
            "wall_ns_" + p in b for p in PHASES):
        n = max(sum(b[k] - a[k] for k in DISPATCHES), 1)
        wall = {p: b["wall_ns_" + p] - a["wall_ns_" + p] for p in PHASES}
        notes["engine_clocks"] = {
            "dispatches": n,
            "wall_ms_a_dispatch": {p: wall[p] / n / 1e6 for p in PHASES},
            "host_cpu_ms_a_dispatch":
                (b["cpu_ns_host"] - a["cpu_ns_host"]) / n / 1e6,
            "covered_pct": 100.0 * sum(wall.values()) / 1e9
            / data["window_s"] if data.get("window_s") else None}
    return engine_stats.read(data, args)
