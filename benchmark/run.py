"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

BENCHMARK.json says what a cell is (a configuration under a traffic mix, on
1 or 4 chips) and which metrics it reports; everything that belongs to one
configuration, one mix or one metric is a file of its own that this program
finds by name:

    benchmark/configs/<config>.json    sizes and deployment settings
    benchmark/traffic/<mix>.json       the mix's parameters and its runner
    benchmark/runners/<runner>.py      how that kind of work is driven
    benchmark/metrics/<metric>.json    the metric's reader and arguments
    benchmark/readers/<reader>.py      how that kind of number is read

No name of a cell, configuration, mix or metric appears in this file.

The last line of stdout is one JSON object: correct, attempted, failed,
metrics (--trace 0: the cell's end-to-end metrics; --trace 1: its per-layer
metrics), device, and with --trace 1 the breakdown. This process never
initialises a jax backend: the workers the runtime leases the chips to own
them. With no TPU it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import importlib            # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import signal               # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")
DEADLINE_S = 1150           # the first run of a cell may take 1200 s


def load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": load("traffic", cell["traffic"])}


def metrics_of(bench: dict, workload: str, tier: str) -> list:
    return [m for m in bench[tier]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(entries: list, data: dict, rehearse: bool) -> dict:
    from benchmark.kernel_cost import UnknownDevice
    out = {}
    for entry in entries:
        spec = load("metrics", entry["name"])
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        try:
            value = reader.read(data, spec.get("args", {}))
        except UnknownDevice:
            if not rehearse:    # a CPU rehearsal has no peaks to divide by
                raise
            value = None
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def _merge(base: dict, over: dict) -> None:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v


def main(argv=None, rehearse: dict = None) -> int:
    """`rehearse` is the selftest's: {"devices": n, "config": {...},
    "traffic": {...}} runs the cell on n virtual CPU devices with the
    files' numbers overridden (tiny widths). The command line cannot ask
    for it, and its result says platform "cpu"."""
    rehearse_devices = (rehearse or {}).get("devices", 0)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    what = resolve(args.workload)
    bench, cell, config, traffic = (what[k] for k in (
        "bench", "cell", "config", "traffic"))
    _merge(config, (rehearse or {}).get("config", {}))
    _merge(traffic, (rehearse or {}).get("traffic", {}))
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    from benchmark import cluster, trace_reduce
    cluster.prepare_environment(config, rehearse_devices)
    shutil.rmtree(cluster.SCRATCH, ignore_errors=True)   # the last run's
    os.makedirs(cluster.SCRATCH, exist_ok=True)
    cluster.keep_stdout_for_the_result()

    def on_deadline(*_):
        raise TimeoutError(f"not done after {DEADLINE_S} s")
    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)

    runner = importlib.import_module("benchmark.runners."
                                     + traffic["runner"])
    ctx = {"config": config, "traffic": traffic, "seed": args.seed,
           "seconds": seconds, "trace": args.trace, "chips": cell["chips"],
           "t_start": T_START, "rehearse": bool(rehearse_devices),
           "scratch": cluster.SCRATCH}
    try:
        data = runner.run(ctx)
    except cluster.NoAccelerator as e:
        sys.stderr.write(f"benchmark: {e}\n")
        return 1
    signal.alarm(0)

    tier = "per_layer" if args.trace else "end_to_end"
    device = data["device"]
    dev_out = {"platform": device["platform"], "kind": device["kind"],
               "count": device["count"],
               "memory_peak_bytes": device["memory_peak_bytes"]}
    result = {"correct": not data["faults"], "attempted": data["attempted"],
              "failed": data["failed"]}
    if args.trace:
        span = data.get("trace") or {}
        summary = trace_reduce.summarize(span["dir"]) \
            if span.get("dir") else None
        data["trace_summary"] = summary
        if summary is not None:
            dev_out.update(busy_s=summary.busy_s, window_s=summary.span_s)
            result["breakdown"] = summary.breakdown()
        elif not rehearse_devices:
            data["faults"].append("the traced window holds no device op")
            result["correct"] = False
    result["metrics"] = read_metrics(
        metrics_of(bench, args.workload, tier), data,
        bool(rehearse_devices))
    result["device"] = dev_out
    result["faults"] = data["faults"][:20]
    result["notes"] = data.get("notes", {})
    result["timing"] = data["timing"]
    result["offered"] = {k: v for k, v in data.get("offered", {}).items()
                         if not isinstance(v, list)}
    for f in data["faults"]:
        sys.stderr.write(f"benchmark: NOT CORRECT: {f}\n")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
