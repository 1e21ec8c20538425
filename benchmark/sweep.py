"""Find an open-loop cell's knee: one replica, several rates in turn.

    python3 benchmark/sweep.py --workload <cell> --rates 1,2,3,4,5 --seconds 25

For each rate: the cell's mix at that rate (lead-in, window, requests
followed to their end), then one JSON line: requests in flight at window
open and at window close (the backlog), how late the generator ran, the
latencies, the tokens per second. The knee is the highest rate at which
the backlog at close is no larger than at open and gen_late_p99_ms stays
under one mixed step; the cell's rate is half of it, written into the mix's
file by hand with the table in PERF.md. Run it on the chip (chiprun); it is
not part of a check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import cluster, loadgen, run
    from benchmark.readers import client_latency, client_throughput
    from benchmark.runners import serve
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    what = run.resolve(args.workload)
    config, mix = what["config"], what["traffic"]
    cluster.prepare_environment(config)
    cluster.keep_stdout_for_the_result()
    with cluster.Cluster(what["cell"]["chips"]):
        sess = serve.Session(config, args.seed)
        dev = sess.probe()
        if dev["platform"] != "tpu":
            sys.stderr.write("sweep: no TPU here\n")
            return 1
        sess.warm_and_score(mix, args.seed)
        for rate in [float(r) for r in args.rates.split(",")]:
            plan = loadgen.open_loop({**mix, "rate": rate}, args.seed,
                                     args.seconds, config["vocab_size"])
            win = serve._window_requests(plan, sess, args.seconds)
            res = win["results"]

            def in_flight(t):
                return sum(1 for r in res if r["due"] <= t and (
                    not r["events"] or r["events"][-1][0] > t
                    or not r["done"]))
            def queued(t):
                return sum(1 for r in res if r["due"] <= t and (
                    not r["events"] or r["events"][0][0] > t))
            data = {"measured": [r for r in res if r["measured"]],
                    "results": res, "t_open": win["t_open"],
                    "t_close": win["t_close"]}

            def lat(q, p):
                return client_latency.read(data, {"quantity": q,
                                                  "percentile": p})
            print(json.dumps({
                "rate": rate, "requests": len(data["measured"]),
                "errors": sum(1 for r in res if r["error"]),
                "backlog_open": in_flight(win["t_open"]),
                "backlog_close": in_flight(win["t_close"]),
                "queued_open": queued(win["t_open"]),
                "queued_close": queued(win["t_close"]),
                "gen_late_p99_ms": lat("late", 99),
                "ttft_p50_ms": lat("ttft", 50), "ttft_p90_ms": lat("ttft", 90),
                "tpot_p50_ms": lat("tpot", 50), "tpot_p90_ms": lat("tpot", 90),
                "out_tok_per_s": client_throughput.read(data, {}),
                "drain_s": time.monotonic() - win["t_close"],
                "preemptions": win["close_probe"]["stats"]["preemptions"],
            }), flush=True)
            sess.wait_idle()
    return 0


if __name__ == "__main__":
    sys.exit(main())
