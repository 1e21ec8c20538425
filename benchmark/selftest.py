"""What can be checked without a chip (run: python3 benchmark/selftest.py).

  files      BENCHMARK.json against the files it names: every metric has a
             metric file and a reader, every cell a configuration and a mix
  traffic    every seed offers a cell the same number of requests, the same
             multiset of prompt and output lengths and (open loop) the same
             due times; the order and the token ids are the seed's
  reduce     trace_reduce.py on the recorded TPU trace in fixtures/ gives
             the recorded reduction (busy union, kernel sums, gap naming)
  cost       kernel_cost.py on hand-computed cases
  rehearse   every cell end to end at tiny widths on virtual CPU devices
             (JAX_PLATFORMS=cpu, as many as the cell has chips): control
             flow, counts and the result line - never a time

Kept with the benchmark, not in tests/: it is the yardstick's own check.
Nothing printed here is a device metric.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmark")

TINY_CONFIG = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
               "num_hidden_layers": 2, "num_attention_heads": 8,
               "num_key_value_heads": 4, "head_dim": 8,
               "engine": {"page_size": 8, "total_pages": 96, "max_batch": 4,
                          "max_seq_len": 128, "prefill_chunk": 16}}
TINY_LENS = {"prompt": {"median": 14, "sigma": 0.6, "min": 4, "max": 40},
             "output": {"median": 6, "sigma": 0.4, "min": 3, "max": 12}}
TINY_TRAFFIC = {
    "open_loop": {**TINY_LENS, "rate": 4.0, "lead_in_s": 1.0,
                  "sample": [{"prompt": 40, "max_tokens": 6},
                             {"prompt": 9, "max_tokens": 5}],
                  "reference_pad_to": 64, "score_in_window": 2,
                  "trace_after_s": 0.5, "trace_seconds": 1.0},
    "closed_loop": {**TINY_LENS, "per_client": 4, "lead_in_s": 1.0,
                    "sample": [{"prompt": 40, "max_tokens": 6},
                               {"prompt": 9, "max_tokens": 5}],
                    "reference_pad_to": 64, "score_in_window": 2,
                    "trace_after_s": 0.5, "trace_seconds": 1.0},
    "train_job": {"rows": 2, "seq_len": 64, "distinct_batches": 2,
                  "trace_after_steps": 1, "trace_steps": 2},
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind, name):
    from benchmark import run
    return run.load(kind, name)


def check_files() -> None:
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        spec = _load("metrics", m["name"])
        assert spec["name"] == m["name"], m["name"]
        importlib.import_module("benchmark.readers." + spec["reader"])
        assert set(m.get("workloads", cells)) <= cells, m["name"]
        if "moves" in m:
            assert m["moves"] in e2e, m["name"]
            for w in m.get("workloads", cells):
                moved = next(x for x in b["end_to_end"]
                             if x["name"] == m["moves"])
                assert w in moved.get("workloads", cells), (m["name"], w)
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]), c["name"]
    for w in b["workloads"]:
        mix = _load("traffic", w["traffic"])
        importlib.import_module("benchmark.runners." + mix["runner"])
        assert any(c["name"] == w["config"] for c in b["configs"])
    n4 = sum(w["chips"] == 4 for w in b["workloads"])
    assert n4 <= max(1, len(b["workloads"]) // 4)
    print(f"files: {len(b['workloads'])} cells, {len(b['end_to_end'])} "
          f"end-to-end and {len(b['per_layer'])} per-layer metrics resolve")


def check_traffic() -> None:
    from benchmark import loadgen
    b = _bench()
    seeds = [0, 1, 7, 123456789, 2 ** 31 + 11]
    for w in b["workloads"]:
        mix = _load("traffic", w["traffic"])
        cfg = next(c for c in b["configs"] if c["name"] == w["config"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            config = json.load(f)
        if mix["kind"] == "open_loop":
            plans = [loadgen.open_loop(mix, s, b["run_seconds"], 1000)
                     for s in seeds]
        elif mix["kind"] == "closed_loop":
            clients = config["engine"]["max_batch"]
            plans = [loadgen.closed_loop(mix, s, clients, 1000)
                     for s in seeds]
        else:
            shapes = {loadgen.train_batches(mix, s, 1000).shape
                      for s in seeds}
            assert len(shapes) == 1
            print(f"traffic: {w['name']}: batches {shapes.pop()} under "
                  f"every seed")
            continue
        offered = [loadgen.offered_work(p) for p in plans]
        assert all(o == offered[0] for o in offered), w["name"]
        if mix["kind"] == "open_loop":
            order = [[(len(r["prompt"]), r["max_tokens"])
                      for r in p["requests"]] for p in plans]
            assert len({tuple(o) for o in order}) > 1   # the seed permutes
            dues = [[r["due"] for r in p["requests"]] for p in plans]
            assert all(d == dues[0] for d in dues)      # the same due times
            due = [r["due"] for r in plans[0]["requests"] if r["measured"]]
            assert 0 <= min(due) and max(due) < b["run_seconds"]
        else:
            order = [[[r["max_tokens"] for r in q] for q in p["queues"]]
                     for p in plans]
            assert len({json.dumps(o) for o in order}) > 1
        o = offered[0]
        print(f"traffic: {w['name']}: {o['n']} requests, "
              f"{sum(o['prompt_lens'])} prompt and {sum(o['output_lens'])} "
              f"output tokens under every seed")


def check_reduce() -> None:
    from benchmark import trace_reduce
    fx = os.path.join(HERE, "fixtures")
    with open(os.path.join(fx, "expected_reduction.json")) as f:
        want = json.load(f)
    s = trace_reduce.summarize(os.path.join(fx, want["trace"]))
    assert s is not None, "the fixture holds no device plane"
    got = reduction_of(s, want["patterns"])
    for k, v in want["reduction"].items():
        g = got[k]
        same = g == v if not isinstance(v, float) \
            else abs(g - v) <= 1e-9 * max(1.0, abs(v))
        assert same, (k, g, v)
    print(f"reduce: {len(want['reduction'])} numbers of the recorded "
          f"trace reproduce")


def reduction_of(s, patterns: dict) -> dict:
    out = {"n_devices": s.n_devices, "busy_s": s.busy_s, "span_s": s.span_s,
           "device_ops": s.breakdown()["device_ops"],
           "idle_gaps": s.breakdown()["idle_gaps"]}
    for name, pats in patterns.items():
        out[f"op_time:{name}"] = s.op_time(pats)
        out[f"op_count:{name}"] = s.op_count(pats)
        out[f"module_time:{name}"] = s.module_time(pats)
        out[f"module_count:{name}"] = s.module_count(pats)
    return out


def check_cost() -> None:
    from benchmark import kernel_cost as kc
    dims = {"heads": 32, "kv_heads": 8, "head_dim": 128, "layers": 2,
            "tp": 1}
    # one request: 3-token prompt in one chunk, then 2 decode dispatches
    rec = {"t0_wall": 100.0, "prompt_tokens": 3, "admits": [[0.0, 0]],
           "chunks": [[0.1, 3, 1]], "ttft": 0.1,
           "decode": [[0.1, 1], [0.1, 2]]}
    f, b = kc.paged_attention_work([rec], 0.0, 1e9, dims)
    ctx = (1 + 2 + 3) + (3 + 1) + (3 + 2) + (3 + 3)   # per query token
    assert f == 4 * 32 * 128 * ctx * 2, f
    kv_reads = 3 + (4 + 5 + 6)
    assert b == (2 * 8 * 128 * kv_reads + 2 * 32 * 128 * 6) * 2 * 2, b
    f2, _ = kc.paged_attention_work([rec], 100.15, 100.25, dims)
    assert f2 == 4 * 32 * 128 * 4 * 2          # the first decode only
    fd = {"rows": 2, "seq_len": 4096, "heads": 32, "kv_heads": 8,
          "head_dim": 128}
    f, _ = kc.flash_attention_work("fwd", 1, fd)
    assert f == 2 * 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    pct, bound = kc.roofline_pct(197e12, 1.0, 2.0, "TPU v5 lite")
    assert abs(pct - 50.0) < 1e-9 and bound == "compute"
    try:
        kc.peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device_kind must be an error")
    print("cost: paged and flash work, roofline share and the peaks table")


def rehearse(workload: str, trace: int = 1, seconds: float = 3.0) -> dict:
    """One cell at tiny widths in a fresh interpreter (this process may
    have used jax; the parent of a run must not)."""
    b = _bench()
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    mix = _load("traffic", cell["traffic"])
    over = {"devices": cell["chips"], "config": TINY_CONFIG,
            "traffic": TINY_TRAFFIC[mix["kind"]]}
    code = ("import json, sys\n"
            f"sys.path.insert(0, {ROOT!r})\n"
            "from benchmark import run\n"
            f"sys.exit(run.main(['--workload', {workload!r}, '--seed', "
            f"'{2 ** 31 + 5}', '--seconds', '{seconds}', '--trace', "
            f"'{trace}'], rehearse={over!r}))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=dict(os.environ), capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-6000:]
    line = proc.stdout.strip().splitlines()[-1]
    res = json.loads(line)
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res, key
    assert res["device"]["platform"] == "cpu"
    assert res["correct"], (res["faults"], proc.stderr[-3000:])
    assert res["failed"] == 0 and res["attempted"] > 0
    tier = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in b[tier]
               if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) <= allowed, set(res["metrics"]) - allowed
    if not trace:
        assert set(res["metrics"]) == allowed, allowed - set(res["metrics"])
    print(f"rehearse: {workload} trace={trace}: correct, "
          f"{res['attempted']} attempted, metrics "
          f"{sorted(res['metrics'])} (CPU rehearsal: counts only, no "
          f"number here is a device metric)")
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--skip-rehearsal", action="store_true")
    ap.add_argument("--only", default=None,
                    help="rehearse this workload only")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if not args.only:
        check_files()
        check_traffic()
        check_cost()
        check_reduce()
    if not args.skip_rehearsal:
        for w in _bench()["workloads"]:
            if args.only in (None, w["name"]):
                rehearse(w["name"], trace=0)
                rehearse(w["name"], trace=1)
    print("selftest passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
