"""What can be checked without a chip (run: python3 benchmark/selftest.py).

  files      BENCHMARK.json against the files it names: every metric has a
             metric file and a reader, every cell a configuration and a mix;
             per_layer holds 128 entries at most and ONE entry a reading
             (reader, arguments, `moves`), the cells in its list
  fold       every (cell, reading) of the list as PR 53 left it
             (fixtures/per_layer_pr53.json) is reported by exactly one
             entry of today's list: a fold loses and doubles nothing
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


#: entries that repeat a bare-named entry's reader and arguments under a
#: cell's suffix, kept only because a test under tests/ names them and a
#: `benchmark` PR may not touch tests/ (PERF.md section 7, "Left by PR 54").
#: The list only shrinks: once no test names one, a `benchmark` PR moves its
#: cells into the bare entry's list and takes it out of here.
HELD_BY_TESTS = frozenset((
    "decode_step_ms.reason", "decode_step_ms.mimo",
    "mixed_step_ms.reason", "mixed_step_ms.granite", "mixed_step_ms.mimo",
    "mixed_step_time_pct.reason", "mixed_step_time_pct.granite",
    "mixed_step_time_pct.mimo", "device_idle_pct.mimo",
    "engine_host_gap_ms.reason", "engine_host_gap_ms.mimo",
    "idle_prep_pct.reason", "idle_prep_pct.mimo",
    "paged_attn_time_pct.mimo", "moe_ffn_time_pct.mimo"))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(kind, name):
    from benchmark import run
    return run.load(kind, name)


def _reading(reader: str, args: dict, moves: str) -> tuple:
    """What a per_layer entry reads: a reader, its arguments, and the
    end-to-end metric the number moves."""
    return (reader, json.dumps(args, sort_keys=True), moves)


def _reading_of(m: dict) -> tuple:
    spec = _load("metrics", m["name"])
    return _reading(spec["reader"], spec.get("args", {}), m["moves"])


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
    assert len(b["per_layer"]) <= 128, len(b["per_layer"])
    # one entry a reading: a cell that reads what an accepted cell reads
    # joins that entry's list, and spends an entry only on what is its own
    one, held = {}, []
    for m in b["per_layer"]:
        reading = _reading_of(m)
        if m["name"] in HELD_BY_TESTS:
            held.append((m, reading))
            continue
        assert reading not in one, (m["name"], one[reading]["name"])
        one[reading] = m
    assert {m["name"] for m, _ in held} == HELD_BY_TESTS
    for m, reading in held:     # a repeat of a bare entry, no cell in both
        bare = one[reading]
        assert bare["name"] == m["name"].rsplit(".", 1)[0], m["name"]
        assert not set(m["workloads"]) & set(bare["workloads"]), m["name"]
    print(f"files: {len(b['workloads'])} cells, {len(b['end_to_end'])} "
          f"end-to-end and {len(b['per_layer'])} per-layer metrics resolve")


def check_fold() -> None:
    with open(os.path.join(HERE, "fixtures", "per_layer_pr53.json")) as f:
        was = json.load(f)["per_layer"]
    now = [(_reading_of(m), m["workloads"]) for m in _bench()["per_layer"]]
    pairs = 0
    for old in was:
        reading = _reading(old["reader"], old["args"], old["moves"])
        for cell in old["workloads"]:
            n = sum(r == reading and cell in cells for r, cells in now)
            assert n == 1, (old["name"], cell, n)
            pairs += 1
    print(f"fold: {pairs} (cell, reading) pairs of PR 53's {len(was)} "
          f"entries are each reported by one of today's {len(now)}")


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
    # the routing counters' two ratios over a block's own dims
    from benchmark.readers import trinity_counters
    with open(os.path.join(HERE, "configs",
                           "trinity-mini-serve-1chip.json")) as f:
        config = json.load(f)       # 128 experts x 4 expert layers, all held
    keys = ("moe_pairs", "moe_hits", "moe_hot", "decode_steps")
    data = {"config": config, "stats_open": dict.fromkeys(keys, 0),
            "stats_close": {"moe_pairs": 40960, "moe_hits": 4608,
                            "moe_hot": 1760, "decode_steps": 10}}
    assert trinity_counters.read(data, {"quantity": "hit_pct"}) \
        == 100 * 4608 / (10 * 4 * 128) == 90.0
    assert trinity_counters.read(data, {"quantity": "load_skew"}) \
        == 1760 * 128 / 40960 == 5.5
    for quantity in ("hit_pct", "load_skew"):
        assert trinity_counters.read({"config": config},
                                     {"quantity": quantity}) is None
        assert trinity_counters.read(
            {"config": config, "stats_open": {"decode_steps": 1},
             "stats_close": {"decode_steps": 5}},
            {"quantity": quantity}) is None
    print("cost: paged and flash work, roofline share, the peaks table and "
          "the routing counters' ratios")


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
        check_fold()
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
