"""The readings that checks_trinity.py's limits lie between, taken on the
chip at the published widths, and the limits tried on them: hold_mimo.py's
method.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids (over all 200192 rows of the vocabulary) of the cell's
reference length, and the float32 reference's (reference_trinity.py) own
greedy choice at every position. Against that choice, the same reference

  bf16        weights and the values between operators HELD in bf16,
              arithmetic float32 at the highest precision: what merely
              storing in the stated precision costs;
  bf16_matmul the same, and every matmul's operands rounded to bf16 too
              (matmul precision "bfloat16"): what COMPUTING in the stated
              precision costs, which is what the served program does;
  fp8         held in float8_e4m3, the nearest precision below the stated
              one;
  <fault>     computed as bf16_matmul (as a program that serves in bf16
              would) with ONE part of the block left out or done wrong
              (reference_trinity.FAULTS): the gate, the q/k norm, a full
              layer rotated, a window layer not rotated, the window, the
              window off by one, a norm after a branch, the routing scale,
              the shared expert, the embedding's multiplier.

Each held choice is then cut into the requests the cell scores (the traffic
file's four warm-up samples at their positions, and four window requests of
the mix's median output after prompts spread over what fits the reference's
length) and put through checks_trinity.served_tokens: both bf16 readings
have to come out correct and every other one NOT correct (but a window off
by one, which is reported: MUST_PASS). The exit code says whether they did;
the table (which fault fails which limit) goes into PERF.md.

    chiprun -- python3 benchmark/hold_trinity.py <seed> [<seed> ...]

writes chiprun_out/hold_trinity.json. (tests/test_kernel_cost_trinity.py
runs `readings` at tiny widths on the CPU: the method, not the numbers.)
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import checks_trinity  # noqa: E402
from benchmark import reference_trinity as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_trinity  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "trinity-mini-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "mixed-window.json")
#: name -> (held in, matmul precision, fault)
HOLDS = {"bf16": (jnp.bfloat16, "highest", None),
         "bf16_matmul": (jnp.bfloat16, "bfloat16", None),
         "fp8": (jnp.float8_e4m3fn, "highest", None),
         **{fault: (jnp.bfloat16, "bfloat16", fault)
            for fault in ref.FAULTS}}
#: what each reading has to come out as; None: reported, not required (a
#: window off by one moves one key in 2048: checks_trinity.py says where it
#: is held instead)
MUST_PASS = {name: None if name == "window_off_by_one"
             else name in ("bf16", "bf16_matmul") for name in HOLDS}


def readings(seed: int, cfg, dims, mix: dict, length: int,
             holds=HOLDS) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length), jnp.int32)
    top, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims)
    top = np.asarray(top)
    cuts = requests_of(mix, length)
    row = {"seed": seed, "length": length}
    for name, (hold, precision, fault) in holds.items():
        held, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims,
                                   hold, precision, fault)
        # the held choice, scored by the float32 reference
        _, gap = ref.token_scores(params, toks, held, dims)
        held, gap = np.asarray(held), np.asarray(gap)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_trinity.served_tokens(groups)
        same = held == top
        row[name] = {
            "equal": float(same.mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "groups": {g: checks_trinity.shares(s)
                       for g, s in groups.items()},
            "requests": checks_trinity.request_shares(groups),
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_trinity.model_fields(config))
    dims = ref.dims_of(cfg)
    rows, wrong = [], []
    for seed in [int(a) for a in argv] or [0]:
        t0 = time.time()
        row = readings(seed, cfg, dims, mix, length)
        row["seconds"] = time.time() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
        wrong += [f"seed {seed}: {name} came out "
                  f"{'correct' if row[name]['correct'] else 'NOT correct'}"
                  for name, want in MUST_PASS.items()
                  if want is not None and row[name]["correct"] != want]
    out = {"device": jax.devices()[0].device_kind,
           "limits": {k: getattr(checks_trinity, k) for k in (
               "MIN_EQUAL", "MAX_OVER", "MIN_EQUAL_REQUEST")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_trinity.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
