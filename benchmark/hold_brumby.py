"""The two readings that checks_brumby.py's tolerance lies between, taken
on the chip at the published widths, and the limits tried on them
(hold_granite.py's method for the Brumby block).

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids of the cell's reference length, and the float32
reference's (reference_brumby.py: the quadratic form) own greedy choice at
every position. Against that choice, the same weights run through
reference_brumby.forward(hold=): weights, the values between operators and
the retention's MATRIX STATE (the recurrence, token after token) held in
bf16, the stated precision (once with float32 arithmetic at the highest
matmul precision: what merely storing in bf16 costs; once with every
matmul's operands rounded to bf16 too, which is what the served program
computes), and in float8_e4m3, the nearest below it; the normaliser float32
as the program holds it. Each held choice is cut into the requests
the cell scores (hold_kanana.requests_of) and put through
checks_brumby.served_tokens: both bf16 readings have to come out correct
and fp8 NOT correct. The exit code says whether they did.

    chiprun -- python3 benchmark/hold_brumby.py <seed> [<seed> ...]

writes chiprun_out/hold_brumby.json. (tests/test_kernel_cost_brumby.py runs
`readings` at tiny widths on the CPU: the method, not the numbers.)
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

from benchmark import checks, checks_brumby  # noqa: E402
from benchmark import reference_brumby as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_brumby  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "brumby-14b-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic",
                       "context-retention.json")
HOLDS = {"bf16": (jnp.bfloat16, "highest"),
         "bf16_matmul": (jnp.bfloat16, "bfloat16"),
         "fp8": (jnp.float8_e4m3fn, "highest")}
MUST_PASS = {"bf16": True, "bf16_matmul": True, "fp8": False}


def readings(seed: int, cfg, dims, mix: dict, length: int) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length), jnp.int32)
    top = np.asarray(ref.next_token_scores(params, toks, dims)[0])
    cuts = requests_of(mix, length)
    row = {"seed": seed, "length": length}
    for name, (hold, precision) in HOLDS.items():
        held = ref.next_token_scores(params, toks, dims, hold,
                                     precision=precision)[0]
        # the held choice, scored by the float32 reference
        gap = np.asarray(ref.next_token_scores(params, toks, dims,
                                               took=held)[1])
        held = np.asarray(held)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_brumby.served_tokens(groups)
        row[name] = {
            "equal": float((held == top).mean()), "worst": float(gap.max()),
            "p99": float(np.quantile(gap, 0.99)),
            "group_shares": {g: checks_brumby.shares(s)
                             for g, s in groups.items()},
            "over_block_tol": int((gap > checks_brumby.LOGIT_TOL).sum()),
            "over_checks_tol": int((gap > checks.LOGIT_TOL).sum()),
            "groups": {g: checks.score_summary(s)
                       for g, s in groups.items()},
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_brumby.model_fields(config))
    dims = ref.dims_of(cfg)
    rows, wrong = [], []
    for seed in [int(a) for a in argv] or [0]:
        t0 = time.time()
        row = readings(seed, cfg, dims, mix, mix["reference_pad_to"])
        row["seconds"] = time.time() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
        wrong += [f"seed {seed}: {name} came out "
                  f"{'correct' if row[name]['correct'] else 'NOT correct'}"
                  for name, want in MUST_PASS.items()
                  if row[name]["correct"] != want]
    out = {"device": jax.devices()[0].device_kind,
           "limits": {"LOGIT_TOL": checks_brumby.LOGIT_TOL,
                      "checks.LOGIT_TOL": checks.LOGIT_TOL,
                      "checks.MIN_EQUAL": checks.MIN_EQUAL},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_brumby.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
