"""The readings that checks_ouro.py's limits lie between, taken on the chip
at the published widths, and the limits tried on them: hold_phi4flash.py's
method for the Ouro block.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids (over all 49152 rows of the vocabulary) of the cell's
reference length, and the float32 reference's (reference_ouro.py) own
greedy choice at every position. Against that choice, the same reference

  bf16        weights and the values between operators HELD in bf16 (by
              lax.reduce_precision), arithmetic float32 at the highest
              precision: what merely storing in the stated precision costs;
  bf16_matmul the same, and every matmul's operands rounded to bf16 too
              (matmul precision "bfloat16"): what COMPUTING in the stated
              precision costs, which is what the served program does;
  fp8         held in float8_e4m3, the nearest precision below the stated
              one: the lower-precision control, which has to come out NOT
              correct;
  shared_kv   computed as bf16_matmul, passes 2-4 attending over pass 1's
              keys and values: a pool with ONE plane a layer;
  norm_once   computed as bf16_matmul, the final norm applied after the
              last pass only (the stream between passes not normed).

Each held choice is then cut into the requests the cell scores (the traffic
file's warm-up samples at their positions, and four window requests) and
put through checks_ouro.served_tokens: both bf16 readings have to come out
correct, the three controls NOT correct. The exit code says whether they
did.

    chiprun -- python3 benchmark/hold_ouro.py <seed> [<seed> ...]

writes chiprun_out/hold_ouro.json. (tests/test_kernel_cost_ouro.py runs
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

from benchmark import checks_ouro  # noqa: E402
from benchmark import reference_ouro as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_ouro  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "ouro-2.6b-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "reason-loop.json")
#: name -> (held in, matmul precision, faults)
HOLDS = {"bf16": ("bf16", "highest", ()),
         "bf16_matmul": ("bf16", "bfloat16", ()),
         "fp8": ("fp8", "highest", ()),
         "shared_kv": ("bf16", "bfloat16", ("shared_kv",)),
         "norm_once": ("bf16", "bfloat16", ("norm_once",))}
#: what each reading has to come out as
MUST_PASS = {name: name in ("bf16", "bf16_matmul") for name in HOLDS}


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
    for name, (hold, precision, faults) in holds.items():
        held, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims,
                                   hold, precision, faults)
        # the held choice, scored by the float32 reference
        _, gap = ref.token_scores(params, toks, held, dims)
        held, gap = np.asarray(held), np.asarray(gap)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_ouro.served_tokens(groups)
        row[name] = {
            "equal": float((held == top).mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "groups": {g: checks_ouro.shares(s) for g, s in groups.items()},
            "requests": checks_ouro.request_shares(groups),
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_ouro.model_fields(config))
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
                  if row[name]["correct"] != want]
    out = {"device": jax.devices()[0].device_kind,
           "limits": {k: getattr(checks_ouro, k) for k in (
               "MAX_OVER_1", "MAX_OVER_1_REQUEST", "MIN_EQUAL", "MAX_OVER",
               "MAX_GAP")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_ouro.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
