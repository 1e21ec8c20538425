"""The readings that checks_phi4flash.py's limits lie between, taken on the
chip at the published widths, and the limits tried on them: hold_trinity.py's
method for the Phi-4-mini-flash block.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids (over all 200064 rows of the vocabulary) of the cell's
reference length, and the float32 reference's (reference_phi4flash.py) own
greedy choice at every position. Against that choice, the same reference

  bf16        weights and the values between operators HELD in bf16 (by
              lax.reduce_precision: a pair of casts is the compiler's to
              drop), arithmetic float32 at the highest precision: what
              merely storing in the stated precision costs;
  bf16_matmul the same, and every matmul's operands rounded to bf16 too
              (matmul precision "bfloat16"): what COMPUTING in the stated
              precision costs, which is what the served program does;
  control     bf16_matmul, and the recurrence's decay and carried state
              rounded to bf16 every token, and lambda left at lambda_init:
              the lower-precision control, which has to come out NOT
              correct;
  fp8         held in float8_e4m3, the nearest precision below the stated
              one;
  <fault>     computed as bf16_matmul with ONE part of the block done
              wrong (reference_phi4flash.FAULTS).

Each held choice is then cut into the requests the cell scores (the traffic
file's warm-up samples at their positions, and four window requests) and
put through checks_phi4flash.served_tokens: both bf16 readings have to come
out correct, the control and fp8 NOT correct; the faults are reported (a
window off by one moves one key in 512; the tests hold every fault on
logits, at 1e-4 in float32). The exit code says whether they did.

    chiprun -- python3 benchmark/hold_phi4flash.py <seed> [<seed> ...]

writes chiprun_out/hold_phi4flash.json. (tests/test_kernel_cost_phi4flash.py
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

from benchmark import checks_phi4flash  # noqa: E402
from benchmark import reference_phi4flash as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_phi4flash  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "phi4-mini-flash-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "reason-shared-kv.json")
#: name -> (held in, matmul precision, faults)
HOLDS = {"bf16": ("bf16", "highest", ()),
         "bf16_matmul": ("bf16", "bfloat16", ()),
         "control": ("bf16", "bfloat16", ("carry_bf16", "lambda_at_init")),
         "fp8": ("fp8", "highest", ()),
         **{fault: ("bf16", "bfloat16", (fault,)) for fault in ref.FAULTS}}
#: what each reading has to come out as; None: reported, not required
MUST_PASS = {name: True if name in ("bf16", "bf16_matmul")
             else False if name in ("control", "fp8") else None
             for name in HOLDS}


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
        faults = checks_phi4flash.served_tokens(groups)
        row[name] = {
            "equal": float((held == top).mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "groups": {g: checks_phi4flash.shares(s)
                       for g, s in groups.items()},
            "requests": checks_phi4flash.request_shares(groups),
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_phi4flash.model_fields(config))
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
           "limits": {k: getattr(checks_phi4flash, k) for k in (
               "MIN_EQUAL", "MAX_OVER", "MAX_GAP", "MIN_EQUAL_REQUEST")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_phi4flash.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
