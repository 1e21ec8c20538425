"""The two readings that checks_kanana.py's limits lie between, taken on
the chip at the published widths, and the limits tried on them.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids of the cell's reference length, and the float32
reference's (reference_kanana.py) own greedy choice at every position.
Against that choice, the same reference

  bf16        weights and the values between operators HELD in bf16,
              arithmetic float32 at the highest precision: what merely
              storing in the stated precision costs;
  bf16_matmul the same, and every matmul's operands rounded to bf16 too
              (matmul precision "bfloat16"): what COMPUTING in the stated
              precision costs, which is what the served program does;
  fp8         held in float8_e4m3, the nearest precision below the stated
              one.

Each held choice is then cut into the requests the cell scores (the
traffic file's four warm-up samples at their positions, and four window
requests of the mix's median output after prompts spread over what fits
the reference's length) and put through checks_kanana.served_tokens: both
bf16 readings have to come out correct and the fp8 one NOT correct. The
exit code says whether they did.

    chiprun -- python3 benchmark/hold_kanana.py <seed> [<seed> ...]

writes chiprun_out/hold_kanana.json. (tests/test_kernel_cost_kanana.py runs
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

from benchmark import checks_kanana  # noqa: E402
from benchmark import reference_kanana as ref  # noqa: E402
from benchmark.runners import serve_kanana  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "kanana2-30b-a3b-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "context-latent.json")
HOLDS = {"bf16": (jnp.bfloat16, "highest"),
         "bf16_matmul": (jnp.bfloat16, "bfloat16"),
         "fp8": (jnp.float8_e4m3fn, "highest")}
MUST_PASS = {"bf16": True, "bf16_matmul": True, "fp8": False}


def requests_of(mix: dict, length: int) -> dict:
    """{group: [(first scored position, tokens)]}: the cell's scored
    requests laid on one sequence of ``length`` positions."""
    warm = [(s["prompt"] - 1, s["max_tokens"]) for s in mix["sample"]
            if s["prompt"] + s["max_tokens"] <= length]
    n, out = mix["score_in_window"], int(mix["output"]["median"])
    lo, hi = mix["prompt"]["min"], length - out
    window = [(lo + i * (hi - lo) // max(n - 1, 1) - 1, out)
              for i in range(n)] if hi > lo else [(length // 2, length // 4)]
    return {"warm-up": warm, "window": window}


def as_groups(cuts: dict, held, top, gap) -> dict:
    """The held choice as served_tokens' samples."""
    return {group: [{"served": held[a:a + n].tolist(),
                     "reference_tokens": top[a:a + n].tolist(),
                     "gap": gap[a:a + n].tolist(), "max_tokens": n}
                    for a, n in spans] for group, spans in cuts.items()}


def readings(seed: int, cfg, dims, mix: dict, length: int) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length), jnp.int32)
    top, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims)
    top = np.asarray(top)
    cuts = requests_of(mix, length)
    row = {"seed": seed, "length": length}
    for name, (hold, precision) in HOLDS.items():
        held, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims,
                                   hold, precision)
        # the held choice, scored by the float32 reference
        _, gap = ref.token_scores(params, toks, held, dims)
        held, gap = np.asarray(held), np.asarray(gap)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_kanana.served_tokens(groups)
        same = held == top
        row[name] = {
            "equal": float(same.mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "equal_by_eighth": [float(part.mean())
                                for part in np.array_split(same, 8)],
            "groups": {g: checks_kanana.shares(s)
                       for g, s in groups.items()},
            "requests": checks_kanana.request_shares(groups),
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_kanana.model_fields(config))
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
           "limits": {k: getattr(checks_kanana, k) for k in (
               "MIN_EQUAL", "MAX_OVER", "MAX_OVER_1", "MIN_EQUAL_REQUEST")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_kanana.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
