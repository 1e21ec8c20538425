"""The two readings that checks_granite.py's tolerance lies between, taken
on the chip at the published widths, and the limits tried on them
(hold_kanana.py's method for the granite-4.0-h block).

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids of the cell's reference length, and the float32
reference's (reference_granite.py) own greedy choice at every position.
Against that choice, the same reference with its weights, the values
between operators and what the block caches (the conv's inputs, the matrix
state token after token) HELD in bf16, the stated precision, and in
float8_e4m3, the nearest below it; arithmetic float32 at the highest matmul
precision in both. Each held choice is cut into the requests the cell
scores (hold_kanana.requests_of) and put through
checks_granite.served_tokens: bf16 has to come out correct and fp8 NOT
correct. The exit code says whether they did.

    chiprun -- python3 benchmark/hold_granite.py <seed> [<seed> ...]

writes chiprun_out/hold_granite.json. (tests/test_kernel_cost_granite.py
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

from benchmark import checks, checks_granite  # noqa: E402
from benchmark import reference_granite as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_granite  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "granite4-h-micro-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "reason-ssm.json")
HOLDS = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}
MUST_PASS = {"bf16": True, "fp8": False}


@jax.jit
def _scores(logits, took):
    top2 = jax.lax.top_k(logits, 2)[0]
    at = jnp.take_along_axis(logits, took[:, None], axis=-1)[:, 0]
    return (jnp.argmax(logits, axis=-1), top2[:, 0] - at,
            logits.std(axis=-1).mean(), (top2[:, 0] - top2[:, 1]).mean())


def token_scores(params, tokens, took, dims, hold=None):
    """Per position of tokens [S]: the (held) reference's argmax, how far
    under its top logit the token ``took`` names sits, and the logits'
    standard deviation and top-2 margin (what a tolerance is read
    against)."""
    with jax.default_matmul_precision("highest"):
        return _scores(ref.forward(params, tokens, dims, hold), took)


def readings(seed: int, cfg, dims, mix: dict, length: int) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length), jnp.int32)
    top, _, std, margin = token_scores(params, toks, toks, dims)
    top = np.asarray(top)
    cuts = requests_of(mix, length)
    row = {"seed": seed, "length": length, "logits_std": float(std),
           "top2_margin": float(margin)}
    for name, hold in HOLDS.items():
        held = token_scores(params, toks, toks, dims, hold)[0]
        # the held choice, scored by the float32 reference
        gap = np.asarray(token_scores(params, toks, held, dims)[1])
        held = np.asarray(held)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_granite.served_tokens(groups)
        row[name] = {
            "equal": float((held == top).mean()), "worst": float(gap.max()),
            "over_block_tol": int((gap > checks_granite.LOGIT_TOL).sum()),
            "over_checks_tol": int((gap > checks.LOGIT_TOL).sum()),
            "groups": {g: checks.score_summary(s)
                       for g, s in groups.items()},
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_granite.model_fields(config))
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
           "limits": {"LOGIT_TOL": checks_granite.LOGIT_TOL,
                      "checks.LOGIT_TOL": checks.LOGIT_TOL,
                      "checks.MIN_EQUAL": checks.MIN_EQUAL},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_granite.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
