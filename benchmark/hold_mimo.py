"""The readings that checks_mimo.py's limits lie between, taken on the chip
at the published widths, and the limits tried on them.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids (from the held rows of the vocabulary) of the cell's
reference length, and the float32 reference's (reference_mimo.py) own
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
              (reference_mimo.FAULTS): the sink, the window, the window off
              by one, the value scale, the two rotary bases swapped, the
              rotary embedding over all 192 values.

Each held choice is then cut into the requests the cell scores (the traffic
file's four warm-up samples at their positions, and four window requests of
the mix's median output after prompts spread over what fits the reference's
length) and put through checks_mimo.served_tokens: both bf16 readings have
to come out correct and every other one NOT correct (but a window off by
one, which is reported: MUST_PASS). The exit code says whether they did;
the table (which fault fails which limit) goes into PERF.md.

    chiprun -- python3 benchmark/hold_mimo.py <seed> [<seed> ...]

writes chiprun_out/hold_mimo.json. (tests/test_kernel_cost_mimo.py runs
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

from benchmark import checks_mimo  # noqa: E402
from benchmark import reference_mimo as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_mimo  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "mimo-v2-flash-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "context-window.json")
#: name -> (held in, matmul precision, fault)
HOLDS = {"bf16": (jnp.bfloat16, "highest", None),
         "bf16_matmul": (jnp.bfloat16, "bfloat16", None),
         "fp8": (jnp.float8_e4m3fn, "highest", None),
         **{fault: (jnp.bfloat16, "bfloat16", fault)
            for fault in ref.FAULTS}}
#: what each reading has to come out as; None: reported, not required (a
#: window off by one is inside the rounding's own range on served tokens:
#: checks_mimo.py says where it is held instead)
MUST_PASS = {name: None if name == "window_off_by_one"
             else name in ("bf16", "bf16_matmul") for name in HOLDS}


def sink_mass(params, toks, dims) -> dict:
    """The sink's share of a row's softmax mass in the first window layer
    on the seeded weights, over the rows with a full window and over a
    sequence's first 16 tokens (min, mean, max over heads and rows): what
    the seeded range [3, 6] was chosen by (the configuration file keeps the
    reading)."""
    H, _, G, dk, _, r, _, theta, W = dims[:9]
    f32 = jnp.float32
    lp = {k: a[0].astype(f32)
          for k, a in params["layers"]["attn_window"].items()}
    n = min(toks.shape[0], 4 * W)
    with jax.default_matmul_precision("highest"):
        # layer 1's input is not layer 0's output here: the embedding's
        # rows, normed, have the same spread, which is all the reading uses
        x = params["embed"][toks[:n]].astype(f32)
        z = ref._rmsnorm(x, lp["attn_norm"], dims[10])
        q = ref.rope_leading((z @ lp["wq"]).reshape(n, H, dk), theta, r)
        k = ref.rope_leading((z @ lp["wk"]).reshape(n, G, dk), theta, r)
        k = jnp.repeat(k, H // G, axis=1)
        a = jnp.einsum("thd,shd->hts", q, k) * dk ** -0.5
        t, s = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
        a = jnp.where((s <= t) & (t - s < W), a, -jnp.inf)
        sink = lp["sink"][:, None]
        m = jnp.maximum(a.max(axis=-1), sink)
        share = jnp.exp(sink - m) / (
            jnp.exp(a - m[..., None]).sum(axis=-1) + jnp.exp(sink - m))
    full, first = np.asarray(share[:, W:]), np.asarray(share[:, :16])
    return {"full_window": [float(full.min()), float(full.mean()),
                            float(full.max())],
            "first_16_tokens": [float(first.min()), float(first.mean()),
                                float(first.max())]}


def readings(seed: int, cfg, dims, mix: dict, length: int,
             holds=HOLDS) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length), jnp.int32)
    top, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims)
    top = np.asarray(top)
    cuts = requests_of(mix, length)
    row = {"seed": seed, "length": length,
           "sink_mass": sink_mass(params, toks, dims)}
    for name, (hold, precision, fault) in holds.items():
        held, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims,
                                   hold, precision, fault)
        # the held choice, scored by the float32 reference
        _, gap = ref.token_scores(params, toks, held, dims)
        held, gap = np.asarray(held), np.asarray(gap)
        groups = as_groups(cuts, held, top, gap)
        faults = checks_mimo.served_tokens(groups)
        same = held == top
        row[name] = {
            "equal": float(same.mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "groups": {g: checks_mimo.shares(s) for g, s in groups.items()},
            "requests": checks_mimo.request_shares(groups),
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_mimo.model_fields(config))
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
           "limits": {k: getattr(checks_mimo, k) for k in (
               "MIN_EQUAL", "MAX_OVER", "MIN_EQUAL_REQUEST")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_mimo.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
