"""The readings that checks_gigachat.py's limits lie between, taken on the
chip at the published widths, and the limits tried on them: hold_trinity.py's
method.

For each seed: seeded weights as a replica draws them, a sequence of
uniform token ids (over the 16032 held rows of the vocabulary) of the cell's
reference length, and the float32 reference's (reference_gigachat.py) own
greedy choice at every position. Against that choice, the same reference

  bf16         weights and the values between operators HELD in bf16,
               arithmetic float32 at the highest precision, the recurrent
               state float32: what merely storing in the stated precision
               costs;
  bf16_matmul  the same, and every matmul's operands rounded to bf16 too
               (matmul precision "bfloat16"): what COMPUTING in the stated
               precision costs, which is what the served program does
               around its float32 recurrence;
  state_bf16   bf16_matmul with the recurrent STATE held in bf16 as well,
               rounded after every token (lax.reduce_precision: a cast and
               back is a pair the TPU compiler removes): ISSUE 55's control.
               Its TOKENS read inside the served path's own range; it comes
               out NOT correct by the state's own limit
               (checks_gigachat.MAX_STATE_ERROR);
  fp8          held in float8_e4m3 (state float32): the nearest precision
               below the stated one for everything else;
  <fault>      computed as bf16_matmul with ONE part of the block left out
               (reference_gigachat.FAULTS): the delta rule's read, its
               decay, the YaRN frequencies and scale, the latent layer's
               output gate, the sigmoid in the norms.

Each held choice is then cut into the requests the cell scores (the traffic
file's four warm-up samples at their positions, and four window requests of
the mix's median output after prompts spread over what fits the reference's
length), given the state reading `correct` takes of every scored request
(the first layer's recurrence over the head of the request with the state
held as the reading holds it, against the same recurrence in float32 on the
same inputs: 0 where the state is float32), and put through
checks_gigachat.served_tokens: both bf16 readings have to come out correct
and every other one NOT correct, but the one that is reported (MUST_PASS).
Beside them `served_state`: the PROGRAM's two forms of the recurrence
(replica_gigachat.served_state, as a replica's reference check runs them)
on the same requests, which has to pass that limit. The exit code says
whether they did; the table goes into PERF.md.

    chiprun -- python3 benchmark/hold_gigachat.py <seed> [<seed> ...]

writes chiprun_out/hold_gigachat.json. (tests/test_kernel_cost_gigachat.py
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

from benchmark import checks_gigachat  # noqa: E402
from benchmark import reference_gigachat as ref  # noqa: E402
from benchmark.hold_kanana import as_groups, requests_of  # noqa: E402
from benchmark.runners import serve_gigachat  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "gigachat35-432b-a28b-serve-1chip.json")
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic", "reason-delta.json")
#: name -> (held in, matmul precision, the state held in, fault)
HOLDS = {"bf16": (jnp.bfloat16, "highest", None, None),
         "bf16_matmul": (jnp.bfloat16, "bfloat16", None, None),
         "state_bf16": (jnp.bfloat16, "bfloat16", jnp.bfloat16, None),
         "fp8": (jnp.float8_e4m3fn, "highest", None, None),
         **{fault: (jnp.bfloat16, "bfloat16", None, fault)
            for fault in ref.FAULTS}}
#: what each reading has to come out as; None: reported, not required (the
#: YaRN frequencies left out read inside the served path's own range:
#: checks_gigachat.py says where they are held instead)
MUST_PASS = {name: None if name == "no_yarn"
             else name in ("bf16", "bf16_matmul") for name in HOLDS}


def state_readings(cfg, dims, params, toks, cuts: dict, state_holds,
                   row: int) -> dict:
    """{state_hold or "served": {group: [state error of each request]}}:
    what a replica's reference check reads of a scored request
    (replica_gigachat.ProbedGigaChatServer), the request being positions
    a + 1 .. a + n served after the prompt toks[:a + 1]."""
    from benchmark.replica_gigachat import served_state
    n_p, n_g = checks_gigachat.STATE_TOKENS
    out = {how: {g: [] for g in cuts} for how in (*state_holds, "served")}
    impl = None if jax.default_backend() == "tpu" else "reference"
    for group, spans in cuts.items():
        for a, n in spans:
            head = np.concatenate([toks[:a + 1][:n_p],
                                   toks[a + 1:a + 1 + min(n, n_g)]])
            padded = np.zeros((n_p + n_g,), np.int32)
            padded[:len(head)] = head
            inputs, want = ref.first_layer_state(params, padded, len(head),
                                                 dims)
            for hold in state_holds:
                _, held = ref.first_layer_state(params, padded, len(head),
                                                dims, hold)
                out[hold][group].append(ref.state_error(held, want))
            got = served_state(cfg, inputs, min(a + 1, n_p), len(head), row,
                               impl)
            out["served"][group].append(ref.state_error(got, want))
    return out


def readings(seed: int, cfg, dims, mix: dict, length: int,
             holds=HOLDS, chunk_row: int = 512) -> dict:
    from ray_tpu.llm.model import _init_params
    params = _init_params(cfg, jax.random.PRNGKey(seed % 2 ** 31))
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length) \
        .astype(np.int32)
    cuts = requests_of(mix, length)
    states = state_readings(
        cfg, dims, params, toks, cuts,
        {h[2] for h in holds.values() if h[2] is not None}, chunk_row)
    toks = jnp.asarray(toks)
    top, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims)
    top = np.asarray(top)
    row = {"seed": seed, "length": length,
           "served_state": {
               "state_error": states["served"],
               "correct": all(e <= checks_gigachat.MAX_STATE_ERROR
                              for g in states["served"].values()
                              for e in g)}}
    for name, (hold, precision, state_hold, fault) in holds.items():
        held, _ = ref.token_scores(params, toks, jnp.roll(toks, -1), dims,
                                   hold, precision, fault, state_hold)
        # the held choice, scored by the float32 reference
        _, gap = ref.token_scores(params, toks, held, dims)
        held, gap = np.asarray(held), np.asarray(gap)
        groups = as_groups(cuts, held, top, gap)
        for group, samples in groups.items():
            for i, s in enumerate(samples):
                s["state_error"] = 0.0 if state_hold is None \
                    else states[state_hold][group][i]
        faults = checks_gigachat.served_tokens(groups)
        same = held == top
        row[name] = {
            "equal": float(same.mean()), "worst": float(gap.max()),
            "over_checks_tol": float((gap > 0.12).mean()),
            "over_1.0": float((gap > 1.0).mean()),
            "equal_by_eighth": [float(part.mean())
                                for part in np.array_split(same, 8)],
            "groups": {g: checks_gigachat.shares(s)
                       for g, s in groups.items()},
            "requests": checks_gigachat.request_shares(groups),
            "state_error": {g: [s["state_error"] for s in samples]
                            for g, samples in groups.items()},
            "correct": not faults, "faults": faults}
    return row


def main(argv) -> int:
    config = json.load(open(CONFIG))
    mix = json.load(open(TRAFFIC))
    length = mix["reference_pad_to"]
    from ray_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.tiny(**serve_gigachat.model_fields(config))
    dims = ref.dims_of(cfg)
    rows, wrong = [], []
    for seed in [int(a) for a in argv] or [0]:
        t0 = time.time()
        row = readings(seed, cfg, dims, mix, length,
                       chunk_row=config["engine"]["prefill_chunk"])
        row["seconds"] = time.time() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
        wrong += [f"seed {seed}: {name} came out "
                  f"{'correct' if row[name]['correct'] else 'NOT correct'}"
                  for name, want in {**MUST_PASS,
                                     "served_state": True}.items()
                  if want is not None and row[name]["correct"] != want]
    out = {"device": jax.devices()[0].device_kind,
           "limits": {k: getattr(checks_gigachat, k) for k in (
               "MIN_EQUAL", "MAX_OVER", "MIN_EQUAL_REQUEST",
               "MAX_STATE_ERROR")},
           "rows": rows, "wrong": wrong}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "hold_gigachat.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
