"""Traffic generation: one general generator, every mix a data file.

A mix (benchmark/traffic/<mix>.json) names a `kind` and its parameters; the
functions here turn (mix, seed, seconds) into the work a run offers. The
rule that makes runs comparable: **the seed permutes, it does not resample**.
Lengths are the quantiles of the mix's distributions, a fixed multiset for a
given request count, and an open loop's due times are the same in every run;
`--seed` decides which prompt length meets which output length and in what
order they come (two permutations), and draws the token ids (and, in the
program, the weights). At some fifty requests a window the order alone moves
a result by a few percent (PERF.md, PR 23): the runs of a set sample that, and
the bounds are set from it. A mix that froze one order read far steadier, and
hid what a change to the program's timing would re-roll.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def lognormal_quantiles(n: int, spec: Dict) -> List[int]:
    """The n quantiles at (i+0.5)/n of lognormal(median, sigma), clipped to
    [min, max] and rounded: a fixed multiset, no sampling."""
    inv = NormalDist().inv_cdf
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * inv((i + 0.5) / n))
        out.append(int(round(min(spec["max"], max(spec["min"], x)))))
    return out


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    """Uniform token ids; nothing shares a prefix (16 equal ids in a row at
    the start of two prompts has probability vocab**-16)."""
    return rng.integers(0, vocab, size=n, dtype=np.int64).tolist()


def _requests(prompt_lens, output_lens, seed: int, vocab: int, salt: int):
    """Pair and order the two multisets by two permutations drawn from the
    seed; make the token ids from it too."""
    py = random.Random(seed * 1000003 + salt)
    p, o = list(prompt_lens), list(output_lens)
    py.shuffle(p)
    py.shuffle(o)
    rng = np.random.default_rng([seed, salt])
    return [{"prompt": _tokens(rng, pl, vocab), "max_tokens": ol}
            for pl, ol in zip(p, o)]


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    """Independent users: N = round(rate * seconds) requests due inside the
    window, request k in the middle of its own slot [k/rate, (k+1)/rate):
    every window offers exactly N at the same N due times, and the seed
    decides only which request comes when. Before them a lead-in of the
    same schedule, not measured."""
    rate = float(mix["rate"])
    n = int(round(rate * seconds))
    n_lead = int(round(rate * mix["lead_in_s"]))
    reqs = _requests(lognormal_quantiles(n, mix["prompt"]),
                     lognormal_quantiles(n, mix["output"]),
                     seed, vocab, salt=1)
    for k, r in enumerate(reqs):
        r.update(due=(k + 0.5) / rate, measured=True)
    lead = _requests(lognormal_quantiles(n_lead, mix["prompt"]),
                     lognormal_quantiles(n_lead, mix["output"]),
                     seed, vocab, salt=2)
    for k, r in enumerate(lead):
        r.update(due=(k + 0.5 - n_lead) / rate, measured=False)
    return {"kind": "open_loop", "requests": lead + reqs,
            "lead_in_s": n_lead / rate, "n_measured": n}


def closed_loop(mix: Dict, seed: int, clients: int, vocab: int) -> Dict:
    """Callers that each wait for their reply: `clients` clients, a fixed
    multiset of per_client * clients requests dealt out by the seed. Before
    them each client sends one opening request from a second fixed set,
    whose output lengths are the stratified fractions (j+0.5)/clients of
    the median output — dealt by the seed — so the clients are out of phase
    when the window opens."""
    n = int(mix["per_client"]) * clients
    reqs = _requests(lognormal_quantiles(n, mix["prompt"]),
                     lognormal_quantiles(n, mix["output"]),
                     seed, vocab, salt=3)
    opening = _requests(
        lognormal_quantiles(clients, mix["prompt"]),
        [max(1, int(round(mix["output"]["median"] * (j + 0.5) / clients)))
         for j in range(clients)], seed, vocab, salt=7)
    queues = [[opening[j]] + reqs[j::clients] for j in range(clients)]
    return {"kind": "closed_loop", "queues": queues,
            "lead_in_s": float(mix["lead_in_s"])}


def sample_requests(mix: Dict, seed: int, vocab: int) -> List[Dict]:
    """The requests sent before the window: they warm the step programs
    and are the ones whose tokens the plain reference scores. Their lengths
    are the mix's `sample` (fixed); the seed makes the ids."""
    rng = np.random.default_rng([seed, 4])
    return [{"prompt": _tokens(rng, s["prompt"], vocab),
             "max_tokens": s["max_tokens"]} for s in mix["sample"]]


def shared_prefix_pair(mix: Dict, seed: int, vocab: int,
                       page_size: int) -> List[Dict]:
    """Two requests, the second the first's prompt again: a page-aligned
    full hit, so the engine copies a page on write — the one program no
    other warm-up request reaches."""
    rng = np.random.default_rng([seed, 5])
    prompt = _tokens(rng, 2 * page_size, vocab)
    return [{"prompt": prompt, "max_tokens": 2} for _ in range(2)]


def train_batches(mix: Dict, seed: int, vocab: int) -> np.ndarray:
    """[n_distinct, rows, seq_len] int32 token rows from the seed; the job
    cycles through them. Full rows: the trainer has no document packing
    (models/llama.py loss_fn takes [B, L] tokens and no segment ids)."""
    rng = np.random.default_rng([seed, 6])
    return rng.integers(0, vocab, size=(mix["distinct_batches"], mix["rows"],
                                        mix["seq_len"]), dtype=np.int32)


def offered_work(plan: Dict) -> Dict:
    """What a plan offers, for the selftest and the result line: request
    count and the sorted multisets of prompt and output lengths."""
    if plan["kind"] == "open_loop":
        reqs = [r for r in plan["requests"] if r["measured"]]
        return {"n": len(reqs),
                "prompt_lens": sorted(len(r["prompt"]) for r in reqs),
                "output_lens": sorted(r["max_tokens"] for r in reqs)}
    reqs = [r for q in plan["queues"] for r in q]
    return {"n": len(reqs),
            "prompt_lens": sorted(len(r["prompt"]) for r in reqs),
            "output_lens": sorted(r["max_tokens"] for r in reqs)}
