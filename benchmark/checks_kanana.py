"""The limits of the comparison that decides `correct` for the Kanana-2
block (checks.served_tokens' method, this block's own numbers; every other
check of a run is checks.py's, unchanged).

Why this block cannot be held to checks.py's limits (0.12 logits a token,
90 % of tokens equal), nor to checks_lfm2.py's: its router renormalises
the 6 chosen of 128 experts' sigmoid scores and multiplies them by 2.448,
so each chosen expert carries about 0.4 of a routed layer's output, and
with seeded (random) weights the 6th and 7th candidate are ~0.011 apart.
The rounding of bf16 is enough to swap them: the float32 reference itself,
with its weights and the values between operators merely HELD in bf16
(reference_kanana.hidden(hold=bfloat16), arithmetic still float32), gives
68.4-70.4 % of its float32 self's greedy tokens, 22.0-24.0 % of them more
than 0.12 logits under, 5.8-6.4 % more than 1.0, the worst 3.90-4.29 (my
chip runs, PR 33, 3 seeds x 4608 tokens at the published widths). A token
the top of 128256 unit-variance logits sits ~4.4 above the mean, so a
flipped token's gap of 4 is as large as a gap can be: **this block brings
no per-token limit**. The served path's worst token read 3.11-4.74 over its
runs and the reference held in fp8 4.88-5.25: no limit lies between them
with room on both sides, so the worst gap is reported (notes.scored_gaps)
and must only be finite.

What takes the per-token limit's place: the three shares below are held
three times over. On all scored tokens together; on each GROUP by itself
(the warm-up group is the only traffic that takes the prefix hit, the copy
on write and the 4500-token prompt, and is a sixth of the tokens: pooled,
it could be wholly wrong and pass); and the equal share on each REQUEST by
itself (a slot's page table, a stale latent page: one request of eight
wholly wrong reads ~0 % equal and moves the pool by an eighth).

Each limit lies between two readings on the chip (PERF.md, PR 33): the
worst the served path gave over its seeds, and what the reference gives
held in the nearest precision below bf16, float8_e4m3, which comes out as
NOT correct by every one of them (benchmark/hold_kanana.py takes the
readings and puts the held tokens through served_tokens below: 17 faults
of 17 on each of 3 seeds).

Why the served path reads a little under the reference held in bf16
(0.599-0.645 equal against 0.684-0.704): the held reference still computes
in float32 INSIDE its operators. With every matmul's operands rounded to
bf16 as well (hold_kanana.py's `bf16_matmul`), which is what the program
does, it reads 0.637-0.663 equal, 0.263-0.275 over 0.12, 0.073-0.078 over
1.0: the served path's range within two points (the absorbed products
round q~ and o~ once more, and the program's elementwise work is in bf16).
"""

from __future__ import annotations

import math
from typing import Dict, List

from benchmark import checks
from benchmark.checks_lfm2 import gap_summary   # the same summary

#: share of served tokens equal to the reference's, of all scored tokens
#: and of each group's. Served: all tokens 0.599-0.645 over 21 runs, the
#: warm-up group (480 tokens: 0.022 a standard deviation) 0.588-0.642 and
#: the window group 0.616-0.649 over the 6 runs that report them; the
#: reference held in bf16 with bf16 matmuls 0.637-0.663 (groups
#: 0.604-0.683); held in fp8 0.029-0.034 (groups 0.015-0.042)
MIN_EQUAL = 0.50
#: share more than checks.LOGIT_TOL (0.12) under the reference's choice:
#: the flipped ones. Served 0.274-0.318 (groups 0.256-0.327); held in fp8
#: 0.953-0.959 (groups 0.944-0.969)
MAX_OVER = 0.45
#: ... and more than 1.0 under: flipped by a whole logit. Served
#: 0.074-0.100 (groups 0.062-0.099); held in fp8 0.737-0.764 (groups
#: 0.702-0.781)
MAX_OVER_1 = 0.20
#: the equal share of ONE request of at least MIN_REQUEST_TOKENS tokens
#: (the warm-up's are 96-128 by the traffic file; a window request is
#: whatever the seed dealt, down to a client's opener of a few tokens,
#: which is held in its group and in the pool only). Served 0.508-0.690
#: over 48 requests of 88-1081 tokens (96 tokens stray 0.05 a standard
#: deviation, and a short context reads lower than a long one); held in
#: fp8 0.000-0.086 over 24; a request served from another's pages reads
#: what chance gives, 0.00
MIN_EQUAL_REQUEST = 0.25
MIN_REQUEST_TOKENS = 64


def shares(samples: List[Dict]) -> Dict:
    """Of the samples' tokens together: how many, and the shares that are
    equal to the reference's, more than checks.LOGIT_TOL under its choice
    and more than 1.0 under."""
    gaps = [g for s in samples for g in s["gap"]]
    n = max(len(gaps), 1)
    return {"tokens": len(gaps),
            "equal": checks.score_summary(samples)["equal"] / n,
            "over_checks_tol": sum(g > checks.LOGIT_TOL for g in gaps) / n,
            "over_1.0": sum(g > 1.0 for g in gaps) / n}


def request_shares(groups: Dict[str, List[Dict]]) -> List[Dict]:
    """shares() of every scored request, with its group: what the
    per-request limit is held against (a run's notes keep it)."""
    return [{"group": group, **shares([s])}
            for group, samples in groups.items() for s in samples]


def _held_to_limits(what: str, got: Dict) -> List[str]:
    bad = []
    if got["equal"] < MIN_EQUAL:
        bad.append(f"only {got['equal']:.1%} of {what} ({got['tokens']}) "
                   f"equal the reference's (want {MIN_EQUAL:.0%})")
    if got["over_checks_tol"] > MAX_OVER:
        bad.append(f"{got['over_checks_tol']:.1%} of {what} sit more than "
                   f"{checks.LOGIT_TOL} logits under the reference's "
                   f"choice (at most {MAX_OVER:.0%})")
    if got["over_1.0"] > MAX_OVER_1:
        bad.append(f"{got['over_1.0']:.1%} of {what} sit more than 1.0 "
                   f"logits under the reference's choice (at most "
                   f"{MAX_OVER_1:.0%})")
    return bad


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """checks.served_tokens with this block's limits: every group holds a
    request that returned all its tokens and whose gaps are finite; at
    least MIN_EQUAL of the tokens are equal, at most MAX_OVER sit more than
    checks.LOGIT_TOL under the reference's choice and at most MAX_OVER_1
    more than 1.0, of all scored tokens AND of each group's; and of each
    request's tokens (MIN_REQUEST_TOKENS or more) at least
    MIN_EQUAL_REQUEST are equal."""
    bad = []
    for group, samples in groups.items():
        if not samples:
            bad.append(f"no {group} request was scored against the "
                       f"reference")
            continue
        for i, s in enumerate(samples):
            if len(s["served"]) != s["max_tokens"]:
                bad.append(f"{group} sample {i}: {len(s['served'])} tokens "
                           f"served, {s['max_tokens']} asked")
            equal = shares([s])["equal"]
            if len(s["gap"]) >= MIN_REQUEST_TOKENS \
                    and equal < MIN_EQUAL_REQUEST:
                bad.append(f"{group} sample {i}: only {equal:.1%} of its "
                           f"{len(s['gap'])} tokens equal the reference's "
                           f"(want {MIN_EQUAL_REQUEST:.0%} of a request)")
        worst = checks.score_summary(samples)["worst_gap"]
        if worst is None or not math.isfinite(worst):
            bad.append(f"a {group} token's gap to the reference's choice "
                       f"is {worst}")
        bad += _held_to_limits(f"the {group} tokens", shares(samples))
    return bad + _held_to_limits(
        "all served tokens", shares([s for g in groups.values() for s in g]))
