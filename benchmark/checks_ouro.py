"""The limits of the comparison that decides `correct` for the Ouro block
(a looped stack: 48 layers walked four times, the final norm between the
passes): checks_phi4flash.served_tokens' method with limits of this block's
own (every other check of a run is checks.py's, unchanged).

Why checks.py's limits (no token more than 0.12 logits under the
reference's choice, 90 % of tokens equal) are not this block's, by a long
way: under SEEDED weights the looped stack amplifies rounding. A token's
stream passes 192 layers' worth of operators before the head, every branch
re-normed to unit size before it joins the stream (so a branch's rounding
is never small beside it) and the whole stream re-normed between the
passes; the float32 reference merely HELD in bf16 moves its own logits by
a quarter of a logit (p99 0.6) and keeps 36-63 % of its own greedy choices
over 49152 unit-variance logits, with bf16 matmuls 35 %; the served path
keeps 53-56 %. What tells a served path from a wrong one is therefore not
the share of equal tokens but HOW FAR under the reference's choice a token
sits: rounding costs tenths of a logit and hardly ever a whole one (0-4
tokens of a run's 1000-2121 sit more than 1.0 under), and every fault tried
costs whole logits on every token.

Each limit lies between two readings on the chip at the published widths
(PERF.md, PR 66; benchmark/hold_ouro.py takes the held ones, two seeds):
the worst the served path and the bf16 holds gave, and what the controls
give, each of which comes out NOT correct by EVERY limit on every group and
request: the reference
held in float8_e4m3 (the nearest precision below the stated one), passes
2-4 attending over pass 1's keys and values (a pool of one plane a layer),
and the final norm applied once, after the last pass only.
"""

from __future__ import annotations

import math
from typing import Dict, List

from benchmark import checks
from benchmark.checks_kanana import request_shares, shares  # noqa: F401
from benchmark.checks_lfm2 import gap_summary   # noqa: F401 — the same

#: share of tokens more than 1.0 logits under the reference's choice, of all
#: scored tokens and of each group's: THE limit of this block. Served (my
#: chip runs, PR 66, sixteen runs): 0-0.003 (0-4 tokens of 1000-2121 a
#: run); the reference held in bf16 0-0.0013, with bf16 matmuls
#: 0.0013-0.0029; held in fp8 0.990-1.0 a group; passes sharing pass 1's
#: planes 0.9985-1.0; the norm applied once 0.999-1.0
MAX_OVER_1 = 0.40
#: ... and of ONE request of at least MIN_REQUEST_TOKENS tokens: a request
#: served from another's pages or another pass's planes reads what chance
#: gives, whole logits under on every token. Served 0-0.125 (2 of a
#: 16-token request; 0.008 of one of 500); held in bf16 0-0.004; the
#: controls 0.938-1.0
MAX_OVER_1_REQUEST = 0.50
MIN_REQUEST_TOKENS = 16
#: share of ALL scored tokens equal to the reference's. Not held a group or
#: a request: a greedy stream from seeded weights settles into a pattern,
#: and a request whose pattern sits on a near-tie reads 0.0 of 32 tokens
#: equal (none of them 1.0 under) while the next reads 0.998 of 410, so a
#: group of four requests swings 0.32-0.76. Served 0.35-0.75 of a run's
#: tokens; held in bf16 0.358-0.626, with bf16 matmuls 0.350-0.355; every
#: control 0.000 on every request
MIN_EQUAL = 0.10
#: share of all scored tokens more than checks.LOGIT_TOL (0.12) under the
#: reference's choice. Served 0.18-0.46 of a run's tokens (a request: up to
#: 0.94); held in bf16 0.236-0.505, with bf16 matmuls 0.529-0.531; every
#: control 1.000
MAX_OVER = 0.82
#: a served token's logit under the reference's top choice. Served: worst
#: 0.66-1.40 a run of 1000-2121 tokens (p99 0.54-0.79); held in bf16
#: 0.59-1.34 over 768; the controls' WORST 6.2-7.1, and their every token
#: over 1.0
MAX_GAP = 3.5


def _far_under(what: str, got: Dict) -> List[str]:
    if got["over_1.0"] <= MAX_OVER_1:
        return []
    return [f"{got['over_1.0']:.1%} of {what} ({got['tokens']}) sit more "
            f"than 1.0 logits under the reference's choice (at most "
            f"{MAX_OVER_1:.0%})"]


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """The faults of the scored requests, {group name: samples}; [] means
    correct: every group holds a request that returned all its tokens; no
    token sits more than MAX_GAP under the reference's choice; at most
    MAX_OVER_1 of all scored tokens AND of each group's sit more than 1.0
    under, and at most MAX_OVER_1_REQUEST of each request's
    (MIN_REQUEST_TOKENS or more); of all scored tokens at least MIN_EQUAL
    are equal and at most MAX_OVER sit more than checks.LOGIT_TOL under."""
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
            far = shares([s])["over_1.0"]
            if len(s["gap"]) >= MIN_REQUEST_TOKENS \
                    and far > MAX_OVER_1_REQUEST:
                bad.append(f"{group} sample {i}: {far:.1%} of its "
                           f"{len(s['gap'])} tokens sit more than 1.0 "
                           f"logits under the reference's choice (at most "
                           f"{MAX_OVER_1_REQUEST:.0%} of a request)")
        worst = checks.score_summary(samples)["worst_gap"]
        if worst is None or not math.isfinite(worst) or worst > MAX_GAP:
            bad.append(f"a {group} token sits {worst} logits under the "
                       f"reference's choice (at most {MAX_GAP})")
        bad += _far_under(f"the {group} tokens", shares(samples))
    got = shares([s for g in groups.values() for s in g])
    bad += _far_under("all served tokens", got)
    if got["equal"] < MIN_EQUAL:
        bad.append(f"only {got['equal']:.1%} of all served tokens "
                   f"({got['tokens']}) equal the reference's (want "
                   f"{MIN_EQUAL:.0%})")
    if got["over_checks_tol"] > MAX_OVER:
        bad.append(f"{got['over_checks_tol']:.1%} of all served tokens sit "
                   f"more than {checks.LOGIT_TOL} logits under the "
                   f"reference's choice (at most {MAX_OVER:.0%})")
    return bad
