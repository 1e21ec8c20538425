"""The limits of the comparison that decides `correct` for the Trinity-Mini
block: checks_mimo.served_tokens' method with limits of this block's own
(every other check of a run is checks.py's, unchanged).

Why checks.py's limits (no token more than 0.12 logits under the
reference's choice, 90 % of tokens equal) cannot hold here, as they cannot
for the other sigmoid-routed blocks: the router renormalises the 8 chosen of
128 experts' sigmoid scores, and with seeded (random) weights the 8th and
9th candidate lie closer together than the rounding of bf16 moves them, so
a served token's set of experts differs from the float32 reference's in
some layer for a share of the tokens, and the swapped expert's whole output
comes or goes (times 2.826 / 8). The greedy choice over 200192
unit-variance logits (ten times the rows of any other cell's head: the
first and the second candidate lie closer) then flips for a share of the
tokens, by a gap as large as a gap can be: this block brings no per-token
limit (the worst gap is reported, notes.scored_gaps, and must be finite),
and holds two shares three times over, as checks_mimo.py does and for its
reasons: on all scored tokens, on each group, and the equal share on each
request.

Each limit lies between two readings on the chip at the published widths
(PERF.md, PR 49; benchmark/hold_trinity.py takes the held ones): the worst
the served path gave over its seeds, and what the reference gives held in
float8_e4m3, the nearest precision below bf16, or with ONE part of the
block left out or done wrong (reference_trinity.FAULTS: the gate, the q/k
norm, a full layer rotated, a window layer not rotated, the window, a norm
after a branch, the routing scale, the shared expert, the embedding's
multiplier). Held in fp8 it comes out NOT correct by both shares on every
group and request; each fault by at least one limit (the nearest, a full
layer rotated: 0.710-0.769 of the window group equal, some requests of
eight under 0.70). A window off by one (2049 positions for 2048) moves one key in
two thousand and reads 0.827-0.867 equal, inside the served path's own
range: no limit on served tokens tells it from rounding; it is reported,
and held where logits are compared, at 1e-4 in float32
(tests/test_llm_trinity.py: the window's width as a field, one more and one
fewer).
"""

from __future__ import annotations

import math
from typing import Dict, List

from benchmark import checks
from benchmark.checks_kanana import request_shares, shares  # noqa: F401
from benchmark.checks_lfm2 import gap_summary   # noqa: F401 — the same

#: share of served tokens equal to the reference's, of all scored tokens
#: and of each group's. Served (my chip runs, PR 49; PERF.md has the runs):
#: 0.853-0.909 over 16 runs x 2 groups (the warm-up group reads lowest: its
#: 100-token prompt alone 0.785-0.865); the reference held in bf16 with bf16
#: matmuls 0.887-0.916 over 3 seeds (held in bf16 alone 0.893-0.933); held
#: in fp8 0.401-0.417; a full layer rotated 0.710-0.793
MIN_EQUAL = 0.75
#: share more than checks.LOGIT_TOL (0.12) under the reference's choice: the
#: flipped ones. Served 0.035-0.081 of a group's tokens; held in bf16 with
#: bf16 matmuls 0.040-0.044; held in fp8 0.456-0.501
MAX_OVER = 0.20
#: the equal share of ONE request of at least MIN_REQUEST_TOKENS tokens (the
#: warm-up's are 128-200 by the traffic file; 200 tokens stray 0.025 a
#: standard deviation at 0.85): a request served from another's pages, or
#: through a compact table whose base is off by a page, reads what chance
#: gives. Served 0.785-0.953 over 120 requests; held in bf16 with bf16
#: matmuls 0.870-0.945 over 24 requests; held in fp8 0.359-0.455
MIN_EQUAL_REQUEST = 0.70
MIN_REQUEST_TOKENS = 64
#: NOT a limit of this block: the share more than 1.0 under. The served
#: path reads 0-1 token of ~4000 a run and the fp8 hold 0.015: no limit
#: lies between them with room. It is reported (notes.scored_gaps); the
#: faults below read 0.33-0.99 there but for the three that move little (a
#: full layer rotated, the q/k norm, the routing scale: 0.001-0.044)


def _held_to_limits(what: str, got: Dict) -> List[str]:
    bad = []
    if got["equal"] < MIN_EQUAL:
        bad.append(f"only {got['equal']:.1%} of {what} ({got['tokens']}) "
                   f"equal the reference's (want {MIN_EQUAL:.0%})")
    if got["over_checks_tol"] > MAX_OVER:
        bad.append(f"{got['over_checks_tol']:.1%} of {what} sit more than "
                   f"{checks.LOGIT_TOL} logits under the reference's "
                   f"choice (at most {MAX_OVER:.0%})")
    return bad


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """The faults of the scored requests, {group name: samples}; [] means
    correct. checks_mimo.served_tokens' method with this block's limits:
    every group holds a request that returned all its tokens and whose gaps
    are finite; the two shares are held on all scored tokens AND on each
    group's; and of each request's tokens (MIN_REQUEST_TOKENS or more) at
    least MIN_EQUAL_REQUEST are equal."""
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
