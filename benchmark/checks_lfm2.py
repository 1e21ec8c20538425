"""The limits of the comparison that decides `correct` for the LFM2 block
(checks.served_tokens' method, this block's own numbers; every other check
of a run is checks.py's, unchanged).

Why this block cannot be held to checks.py's limits (0.12 logits a token,
90 % of tokens equal): its router renormalises the 4 chosen experts'
sigmoid scores, so each carries about a quarter of a layer's feed-forward
output, and with seeded (random) weights the 4th and 5th candidate are
~0.13 logits apart. The rounding of bf16 is enough to swap them: the
float32 reference itself, with its weights and the values between
operators merely HELD in bf16 (reference_lfm2.forward(hold=bfloat16),
arithmetic still float32), disagrees with its float32 self on 3 % of a
layer's routing decisions at the first expert layer and 31 % at the
eighth (37 % at 1280 tokens), 50-62 % of tokens have a flip somewhere,
73-77 % of its greedy tokens are equal, its worst token sits 0.72-1.34
logits under, 11-15 % of tokens more than 0.12 (my chip runs, PR 30, 4
seeds x 768-1280 tokens). OLMoE's softmax
top-8 without renormalisation gives a flipped expert ~0.03 of the output,
which is why it passes checks.py's limits and this block, correct, cannot.

Each limit lies between two readings on the chip (PERF.md, PR 30): the
largest (smallest) the served path gave over its seeds, and what the
reference gives held in the nearest precision below bf16, float8_e4m3,
which comes out as NOT correct by every one of them.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import checks

#: a served token's logit under the reference's top choice. Served (bf16):
#: worst 0.78-1.47 over 15 runs x 1250-2700 scored tokens, 17 of ~28 k
#: tokens over 1.0 and none over 1.5; held in fp8: worst 2.72-3.24 over
#: 4 seeds x 768-1280 tokens
LOGIT_TOL = 2.0
#: ... and tokens that sit more than checks.LOGIT_TOL (0.12) under are the
#: flipped ones: served 0.12-0.18 of the scored tokens, fp8 0.80-0.82
MAX_OVER = 0.45
#: share of served tokens equal to the reference's. Served 0.70-0.75, fp8
#: 0.13-0.15
MIN_EQUAL = 0.45


def gap_summary(groups: Dict[str, List[Dict]]) -> Dict:
    """What the limits are held against, and the tail beside it."""
    gaps = sorted(g for samples in groups.values() for s in samples
                  for g in s["gap"])
    n = max(len(gaps), 1)
    return {"tokens": len(gaps),
            "over_checks_tol": sum(g > checks.LOGIT_TOL for g in gaps) / n,
            "over_1.0": sum(g > 1.0 for g in gaps),
            "over_1.5": sum(g > 1.5 for g in gaps),
            "p99": gaps[int(0.99 * (n - 1))] if gaps else None,
            "worst": gaps[-1] if gaps else None}


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """checks.served_tokens with this block's limits: every group holds a
    request that returned all its tokens, no token sits more than
    LOGIT_TOL under the reference's choice, at most MAX_OVER of them more
    than checks.LOGIT_TOL, at least MIN_EQUAL are equal."""
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
        worst = checks.score_summary(samples)["worst_gap"]
        if not worst <= LOGIT_TOL:
            bad.append(f"a {group} token sits {worst:.4f} logits under the "
                       f"reference's choice (tolerance {LOGIT_TOL})")
    got = checks.score_summary([s for g in groups.values() for s in g])
    if got["equal"] < MIN_EQUAL * got["tokens"]:
        bad.append(f"only {got['equal']} of {got['tokens']} served tokens "
                   f"equal the reference's (want {MIN_EQUAL:.0%})")
    over = gap_summary(groups)["over_checks_tol"]
    if over > MAX_OVER:
        bad.append(f"{over:.1%} of the served tokens sit more than "
                   f"{checks.LOGIT_TOL} logits under the reference's "
                   f"choice (at most {MAX_OVER:.0%})")
    return bad
