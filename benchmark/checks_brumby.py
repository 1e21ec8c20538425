"""The limits of the comparison that decides `correct` for the Brumby block:
checks.served_tokens' method with limits of this block's own (every other
check of a run is checks.py's, unchanged), as checks_kanana.py does it for
the latent block.

Why checks.py's limits (no token more than 0.12 logits under the
reference's choice, 90 % of tokens equal) cannot hold here: a decoded token
reads a matrix state that has been rounded to bf16 once a token for as long
as the slowest head remembers (sigmoid(b_g) up to 1 - 1e-3: a thousand
tokens), and what it reads is a RATIO of two sums over squared products.
The readings are in PERF.md (PR 41; benchmark/hold_brumby.py takes the held
ones, on the chip at the published widths), and each limit below lies
between the served path's worst and the least the reference gives when held
in float8_e4m3.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import checks
from benchmark.checks_kanana import shares as _shares

#: a served token's logit under the reference's top choice, held token by
#: token, a group's worst. The reference held in bf16 with bf16 matmuls:
#: 0.07-0.29 over 4 seeds x 2 groups (held in bf16 alone 0.02-0.06); held in
#: fp8: 1.60-2.52. Served, 8 runs x 2 groups: warm-up 0.066-0.101, window
#: 0.216-0.421 (the window's requests are six times longer)
LOGIT_TOL = 0.8
#: share of a group's tokens equal to the reference's. Held in bf16 with
#: bf16 matmuls 0.896-0.955 (bf16 alone 0.956-0.975); held in fp8
#: 0.242-0.271. Served: warm-up 0.904-0.940, window 0.828-0.881
MIN_EQUAL = 0.60
#: share of a group's tokens more than checks.LOGIT_TOL (0.12) under the
#: reference's choice: the flipped ones. Held in bf16 with bf16 matmuls
#: 0.000-0.031; held in fp8 0.631-0.692. Served: 0.008-0.042 of a run's
#: tokens, at most 0.057 of a group's
MAX_OVER = 0.25


def shares(samples: List[Dict]) -> Dict:
    """What the limits are held against, of one group's samples:
    checks_kanana.shares' (tokens, the shares equal and more than
    checks.LOGIT_TOL under) and the worst token."""
    return {**_shares(samples),
            "worst": checks.score_summary(samples)["worst_gap"]}


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """The faults of the scored requests, {group name: samples}; [] means
    correct. checks.served_tokens' method: every group holds a request that
    returned all its tokens; then each group's worst token, its share of
    equal tokens and its share of flipped ones are held to this block's
    limits."""
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
        got = shares(samples)
        if not got["worst"] <= LOGIT_TOL:
            bad.append(f"a {group} token sits {got['worst']:.4f} logits "
                       f"under the reference's choice (tolerance "
                       f"{LOGIT_TOL})")
        if got["equal"] < MIN_EQUAL:
            bad.append(f"only {got['equal']:.1%} of the {group} tokens "
                       f"equal the reference's (want {MIN_EQUAL:.0%})")
        if got["over_checks_tol"] > MAX_OVER:
            bad.append(f"{got['over_checks_tol']:.1%} of the {group} tokens "
                       f"sit more "
                       f"than {checks.LOGIT_TOL} logits under the "
                       f"reference's choice (at most {MAX_OVER:.0%})")
    return bad
