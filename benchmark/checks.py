"""The comparison that decides `correct`.

Served tokens are scored against the plain reference (reference.py) run on
the same weights, on logits and not on token identity: with random weights
the largest logit changes hands on rounding, and greedy streams are not
bitwise reproducible under concurrency (a token is computed by the mixed
step in one run and by the decode loop in the next). Two groups are scored
in every run: the warm-up requests, served by an otherwise idle engine, and
a seeded handful of the window's own, served under its load. The tolerances
are about twice what was measured against this reference.
"""

from __future__ import annotations

import math
from typing import Dict, List

#: a served greedy token may differ from the reference's where bf16 rounding
#: flips a near-tie; the reference's logit for it must then be within this of
#: its own top logit. Random-init logits have unit variance: a wrong page,
#: mask or position costs whole logits, and serving in a lower precision
#: than bf16 would cost tenths. Measured on the chip against the float32
#: reference: worst gap 0.064 (PERF.md, PR 23); chip_smoke.py's 0.25 was
#: set against the program's own bf16 path and left four-fold room.
LOGIT_TOL = 0.12
#: ... and such near-ties are rare: of a run's 104 warm-up tokens 95-104
#: were equal on the chip, 96 % over 64 runs (PERF.md, PR 23); of all the
#: tokens a run scores, at least this share must be
MIN_EQUAL = 0.90
#: flash kernels in bf16 against the float32 reference, same batch and
#: weights: the step-0 loss agrees to this relative tolerance (chip_smoke's
#: LOSS_RTOL; measured 12.26228 vs 12.26221, PR 21)
LOSS_RTOL = 2e-2


def score_summary(samples: List[Dict]) -> Dict:
    """samples: [{"served": [...], "reference_tokens": [...], "gap": [...],
    "max_tokens": n}] -> requests, tokens, tokens equal, worst gap."""
    return {"requests": len(samples),
            "tokens": sum(len(s["served"]) for s in samples),
            "equal": sum(a == b for s in samples
                         for a, b in zip(s["served"], s["reference_tokens"])),
            "worst_gap": max((max(s["gap"]) for s in samples if s["gap"]),
                             default=None)}


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """The faults of the scored requests, {group name: samples}; [] means
    correct. Every group must hold a request; the gap is held token by
    token; the equal share over all groups together (one warm-up group of
    a hundred tokens swings by several percent on its own)."""
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
        worst = score_summary(samples)["worst_gap"]
        if not worst <= LOGIT_TOL:
            bad.append(f"a {group} token sits {worst:.4f} logits under the "
                       f"reference's choice (tolerance {LOGIT_TOL})")
    got = score_summary([s for g in groups.values() for s in g])
    if got["equal"] < MIN_EQUAL * got["tokens"]:
        bad.append(f"only {got['equal']} of {got['tokens']} served tokens "
                   f"equal the reference's (want {MIN_EQUAL:.0%})")
    return bad


def request_fault(r: Dict):
    """What is wrong with one client record, or None. A request that ended
    returned exactly its max_tokens; none was refused or failed. A request
    the run itself dropped at the end of a closed loop is neither."""
    if r["error"]:
        return r["error"]
    if r["done"] and len(r["tokens"]) != r["max_tokens"]:
        return f"{len(r['tokens'])} tokens, {r['max_tokens']} asked"
    if not r["done"] and not r["aborted"]:
        return "stream ended without [DONE]"
    return None


def request_faults(results: List[Dict]) -> List[str]:
    return [f"request {i}: {fault}" for i, r in enumerate(results)
            for fault in [request_fault(r)] if fault]


def training(losses: List[float], step0_loss: float,
             reference_loss: float) -> List[str]:
    bad = []
    if not losses or not all(math.isfinite(x) for x in losses):
        return [f"losses not finite: {losses[:8]}"]
    if abs(step0_loss - reference_loss) > LOSS_RTOL * max(
            abs(step0_loss), abs(reference_loss)):
        bad.append(f"step-0 loss {step0_loss} vs the plain reference "
                   f"{reference_loss} (rtol {LOSS_RTOL})")
    k = max(1, len(losses) // 4)
    if not sum(losses[-k:]) / k < sum(losses[:k]) / k:
        bad.append(f"loss did not fall over the window: first {losses[:k]}"
                   f" last {losses[-k:]}")
    return bad
