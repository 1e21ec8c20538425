"""The limit of the comparison that decides `correct` for the granite-4.0-h
block: checks.served_tokens' method and limits, and a tighter tolerance on a
token's gap beside them (every other check of a run is checks.py's,
unchanged).

Why checks.py's 0.12 logits a token leaves too little room here: this
block divides its logits by 8 (`logits_scaling`), so with seeded weights
they spread 0.125 (other cells: ~1), and its tied head over an embedding
multiplied by 12 keeps ~3 % of the final hidden state along the INPUT
token's own embedding: the reference's top logit stands 0.40 over its
second on average (3.2 of the logits' standard deviations; my chip runs, PR
37, 8 seeds x 1280 tokens). Greedy tokens are therefore hard to move: the
float32 reference merely HELD in bf16 (reference_granite.forward(hold=),
arithmetic float32) keeps every one of 8 x 1280 tokens (worst gap 0.0), the
served path all but one of ~35 k scored tokens over 24 runs (that one a
near-tie, 0.009 under), and held in float8_e4m3, the nearest precision
below the stated one, it still keeps 99.45-99.92 % and is told apart by its
worst tokens alone, 1 to 5 of 1280: 0.111, 0.136, 0.139, 0.141, 0.160,
0.217, 0.238, 0.241 logits under the reference's choice: checks.py's
tolerance lies INSIDE these readings. The tolerance below lies between the
two sets of readings with room on both sides (PERF.md, PR 37;
benchmark/hold_granite.py takes them). It does not make the comparison
strong: cut into the cell's scored requests fp8 came out not correct in 7
of 8 seeds, its one token over the tolerance lying outside them in the
eighth.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import checks

#: a served token's logit under the reference's top choice. The reference
#: held in bf16: 0.0 on every token; served (bf16): 0.0 but for one near-tie
#: at 0.009 (a flip by bf16 rounding sits under ~0.015, a sixteenth of
#: fp8's). Held in fp8: worst 0.111-0.241 over 8 seeds x 1280 tokens
LOGIT_TOL = 0.06

#: checks.py's own, bound here: the runner puts this module's
#: ``served_tokens`` in its place for the length of a run
_checks_served_tokens = checks.served_tokens


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """checks.served_tokens' faults, and a group's worst token held to this
    block's tolerance."""
    bad = _checks_served_tokens(groups)
    for group, samples in groups.items():
        worst = checks.score_summary(samples)["worst_gap"]
        if worst is not None and checks.LOGIT_TOL >= worst > LOGIT_TOL:
            bad.append(f"a {group} token sits {worst:.4f} logits under the "
                       f"reference's choice (this block's tolerance "
                       f"{LOGIT_TOL})")
    return bad
