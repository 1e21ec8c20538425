"""The limits of the comparison that decides `correct` for the GigaChat3.5
block: checks_trinity.served_tokens' method with limits of this block's own
(every other check of a run is checks.py's, unchanged).

Why checks.py's limits (no token more than 0.12 logits under the
reference's choice, 90 % of tokens equal) cannot hold here: the greedy
choice over 16032 unit-variance logits flips on bf16's rounding for one
token in twenty, mostly by little (99 % of the served gaps under 0.09) and
for one token in 150 by more than 0.12, up to 0.61: the served path read
0.930-0.945 of all scored tokens equal over its 15 runs and the float32
reference merely computed in bf16 0.947-0.951. So this block brings no
per-token limit (the worst gap is reported, notes.scored_gaps, and must be
finite) and holds two shares three times over, as checks_trinity.py does
and for its reasons: on all scored tokens, on each group, and the equal
share on each request.

Each limit lies between two readings on the chip at the published widths
(PERF.md, PR 55; benchmark/hold_gigachat.py takes the held ones): the worst
the served path gave over its seeds, and what the reference gives held in
float8_e4m3, the nearest precision below the bf16 the configuration states,
or with ONE part of the block left out (reference_gigachat.FAULTS). Held in
fp8 it comes out NOT correct by both shares on every group and request; the
delta rule's read left out, its decay left out and the sigmoid in the norms
left out read under 0.10 equal; the latent layer's output gate left out
0.70-0.71 (groups 0.68-0.71) equal and 0.13-0.15 over.

What no limit on served TOKENS can tell from rounding: the recurrent STATE
held in bf16 (ISSUE 55's control: rounded after every token by
lax.reduce_precision, everything else as the served program computes) reads
0.934-0.940 equal, INSIDE the served path's own 0.930-0.945, and so would
its logits: the bf16 AROUND the float32 recurrence (the projections, the
absorbed products, the values between operators) moves a logit as far as the
state's rounding does, and 3 to 4 thousand scored tokens cannot tell 0.937
from 0.927. So the state has a limit of its own, on the one comparison in
which nothing else is rounded: of every scored request, the first delta
layer's recurrence over the head of the prompt and of what was served, as
the PROGRAM computes it (a state leaf made as the pool makes it, the chunk
form in the engine's chunk rows from the slot's state, then the update
kernel a token a step: replica_gigachat.served_state) against the
reference's token-by-token float32 recurrence ON THE SAME INPUTS
(reference_gigachat.first_layer_state), as the worst value head's relative
distance (MAX_STATE_ERROR). The program reads 9e-6 to 1.04e-4 over 4 seeds x
8 requests; the state held in bf16 1.0e-2 to 1.7e-2 (ONE rounding to bf16
is 1.6e-3) and comes out NOT correct by this limit alone (my chip runs,
PR 55). And the pool's state a slot is held to the bytes of a float32 state
(runners/serve_gigachat.py:Session). The YaRN frequencies and scale left out
read 0.924-0.928 (one latent layer in five, seeded weights whose scores are
near uniform): reported, and held on logits on the CPU
(tests/test_llm_gigachat.py:test_no_part_is_left_out).
"""

from __future__ import annotations

import math
from typing import Dict, List

from benchmark import checks
from benchmark.checks_kanana import request_shares, shares  # noqa: F401
from benchmark.checks_lfm2 import gap_summary   # noqa: F401 — the same

#: share of served tokens equal to the reference's, of all scored tokens
#: and of each group's. Served (my chip runs, PR 55; PERF.md has the runs):
#: 0.921-0.947 over 15 runs x 2 groups (the warm-up group, 480 tokens,
#: reads lowest; all scored tokens 0.930-0.945); the reference held in bf16 with bf16 matmuls
#: 0.947-0.951 over 3 seeds x 9216 tokens (groups 0.929-0.965); held in fp8
#: 0.409-0.413 (groups 0.375-0.450); the output gate left out 0.702-0.713
MIN_EQUAL = 0.85
#: share more than checks.LOGIT_TOL (0.12) under the reference's choice: the
#: flipped ones. Served 0.004-0.017 of a group's tokens; held in bf16 with
#: bf16 matmuls 0.005-0.006 (groups 0.002-0.008); held in fp8 0.473-0.487;
#: the output gate left out 0.134-0.147
MAX_OVER = 0.06
#: the equal share of ONE request of at least MIN_REQUEST_TOKENS tokens (the
#: warm-up's are 96-128 by the traffic file; 96 tokens stray 0.025 a
#: standard deviation at 0.94): a request served from another's state or
#: pages reads what chance gives. Served 0.883-0.969 over 117 requests; held in bf16 with bf16 matmuls 0.883-1.0 over 24 requests;
#: held in fp8 0.320-0.484
MIN_EQUAL_REQUEST = 0.70
MIN_REQUEST_TOKENS = 64
#: NOT a limit of this block: the share more than 1.0 under. The served
#: path reads 0 (its worst token 0.61 under) and the fp8 hold 0.019-0.020:
#: no limit lies between them with room. It is reported (notes.scored_gaps)
#: the tokens of a scored request whose recurrence is compared: the head of
#: its prompt (through the chunk form, four chunk rows of 512 at most) and
#: of what was served after it (through the update kernel)
STATE_TOKENS = (2048, 128)
#: how far the PROGRAM's recurrent state may lie from the reference's on
#: the same inputs (reference_gigachat.state_error: the worst value head's
#: relative distance), of every scored request. The program 9.3e-6 to
#: 1.04e-4 over 3 seeds x 8 requests of the hold study and 2.6e-5 at worst
#: in a run of the cell; the state held in bf16 1.00e-2 to 1.71e-2 (my chip
#: runs, PR 55): ten times of room on both sides
MAX_STATE_ERROR = 1e-3


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


def state_faults(groups: Dict[str, List[Dict]]) -> List[str]:
    """Every scored request whose recurrent state, as the program computes
    it, was not compared with the reference's on the same inputs
    (replica_gigachat.py) or lies more than MAX_STATE_ERROR off it."""
    return [f"{group} sample {i}: the recurrent state lies "
            f"{s.get('state_error')} off the reference's on the same "
            f"inputs (at most {MAX_STATE_ERROR})"
            for group, samples in groups.items()
            for i, s in enumerate(samples)
            if s.get("state_error") is None
            or not s["state_error"] <= MAX_STATE_ERROR]


def served_tokens(groups: Dict[str, List[Dict]]) -> List[str]:
    """The faults of the scored requests, {group name: samples}; [] means
    correct. Every group holds a request that returned all its tokens and
    whose gaps are finite; the two shares are held on all scored tokens AND
    on each group's; of each request's tokens (MIN_REQUEST_TOKENS or more)
    at least MIN_EQUAL_REQUEST are equal; and each request's recurrent state
    lies within MAX_STATE_ERROR of the reference's (``state_faults``)."""
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
    return bad + state_faults(groups) + _held_to_limits(
        "all served tokens", shares([s for g in groups.values() for s in g]))
