"""The probed replica for the Brumby block: replica.py's probes unchanged,
with the reference check bound to that block's plain reference
(reference_brumby.py: the quadratic form) instead of the Llama/Mistral
one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedBrumbyServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_brumby
        return reference_brumby.score_greedy(
            self.engine.params, reference_brumby.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
