"""The probed replica for the Ouro block: replica.py's probes unchanged,
with the reference check bound to that block's plain reference
(reference_ouro.py: whole sequences, every pass, no page, no plane) instead
of the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedOuroServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_ouro
        return reference_ouro.score_greedy(
            self.engine.params, reference_ouro.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
