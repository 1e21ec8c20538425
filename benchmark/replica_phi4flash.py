"""The probed replica for the Phi-4-mini-flash block: replica.py's probes
unchanged, with the reference check bound to that block's plain reference
(reference_phi4flash.py) instead of the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedPhi4FlashServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_phi4flash
        return reference_phi4flash.score_greedy(
            self.engine.params, reference_phi4flash.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
