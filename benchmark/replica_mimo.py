"""The probed replica for the MiMo-V2-Flash block: replica.py's probes
unchanged, with the reference check bound to that block's plain reference
(reference_mimo.py: whole sequences, no page, no window group) instead of
the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedMimoServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_mimo
        return reference_mimo.score_greedy(
            self.engine.params, reference_mimo.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
