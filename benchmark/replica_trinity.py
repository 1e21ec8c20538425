"""The probed replica for the Trinity-Mini block: replica.py's probes
unchanged, with the reference check bound to that block's plain reference
(reference_trinity.py: whole sequences, no page, no window group) instead
of the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedTrinityServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_trinity
        return reference_trinity.score_greedy(
            self.engine.params, reference_trinity.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
