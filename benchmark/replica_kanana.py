"""The probed replica for the Kanana-2 block: replica.py's probes unchanged,
with the reference check bound to that block's plain reference
(reference_kanana.py: the published, expanded form of latent attention)
instead of the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedKananaServer(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_kanana
        return reference_kanana.score_greedy(
            self.engine.params, reference_kanana.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
