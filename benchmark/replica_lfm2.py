"""The probed replica for the LFM2 block: replica.py's probes unchanged,
with the reference check bound to that block's plain reference
(reference_lfm2.py) instead of the Llama/Mistral one."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.replica import ProbedLLMServer


class ProbedLFM2Server(ProbedLLMServer):

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        from benchmark import reference_lfm2
        return reference_lfm2.score_greedy(
            self.engine.params, reference_lfm2.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
