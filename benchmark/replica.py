"""The served replica, as the program builds it, plus the benchmark's probes.

ray_tpu has no hook through which a caller can trace the process that owns
the chip, read its allocator, or score served tokens against a reference
that is not the program's own. Until it has (PERF.md, Open questions), the
benchmark deploys a subclass of LLMServer that ADDS those probes and changes
nothing: construction, the engine thread, streaming and every serving method
are the program's. Only this process (the worker the runtime leased the
chips to) may touch jax.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict

from ray_tpu.llm.serve_llm import LLMServer


class ProbedLLMServer(LLMServer):

    def bench_probe(self) -> Dict[str, Any]:
        """One snapshot: where the engine runs, what it compiled, its
        counters, the allocator's peak on the fullest chip, the clocks.
        Called while the engine thread is stepping, so it reads the weights'
        placement and never the page pool (each step donates the pool: its
        arrays die under a reader's hands, which is what
        engine.device_report() trips over)."""
        import jax

        from ray_tpu.util import compile_tracker
        eng = self.engine
        devices = sorted({shard.device
                          for leaf in jax.tree.leaves(eng.params)
                          for shard in leaf.addressable_shards},
                         key=lambda d: d.id)
        stats = [d.memory_stats() or {} for d in devices]
        peaks = [m["peak_bytes_in_use"] for m in stats
                 if m.get("peak_bytes_in_use") is not None]
        tracker = compile_tracker.get_global()
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(jax.devices()), "tp": eng.tp,
                "paged_impl": eng._fns.paged_impl,
                "compiled_step_programs": eng.compiled_step_programs(),
                "memory_peak_bytes": max(peaks) if peaks else None,
                "bytes_limit": stats[0].get("bytes_limit"),
                "compile_counts": tracker.stats()["counts"] if tracker
                else None,
                "compile_cache_dir": jax.config.jax_compilation_cache_dir,
                "stats": dict(eng.stats), "has_work": eng.has_work(),
                "pid": os.getpid(), "wall": time.time(),
                "monotonic": time.monotonic()}

    def bench_trace_start(self, log_dir: str) -> Dict[str, float]:
        from benchmark import trace_reduce
        trace_reduce.start_trace(log_dir)
        return {"wall": time.time(), "monotonic": time.monotonic()}

    def bench_trace_stop(self) -> Dict[str, float]:
        import jax
        # stamped BEFORE the stop: collecting and writing the trace takes
        # seconds that are not part of the traced window
        at = {"wall": time.time(), "monotonic": time.monotonic()}
        jax.profiler.stop_trace()
        return at

    def bench_reference_check(self, request: Dict[str, Any]
                              ) -> Dict[str, Any]:
        """Score {"prompt_ids", "token_ids", "pad_to"} against the
        benchmark's own plain reference (benchmark/reference.py) run on
        this replica's weights."""
        from benchmark import reference
        return reference.score_greedy(
            self.engine.params, reference.dims_of(self.engine.cfg),
            list(request["prompt_ids"]), list(request["token_ids"]),
            int(request["pad_to"]))
