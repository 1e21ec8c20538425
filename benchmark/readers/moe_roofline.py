"""The routed-expert kernel's share of its roofline: the least time the
chip could take for the routing the program counted (kernel_cost_moe.py
over peaks.json) / the kernel's measured device time in the trace.

The work is summed over exactly the dispatches the trace holds: the engine
writes each dispatch's counters (moe_pairs, moe_hits) as metadata of its
engine.readback span, and this reader adds up the spans of the traced
run's .xplane.pb. None where the trace has no such metadata (a program
without the counters, a CPU rehearsal without a device plane) or no kernel
time. Percent, not clamped; which bound it is goes into the run's notes.

args: {"patterns": [regex of the kernel's HLO instruction names]}
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark import kernel_cost, kernel_cost_moe, trace_reduce

SPAN = "engine.readback"


def traced_counters(path: str) -> Optional[Dict[str, float]]:
    """Sums of the integer metadata of every engine.readback span."""
    from jax.profiler import ProfileData
    total: Dict[str, float] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name != SPAN:
                    continue
                for key, value in dict(ev.stats).items():
                    if key.startswith("moe_"):
                        total[key] = total.get(key, 0.0) + float(value)
    return total or None


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
    if tr is None or not path:
        return None
    seconds = tr.op_time(args["patterns"])
    counted = traced_counters(path)
    if not seconds or not counted or not counted.get("moe_pairs"):
        return None
    flops, nbytes = kernel_cost_moe.moe_experts_work(
        counted["moe_pairs"], counted["moe_hits"],
        kernel_cost.model_dims(data["config"]))
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    data.setdefault("notes", {}).update(
        moe_experts_bound=bound, moe_traced_counters=counted)
    return pct
