"""A kernel's share of its roofline in the Trinity-Mini block: readers/
mimo_roofline.py's method (the least time the chip could take for the work
the algorithm needs / the kernel's measured device time in the trace), with
the work counted from this block's own shape numbers
(kernel_cost_trinity.py): the full layer's attention over a row's whole
context and the window layers' over the last 2048 tokens, both at the
published 128 + 128 values a cached token and head; the expert kernel over
the pairs the program counted. Percent, not clamped; which bound it is goes
into the run's notes. None where the trace holds no such kernel, or no
counters (a program from before the block has neither).

args: {"cost": "full_attention" | "window_attention" | "moe_experts",
       "patterns": [regex of the kernel's HLO instruction names]}
"""

from __future__ import annotations

from benchmark import kernel_cost, kernel_cost_trinity, trace_reduce
from benchmark.readers.moe_roofline import traced_counters

_ATTENTION = {"full_attention": kernel_cost_trinity.full_attention_work,
              "window_attention": kernel_cost_trinity.window_attention_work}


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    seconds = tr.op_time(args["patterns"])
    if not seconds:
        return None
    dims = kernel_cost_trinity.model_dims(data["config"])
    notes = {}
    if args["cost"] in _ATTENTION:
        flops, nbytes = _ATTENTION[args["cost"]](
            data.get("request_log", ()), span["start"]["wall"],
            span["stop"]["wall"], dims)
    elif args["cost"] == "moe_experts":
        path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
        counted = traced_counters(path) if path else None
        if not counted or not counted.get("moe_pairs"):
            return None
        flops, nbytes = kernel_cost_trinity.moe_experts_work(
            counted["moe_pairs"], counted["moe_hits"], dims)
        notes["moe_traced_counters"] = counted
    else:
        raise ValueError(f"unknown cost model {args['cost']!r}")
    if not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    notes[f"{args['cost']}_bound"] = bound
    data.setdefault("notes", {}).update(notes)
    return pct
