"""A kernel's share of its roofline in the Phi-4-mini-flash block: readers/
granite_roofline.py's method (the least time the chip could take for the
work the algorithm needs / the kernel's measured device time in the trace),
with the work counted from this block's own shape numbers
(kernel_cost_phi4flash.py): the one-token state update of the Mamba-1
layers, a slot's float32 state read once and written once a decode token a
layer; and the paged attention kernels together, the full layer's pages
counted once for EVERY layer that reads them (itself and the cross layers)
and the window layers' cut to the window. Percent, not clamped; which bound
it is goes into the run's notes. None where the trace holds no such kernel
(a program from before the block has none).

args: {"cost": "selective_update" | "paged_attention",
       "patterns": [regex of the kernels' HLO instruction names]}
"""

from __future__ import annotations

from benchmark import kernel_cost, kernel_cost_phi4flash


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    seconds = tr.op_time(args["patterns"])
    if not seconds:
        return None
    dims = kernel_cost_phi4flash.model_dims(data["config"])
    records = data.get("request_log", ())
    lo, hi = span["start"]["wall"], span["stop"]["wall"]
    notes = {}
    if args["cost"] == "paged_attention":
        flops, nbytes = kernel_cost_phi4flash.paged_attention_work(
            records, lo, hi, dims)
    elif args["cost"] == "selective_update":
        tokens = kernel_cost_phi4flash.decode_tokens(records, lo, hi)
        flops, nbytes = kernel_cost_phi4flash.selective_update_work(
            tokens, dims)
        notes["selective_update_tokens"] = tokens
    else:
        raise ValueError(f"unknown cost model {args['cost']!r}")
    if not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    notes[f"{args['cost']}_bound"] = bound
    data.setdefault("notes", {}).update(notes)
    return pct
