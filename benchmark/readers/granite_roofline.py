"""A kernel's share of its roofline in the granite-4.0-h block: readers/
trace_roofline.py's method (the least time the chip could take for the work
the algorithm needs / the kernel's measured device time in the trace), with
the work counted from this block's own shape numbers
(kernel_cost_granite.py): paged attention over the attention layers only,
and the one-token state update of the state-space layers, a slot's matrix
state read once and written once a decode token a layer. Percent, not
clamped; which bound it is goes into the run's notes. None where the trace
holds no such kernel (a program from before the block has none).

args: {"cost": "paged_attention" | "ssm_update", "patterns": [regex of the
       kernel's HLO instruction names]}
"""

from __future__ import annotations

from benchmark import kernel_cost, kernel_cost_granite


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    seconds = tr.op_time(args["patterns"])
    dims = kernel_cost_granite.model_dims(data["config"])
    records = data.get("request_log", ())
    lo, hi = span["start"]["wall"], span["stop"]["wall"]
    notes = {}
    if args["cost"] == "paged_attention":
        flops, nbytes = kernel_cost_granite.paged_attention_work(
            records, lo, hi, dims)
    elif args["cost"] == "ssm_update":
        tokens = kernel_cost_granite.decode_tokens(records, lo, hi)
        flops, nbytes = kernel_cost_granite.ssm_update_work(tokens, dims)
        notes["ssm_update_tokens"] = tokens
    else:
        raise ValueError(f"unknown cost model {args['cost']!r}")
    if not seconds or not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    notes[f"{args['cost']}_bound"] = bound
    data.setdefault("notes", {}).update(notes)
    return pct
