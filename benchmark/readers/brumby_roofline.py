"""A kernel's share of its roofline in the Brumby block: readers/
trace_roofline.py's method (the least time the chip could take for the work
the algorithm needs / the kernel's measured device time in the trace), with
the work counted from this block's own shape numbers
(kernel_cost_brumby.py): the one-token state update of the retention
layers, a key/value head's matrix state read once and written once a decode
token a layer at the least size any expansion holds. Percent, not clamped;
which bound it is goes into the run's notes. None where the trace holds no
such kernel (a program from before the block has none).

args: {"cost": "retention_update", "patterns": [regex of the kernel's HLO
       instruction names]}
"""

from __future__ import annotations

from benchmark import kernel_cost, kernel_cost_brumby


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    if args["cost"] != "retention_update":
        raise ValueError(f"unknown cost model {args['cost']!r}")
    seconds = tr.op_time(args["patterns"])
    dims = kernel_cost_brumby.model_dims(data["config"])
    tokens = kernel_cost_brumby.decode_tokens(
        data.get("request_log", ()), span["start"]["wall"],
        span["stop"]["wall"])
    flops, nbytes = kernel_cost_brumby.retention_update_work(tokens, dims)
    if not seconds or not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    data.setdefault("notes", {}).update(
        retention_update_tokens=tokens, retention_update_bound=bound)
    return pct
