"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs (kernel_cost.py over peaks.json) / the
kernel's measured device time in the trace. Percent; which bound it is
goes into the run's notes.

args: {"cost": "paged_attention", "patterns": [...]}
   or {"cost": "flash_attention",
       "kernels": {"fwd": [...], "dq": [...], "dkv": [...]}}
"""

from benchmark import kernel_cost


def read(data, args):
    tr = data.get("trace_summary")
    if tr is None:
        return None
    dims = kernel_cost.model_dims(data["config"], data["traffic"])
    if args["cost"] == "paged_attention":
        seconds = tr.op_time(args["patterns"])
        span = data["trace"]
        flops, nbytes = kernel_cost.paged_attention_work(
            data.get("request_log", ()), span["start"]["wall"],
            span["stop"]["wall"], dims)
    elif args["cost"] == "flash_attention":
        seconds = flops = nbytes = 0.0
        for kind, patterns in args["kernels"].items():
            seconds += tr.op_time(patterns)
            f, b = kernel_cost.flash_attention_work(
                kind, tr.op_count(patterns), dims)
            flops, nbytes = flops + f, nbytes + b
    else:
        raise ValueError(f"unknown cost model {args['cost']!r}")
    if not seconds or not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    data.setdefault("notes", {})[f"{args['cost']}_bound"] = bound
    return pct
