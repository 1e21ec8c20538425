"""A kernel's share of its roofline in the GigaChat3.5 block: readers/
trace_roofline.py's and readers/moe_roofline.py's method (the least time
the chip could take for the work the algorithm needs / the measured device
time in the trace), with the work counted from this block's own shape
numbers (kernel_cost_gigachat.py): the delta rule's one-token update over
the decode tokens of the traced span, its chunk form over the span's chunk
tokens and rows, latent attention over the ONE latent layer, the expert
kernel over the pairs routed to the experts HELD here. The chunk form is no
single kernel: its time is the self time of every op under its named scope
(readers/trace_scope.py). Percent, not clamped; which bound it is goes into
the run's notes. None where the trace holds no such kernel or scope, or no
counters (a program from before the block has neither). The update's tokens
count what a request's record folded past its 512 kept dispatches too
(kernel_cost_gigachat.decode_tokens; how many of the span's tokens are so
estimated goes into notes.delta_update_tokens_estimated): a share computed
without them would fall as the program got faster.

args: {"cost": "delta_update" | "delta_chunk" | "latent_attention" |
       "moe_experts", "patterns": [regex of the kernel's HLO instruction
       names] | "scope": the named scope}
"""

from __future__ import annotations

from benchmark import kernel_cost, kernel_cost_gigachat, trace_reduce
from benchmark.readers import trace_scope
from benchmark.readers.moe_roofline import traced_counters


def _seconds(data, args, tr):
    if "scope" not in args:
        return tr.op_time(args["patterns"])
    pct = trace_scope.read(dict(data, notes={}), {"scope": args["scope"]})
    return None if pct is None else pct / 100.0 * tr.busy_s


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    seconds = _seconds(data, args, tr)
    if not seconds:
        return None
    dims = kernel_cost_gigachat.model_dims(data["config"])
    records = data.get("request_log", ())
    lo, hi = span["start"]["wall"], span["stop"]["wall"]
    cost, notes = args["cost"], {}
    if cost == "delta_update":
        tokens, notes["delta_update_tokens_estimated"] = \
            kernel_cost_gigachat.decode_tokens(records, lo, hi)
        flops, nbytes = kernel_cost_gigachat.delta_update_work(tokens, dims)
        notes["delta_update_tokens"] = tokens
    elif cost == "delta_chunk":
        tokens, rows = kernel_cost_gigachat.chunk_tokens(records, lo, hi)
        flops, nbytes = kernel_cost_gigachat.delta_chunk_work(tokens, rows,
                                                              dims)
        notes["delta_chunk_tokens"], notes["delta_chunk_rows"] = tokens, rows
    elif cost == "latent_attention":
        flops, nbytes = kernel_cost_gigachat.latent_attention_work(
            records, lo, hi, dims)
    elif cost == "moe_experts":
        path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
        counted = traced_counters(path) if path else None
        if not counted or not counted.get("moe_pairs"):
            return None
        flops, nbytes = kernel_cost_gigachat.moe_experts_work(
            counted["moe_pairs"], counted["moe_hits"], dims)
        notes["moe_traced_counters"] = counted
    else:
        raise ValueError(f"unknown cost model {cost!r}")
    if not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    notes[f"{cost}_bound"] = bound
    data.setdefault("notes", {}).update(notes)
    return pct
