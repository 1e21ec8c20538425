"""A share of its roofline in the Ouro block: the least time the chip could
take for the work the algorithm needs / the measured device time in the
trace, with the work counted from this block's own shape numbers
(kernel_cost_ouro.py). Percent, not clamped; which bound it is goes into
the run's notes. None where the trace holds no such kernel or module.

  paged_attention  the paged kernels' share, readers/trace_roofline.py's
                   method over this block's dims: 192 attention readings a
                   token (48 layers x 4 passes), the shared
                   kernel_cost.paged_attention_work for the dispatches that
                   completed in the traced span.
  layers_stream    the DECODE LOOP's device time outside the kernels named
                   by `patterns` (their self time INSIDE the loop's module
                   events, not in the mixed step's), against what its steps
                   have to stream: every layer's weights once a pass and
                   the head (kernel_cost_ouro.stream_work), over `rows`
                   one-token rows a step, `steps` steps an execution (both
                   from the configuration file's engine settings).

args: {"cost": "paged_attention", "patterns": [regex of HLO names]}
   or {"cost": "layers_stream", "patterns": [...], "module": [regex of the
       decode loop's module names], "steps": "engine.decode_chunk",
       "rows": "engine.max_batch"}
"""

from __future__ import annotations

import re

from benchmark import kernel_cost, kernel_cost_ouro, trace_reduce
from benchmark.readers._stats import lookup


def _outside(path: str, module, patterns):
    """(seconds of the matching modules' executions outside the matching
    ops, executions), a mean over the chips."""
    module = [re.compile(p) for p in module]
    ops_rx = [re.compile(p) for p in patterns]
    seconds, runs, chips = 0.0, 0, 0
    for pname, lines in trace_reduce.read_planes(path).items():
        if not trace_reduce.DEVICE_PLANE.match(pname):
            continue
        mods = [(s, s + d) for ln, evs in lines.items()
                if trace_reduce.MODULE_LINE.match(ln) for n, s, d in evs
                if any(r.search(trace_reduce.module_name(n))
                       for r in module)]
        ops = [(n.lstrip("%"), s, d) for ln, evs in lines.items()
               if trace_reduce.OP_LINE.match(ln) for n, s, d in evs]
        if not mods or not ops:
            continue
        chips += 1
        for lo, hi in mods:
            inside = [e for e in ops if lo <= e[1] and e[1] + e[2] <= hi]
            self_s, _ = trace_reduce._self_times(inside)
            seconds += (hi - lo) / 1e9 - sum(
                sec for text, sec in self_s.items()
                if any(r.search(text) for r in ops_rx))
            runs += 1
    return (seconds / chips, runs / chips) if chips else (0.0, 0)


def read(data, args):
    tr = data.get("trace_summary")
    span = data.get("trace") or {}
    if tr is None or "start" not in span or "stop" not in span:
        return None
    dims = kernel_cost_ouro.model_dims(data["config"])
    if args["cost"] == "paged_attention":
        seconds = tr.op_time(args["patterns"])
        flops, nbytes = kernel_cost.paged_attention_work(
            data.get("request_log", ()), span["start"]["wall"],
            span["stop"]["wall"], dims)
    elif args["cost"] == "layers_stream":
        path = span.get("dir") and trace_reduce.find_xplane(span["dir"])
        if not path:
            return None
        seconds, runs = _outside(path, args["module"], args["patterns"])
        flops, nbytes = kernel_cost_ouro.stream_work(
            lookup(data["config"], args["rows"]), dims)
        steps = runs * lookup(data["config"], args["steps"])
        flops, nbytes = flops * steps, nbytes * steps
    else:
        raise ValueError(f"unknown cost model {args['cost']!r}")
    if not seconds or not flops:
        return None
    pct, bound = kernel_cost.roofline_pct(flops, nbytes, seconds,
                                          data["device"]["kind"])
    data.setdefault("notes", {})[f"ouro_{args['cost']}_bound"] = bound
    return pct
