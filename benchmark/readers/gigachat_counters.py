"""readers/moe_counters.py's two ratios for the GigaChat3.5 block: the
routing counters are summed over the EXPERT layers only (the leading dense
layer routes nothing) and over the experts HELD here, so the denominator
counts those (kernel_cost_gigachat.model_dims: `experts_held`, not the
router's 256). None for a program without the counters.

args: {"quantity": "hit_pct" | "load_skew"}
"""

from benchmark import kernel_cost_gigachat


def read(data, args):
    a, b = data.get("stats_open"), data.get("stats_close")
    if not a or not b or "moe_pairs" not in b:
        return None
    d = {k: b[k] - a[k] for k in ("moe_pairs", "moe_hits", "moe_hot",
                                  "decode_steps")}
    dims = kernel_cost_gigachat.model_dims(data["config"])
    if args["quantity"] == "hit_pct":
        den = d["decode_steps"] * dims["expert_layers"] \
            * dims["experts_held"]
        return 100.0 * d["moe_hits"] / den if den > 0 else None
    if args["quantity"] == "load_skew":
        return d["moe_hot"] * dims["experts_held"] / d["moe_pairs"] \
            if d["moe_pairs"] > 0 else None
    raise ValueError(f"unknown quantity {args['quantity']!r}")
