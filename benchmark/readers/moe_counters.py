"""Ratios of the routing counters the step programs reduce on the device
(InferenceEngine.stats: moe_pairs, moe_hits, moe_hot), as deltas over the
window. None for a program without them.

args: {"quantity": "hit_pct" | "load_skew"}
  hit_pct    moe_hits / (decode steps x layers x experts), percent: the
             share of a step's experts that at least one token reached
             (a mixed step counts as the one step it is)
  load_skew  moe_hot x experts / moe_pairs: the busiest expert's pairs
             over the mean expert's, per step and layer; 1 = even
"""


def read(data, args):
    a, b = data.get("stats_open"), data.get("stats_close")
    if not a or not b or "moe_pairs" not in b:
        return None
    d = {k: b[k] - a[k] for k in ("moe_pairs", "moe_hits", "moe_hot",
                                  "decode_steps")}
    experts = data["config"]["num_experts"]
    if args["quantity"] == "hit_pct":
        den = d["decode_steps"] * data["config"]["num_hidden_layers"] \
            * experts
        return 100.0 * d["moe_hits"] / den if den > 0 else None
    if args["quantity"] == "load_skew":
        return d["moe_hot"] * experts / d["moe_pairs"] \
            if d["moe_pairs"] > 0 else None
    raise ValueError(f"unknown quantity {args['quantity']!r}")
