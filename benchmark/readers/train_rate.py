"""Training rates from the steps of the window, each of which ended in a
blocked read of its loss.

args: {"quantity": "tokens_per_s" | "step_ms" | "mfu_pct"}
  tokens_per_s  tokens of the window's steps / the window (which closes
                with the step that crossed --seconds)
  step_ms       mean seconds of a step, each timed around its own blocked
                read (so a traced run's profiler stop is not in it)
  mfu_pct       causal forward+backward FLOPs a token (kernel_cost.py; remat
                not counted) * tokens_per_s / (chips * peak bf16 FLOP/s)
"""

from benchmark import kernel_cost


def read(data, args):
    t = data.get("train")
    if not t:
        return None
    steps, window = len(t["step_ends"]), t["step_ends"][-1]
    rate = steps * t["tokens_per_step"] / window
    if args["quantity"] == "tokens_per_s":
        return rate
    if args["quantity"] == "step_ms":
        return 1e3 * sum(t["step_s"]) / steps
    if args["quantity"] == "mfu_pct":
        dims = kernel_cost.model_dims(data["config"], data["traffic"])
        peak = kernel_cost.peaks(t["kind"])["bf16_flops_per_s"]
        return 100.0 * kernel_cost.train_flops_per_token(dims) * rate \
            / (t["count"] * peak)
    raise ValueError(args["quantity"])
