"""The shape numbers of the Trinity-Mini block (window-attention layers and
full-attention layers on the same heads, a head of 128 that is not hidden /
heads, a leading dense layer, 128 routed experts all held here beside a
shared one, a head of 200192 rows) and the work of its two attention
kernels and of its expert kernel, from shapes, whatever implements them.

A dims module of its own (never the shared `kernel_cost.model_dims`, which
knows every layer alike and a head of hidden / heads), over
kernel_cost_mimo.py's walk of the request log (`attention_sums`: a query
token's context and a row's reads cut to the window) and its count of an
attention kernel's work; a reader for the three roofline shares
(readers/trinity_roofline.py); the page counters' ratio is the one
readers/engine_clocks.py reads from window_pages_held_pct.mimo's data file.

What attention HAS to do, at the PUBLISHED widths: a cached token of a
layer is, a key/value head, a key and a value of 128 values each. A row of
a dispatch reads its visible cached tokens once (4 kv heads x 256 elements
each); a query token scores each visible token over 128 values and takes
the value over 128, for each of the 32 heads: 32 x 4 x 128 operations a
visible token; it reads its query and writes its output (32 x 128 each).
bf16. A FULL layer's token sees its whole context; a WINDOW layer's sees
min(context, 2048), the token itself counted, and a row of n tokens reads
min(context, 2047 + n). The pool holds rows of 128 lanes: nothing is
padded. Not counted: the projections, the q/k norm, the rotary embedding,
the gate and wo around the kernels (the `attn_full_proj` /
`attn_window_proj` scopes), the in-place write, padding rows, the page
tables.

The experts: kernel_cost_moe.moe_experts_work at one routed expert's width
(1024) and the model's (2048), over the pairs and hits the program counted
(all 128 experts are held: no pair goes elsewhere). The shared expert is
no work of that kernel (the `moe_shared` scope).
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark import kernel_cost_moe
# (flops, bytes) of the FULL layers' attention for the dispatches that
# completed in [t_lo, t_hi], and of the WINDOW layers' (a token's context cut
# to the window): kernel_cost_mimo's, over this block's numbers
from benchmark.kernel_cost_mimo import (full_attention_work,  # noqa: F401
                                        window_attention_work)

FULL, WINDOW = "full_attention", "sliding_attention"


def model_dims(config: Dict) -> Dict:
    kinds = list(config["layer_types"])
    n, n_dense = config["num_hidden_layers"], config["num_dense_layers"]
    if len(kinds) != n:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers is {n}")
    return {"hidden": config["hidden_size"],
            "full_layers": kinds.count(FULL),
            "window_layers": kinds.count(WINDOW),
            "dense_layers": n_dense, "expert_layers": n - n_dense,
            "expert_ffn": config["moe_intermediate_size"],
            "dense_ffn": config["intermediate_size"],
            "experts": config["num_experts"],
            "shared_experts": config["num_shared_experts"],
            "top_k": config["num_experts_per_tok"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "window_kv_heads": config["num_key_value_heads"],
            "score_head": config["head_dim"],
            "value_head": config["head_dim"],
            "window": config["sliding_window"],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """kernel_cost_moe.moe_experts_work at one routed expert's width."""
    return kernel_cost_moe.moe_experts_work(
        pairs, hits, {"hidden": dims["hidden"], "ffn": dims["expert_ffn"]})
