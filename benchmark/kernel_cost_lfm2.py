"""The shape numbers of a block whose LAYERS DIFFER (LFM2), for the work
functions the benchmark already has.

kernel_cost.model_dims takes `layers = num_hidden_layers` and `ffn =
intermediate_size`: right where every layer is attention and there is one
feed-forward width. Here it would credit the attention kernel with every
layer where only the "full_attention" ones run it, and the expert kernel
with the leading dense layers' width (11776) where an expert's is
`moe_intermediate_size` (1536): shares of a roofline several times too
high. So this block counts its own: attention, conv and expert layers from
`layer_types` and `num_dense_layers`, and the expert width.

The work functions themselves are the shared ones (kernel_cost.py's paged
attention, kernel_cost_moe.py's experts): what a kernel HAS to do does not
depend on the block around it. head_dim is the published 64: the pool
holds rows of 128 lanes (zero-padded), and reading the padding is not work
the algorithm needs, so the share of the roofline says what it costs.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import kernel_cost, kernel_cost_moe


def model_dims(config: Dict) -> Dict:
    types = config["layer_types"]
    n_dense = config["num_dense_layers"]
    return {"hidden": config["hidden_size"],
            "attn_layers": sum(t == "full_attention" for t in types),
            "conv_layers": sum(t == "conv" for t in types),
            "dense_layers": n_dense,
            "expert_layers": len(types) - n_dense,
            "expert_ffn": config["moe_intermediate_size"],
            "dense_ffn": config["intermediate_size"],
            "experts": config["num_experts"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def paged_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                         dims: Dict) -> Tuple[float, float]:
    """kernel_cost.paged_attention_work over the ATTENTION layers only."""
    return kernel_cost.paged_attention_work(
        records, t_lo, t_hi, {**dims, "layers": dims["attn_layers"]})


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """kernel_cost_moe.moe_experts_work at one EXPERT's width."""
    return kernel_cost_moe.moe_experts_work(
        pairs, hits, {"hidden": dims["hidden"], "ffn": dims["expert_ffn"]})
