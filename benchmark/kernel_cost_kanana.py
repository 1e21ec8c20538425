"""The shape numbers of the Kanana-2 block (latent attention, a leading
dense layer, routed experts with a shared one beside them) and the work of
LATENT paged attention, from shapes, whatever implements it.

kernel_cost.model_dims would credit the expert kernel with the dense
layer's width (`intermediate_size` 6144) and with every layer, and knows no
latent: this block counts its own, as kernel_cost_lfm2.py does for LFM2
(a block that is not the Llama block brings its own dims module and
readers; never the shared `kernel_cost.model_dims`).

What latent attention HAS to do, in its absorbed form (the one that reads
the cache as it is stored): a token's cache in a layer is ONE row of
kv_lora_rank + qk_rope_head_dim values for all heads. A row of a dispatch
reads its visible cached rows once (rank + rope elements each, NOT once a
head); a query token scores each visible row over rank + rope values and
takes the value over rank, for each head: heads * (2 (rank + rope) +
2 rank) operations a visible token; it reads its absorbed query (heads *
(rank + rope) elements) and writes heads * rank. bf16. The pool holds the
row in whole lanes (576 -> 640): reading the padding is not work the
algorithm needs, so the share of the roofline says what it costs. Not
counted: the absorbed products around the kernel (q w_uk, o w_uv: the
`mla_proj` scope), padding rows, the page table.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import kernel_cost, kernel_cost_moe


def model_dims(config: Dict) -> Dict:
    n, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    return {"hidden": config["hidden_size"], "attn_layers": n,
            "dense_layers": n_dense, "expert_layers": n - n_dense,
            "expert_ffn": config["moe_intermediate_size"],
            "shared_ffn": config["n_shared_experts"]
            * config["moe_intermediate_size"],
            "dense_ffn": config["intermediate_size"],
            "experts": config["n_routed_experts"],
            "top_k": config["num_experts_per_tok"],
            "heads": config["num_attention_heads"],
            "latent": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "nope": config["qk_nope_head_dim"], "v": config["v_head_dim"],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def attention_sums(records: Iterable[Dict], t_lo: float, t_hi: float
                   ) -> Tuple[float, float, float]:
    """(sum over query tokens of their context length, sum over (dispatch,
    row) of the row's context, query tokens) of the dispatches that
    completed in [t_lo, t_hi]: kernel_cost.paged_attention_work's own walk
    of the request log, read back through its two linear formulas with
    unit shapes (flops = 4 Hq D ctx; bytes = (2 Hkv D reads + 2 Hq D q) 2)."""
    records = list(records)
    unit = {"head_dim": 1, "layers": 1, "tp": 1}
    f, b_q = kernel_cost.paged_attention_work(
        records, t_lo, t_hi, {**unit, "heads": 1, "kv_heads": 0})
    _, b_kv = kernel_cost.paged_attention_work(
        records, t_lo, t_hi, {**unit, "heads": 0, "kv_heads": 1})
    return f / 4.0, b_kv / 4.0, b_q / 4.0


def latent_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                          dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of the latent attention the engine had to compute
    for the dispatches that completed in [t_lo, t_hi] (wall seconds)."""
    ctx_tokens, kv_reads, q_tokens = attention_sums(records, t_lo, t_hi)
    h, row, r = dims["heads"], dims["latent"] + dims["rope"], dims["latent"]
    layers, eb = dims["attn_layers"], 2
    flops = h * (2.0 * row + 2.0 * r) * ctx_tokens * layers
    nbytes = (row * kv_reads + h * (row + r) * q_tokens) * eb * layers
    return flops, nbytes


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """kernel_cost_moe.moe_experts_work at one ROUTED expert's width (the
    shared expert is no part of the kernel: the `moe_shared` scope)."""
    return kernel_cost_moe.moe_experts_work(
        pairs, hits, {"hidden": dims["hidden"], "ffn": dims["expert_ffn"]})
