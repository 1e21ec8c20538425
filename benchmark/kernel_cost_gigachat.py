"""The shape numbers of the GigaChat3.5 block (gated-delta-rule layers
beside latent attention, a leading dense layer, routed experts of which this
chip holds a share beside a shared one) and the work of the delta rule's two
forms, from shapes, whatever implements them.

kernel_cost.model_dims would credit every kernel with every layer and knows
neither a state nor a latent: this block counts its own, by KIND of layer
(at the served cut 4 delta, 1 latent; 1 dense, 4 expert layers of 16 held),
as kernel_cost_granite.py and kernel_cost_kanana.py do for theirs. The
latent kernel's work is kernel_cost_kanana's and the expert kernel's
kernel_cost_moe's, over these dims.

What the one-token UPDATE has to do, for one decode token in one delta
layer: read the slot's float32 matrix state [Hv, dk, dv] once and write it
once, plus the token's q and k (a key head each), v, g, beta in and o out
(float32: the recurrence's own precision); per state element a decay, the
read S^T k (a multiply and an add), the rank-one correction (a multiply and
an add) and the read-out S^T q (a multiply and an add): 7 operations. 2 x
4.19 MB moved for 7.3 MFLOP a layer at the published sizes, so the bound is
the HBM peak.

What the CHUNK form has to do, for one chunk token in one delta layer and
value head, in blocks of Q tokens: the three products with the state
(beta exp(gamma) K S_0, exp(gamma) Q S_0, K^T U: 2 dk dv each); against the
earlier tokens of its block, Q / 2 on average: k . k and q . k (2 dk each),
the forward substitution of its correction over dv and of its state term
over dk (2 each), and the score-weighted sum of corrections (2 dv): Q (3 dk
+ 2 dv). Bytes: q and k (a key head each), v, g and beta in and o out a
token, float32, and the row's state read and written once a row and step.
The published sizes give 8.9 MFLOP a token and layer. The bound is computed
against the chip's bf16 peak, as every share here is; the program runs the
form in float32 at the highest matmul precision (several passes of the
matrix unit a product), so its share reads a fraction of what a bf16 form
would: the share says what the float32 recurrence costs.

Not counted: the projections, the conv, the gates and the norms around the
two forms (the `delta_proj` scope), the decode loop's steps past a finished
request, rows without a token, blocks of padding.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import kernel_cost_granite, kernel_cost_kanana, \
    kernel_cost_moe

_STATE_BYTES = 4      # the matrix state is float32 whatever torch_dtype says


def model_dims(config: Dict) -> Dict:
    n, n_dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    latent = len(config["full_attention_layers"])
    return {"hidden": config["hidden_size"],
            "delta_layers": n - latent, "attn_layers": latent,
            "dense_layers": n_dense, "expert_layers": n - n_dense,
            "expert_ffn": config["moe_intermediate_size"],
            "shared_ffn": config["n_shared_experts"]
            * config["moe_intermediate_size"],
            "dense_ffn": config["intermediate_size"],
            "experts_held": config["experts_held"][1],
            "experts_routed": config.get("published", {}).get(
                "n_routed_experts", config["n_routed_experts"]),
            "top_k": config["num_experts_per_tok"],
            "heads": config["num_attention_heads"],
            "latent": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "nope": config["qk_nope_head_dim"], "v": config["v_head_dim"],
            "q_rank": config["q_lora_rank"],
            "delta_key_heads": config["linear_num_key_heads"],
            "delta_value_heads": config["linear_num_value_heads"],
            "delta_key_dim": config["linear_key_head_dim"],
            "delta_value_dim": config["linear_value_head_dim"],
            "delta_conv": config["linear_conv_kernel_dim"],
            "delta_chunk": config.get("program_fields", {}).get(
                "delta_chunk", 64),
            "state_bytes": _STATE_BYTES,
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def conv_channels(dims: Dict) -> int:
    return 2 * dims["delta_key_heads"] * dims["delta_key_dim"] \
        + dims["delta_value_heads"] * dims["delta_value_dim"]


def state_bytes_per_slot(dims: Dict, conv_bytes: int = 2) -> int:
    """What one batch slot owns of recurrent state over all delta layers:
    the float32 matrix state and the conv's last inputs (bf16)."""
    matrix = dims["delta_value_heads"] * dims["delta_key_dim"] \
        * dims["delta_value_dim"] * dims["state_bytes"]
    conv = (dims["delta_conv"] - 1) * conv_channels(dims) * conv_bytes
    return dims["delta_layers"] * (matrix + conv)


def token_row_bytes(dims: Dict, lanes: int = 128, elem: int = 2) -> int:
    """What one cached token costs over all latent layers: its row of
    latent + rope values held in whole lanes."""
    row = dims["latent"] + dims["rope"]
    return dims["attn_layers"] * -(-row // lanes) * lanes * elem


def parameters(dims: Dict) -> Dict[str, int]:
    """Parameters by part at these dims (the configuration file's
    arithmetic): a delta mixer, a latent mixer, the dense feed-forward, one
    routed expert, the shared expert, the router, embedding + head."""
    d, ch = dims["hidden"], conv_channels(dims)
    wide = dims["delta_value_heads"] * dims["delta_value_dim"]
    h, r, rq = dims["heads"], dims["latent"], dims["q_rank"]
    score, v = dims["nope"] + dims["rope"], dims["v"]
    return {
        "delta_mixer": d * ch + d * wide + wide * d
        + d * 2 * dims["delta_value_heads"] + dims["delta_conv"] * ch,
        "latent_mixer": d * rq + rq * h * score + d * (r + dims["rope"])
        + r * h * (dims["nope"] + v) + h * v * d + d * h * v,
        "dense_ffn": 3 * d * dims["dense_ffn"],
        "expert": 3 * d * dims["expert_ffn"],
        "shared": 3 * d * dims["shared_ffn"],
        "router": d * dims["experts_routed"],
        "vocab": 2 * dims["vocab"] * d}


def total_parameters(dims: Dict) -> int:
    """The matrices of the served cut (norms and the per-head vectors are
    thousands): the leading dense layers are delta layers."""
    p = parameters(dims)
    return dims["delta_layers"] * p["delta_mixer"] \
        + dims["attn_layers"] * p["latent_mixer"] \
        + dims["dense_layers"] * p["dense_ffn"] \
        + dims["expert_layers"] * (dims["experts_held"] * p["expert"]
                                   + p["shared"] + p["router"]) + p["vocab"]


def decode_tokens(records: Iterable[Dict], t_lo: float, t_hi: float
                  ) -> Tuple[float, float]:
    """(tokens that decode rows produced in [t_lo, t_hi] wall seconds, how
    many of them are ESTIMATED): kernel_cost_granite.decode_tokens' count
    of the dispatches a record keeps entry by entry, plus what a record
    folded. A record keeps its first 512 decode dispatches so and folds the
    later ones into ONE exact count with no times
    (llm/request_log.py:DECODE_ENTRY_CAP, `decode_overflow_tokens`), all
    of them between its last kept entry and its last token (ttft + tpot
    (n_generated - 1) after t0): they are spread evenly over that stretch,
    a request decoding a token a step. Leaving them out would read a FASTER
    program (more dispatches a second: the cap is met sooner) as a lower
    share of the roofline."""
    records = list(records)
    kept_tokens = kernel_cost_granite.decode_tokens(records, t_lo, t_hi)
    folded = 0.0
    for rec in records:
        over = rec.get("decode_overflow_tokens")
        if not over or rec.get("ttft") is None or rec.get("tpot") is None:
            continue
        first = rec["t0_wall"] + rec["ttft"]
        kept = first + sum(dt for dt, _ in rec.get("decode", ()))
        last = first + rec["tpot"] * (rec["n_generated"] - 1)
        if last > kept:
            folded += over * max(0.0, min(last, t_hi) - max(kept, t_lo)) \
                / (last - kept)
    return kept_tokens + folded, folded


def chunk_tokens(records: Iterable[Dict], t_lo: float, t_hi: float
                 ) -> Tuple[int, int]:
    """(tokens, rows) that chunk rows computed in the mixed steps that
    completed in [t_lo, t_hi] (wall seconds), from the request log: a
    record's chunk entries are (seconds after t0, tokens, the step)."""
    tokens = rows = 0
    for rec in records:
        for ts, n, _ in rec.get("chunks", ()):
            if t_lo <= rec["t0_wall"] + ts <= t_hi:
                tokens += n
                rows += 1
    return tokens, rows


def _operand_bytes(dims: Dict) -> float:
    """q and k a key head, v, g and beta in, o out, float32, a token."""
    hk, hv = dims["delta_key_heads"], dims["delta_value_heads"]
    return (2 * hk * dims["delta_key_dim"]
            + 2 * hv * dims["delta_value_dim"] + 2 * hv) * 4.0


def delta_update_work(tokens: float, dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of ``tokens`` one-token updates in every delta
    layer."""
    state = dims["delta_value_heads"] * dims["delta_key_dim"] \
        * dims["delta_value_dim"]
    flops = 7.0 * state
    nbytes = 2.0 * state * dims["state_bytes"] + _operand_bytes(dims)
    return flops * tokens * dims["delta_layers"], \
        nbytes * tokens * dims["delta_layers"]


def delta_chunk_work(tokens: float, rows: float, dims: Dict
                     ) -> Tuple[float, float]:
    """(flops, bytes) of ``tokens`` chunk tokens in ``rows`` chunk rows in
    every delta layer, in blocks of ``delta_chunk`` tokens."""
    hv, dk, dv = (dims["delta_value_heads"], dims["delta_key_dim"],
                  dims["delta_value_dim"])
    q = dims["delta_chunk"]
    flops = hv * (6.0 * dk * dv + q * (3.0 * dk + 2.0 * dv)) * tokens
    nbytes = _operand_bytes(dims) * tokens \
        + 2.0 * hv * dk * dv * dims["state_bytes"] * rows
    return flops * dims["delta_layers"], nbytes * dims["delta_layers"]


#: kernel_cost_kanana's, over these dims: ``attn_layers`` here counts the
#: LATENT layers alone
latent_attention_work = kernel_cost_kanana.latent_attention_work


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """kernel_cost_moe.moe_experts_work at one routed expert's width, over
    the HELD experts' pairs and hits."""
    return kernel_cost_moe.moe_experts_work(
        pairs, hits, {"hidden": dims["hidden"], "ffn": dims["expert_ffn"]})
