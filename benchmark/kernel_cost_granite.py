"""The shape numbers of the granite-4.0-h block (state-space layers beside
a few attention layers, dense feed-forwards) and the work of the one-token
STATE UPDATE, from shapes, whatever implements it.

kernel_cost.model_dims would credit the attention kernel with every layer
where only the "attention" ones run it, and knows no state: this block
counts its own, as kernel_cost_lfm2.py and kernel_cost_kanana.py do for
theirs.

What the update HAS to do, for one decode token in one state-space layer:
read the slot's matrix state [H, P, N] once and write it once, at the width
it is held in (`torch_dtype`), plus the token's x [H, P], B [N], C [N], dt
[H] in and y [H, P] out (float32: the recurrence's own precision); per
state element a decay, an outer-product term added and a read-out term
accumulated: 5 operations. 2 M elements moved for 2.6 MFLOP a layer at the
published sizes, so the bound is the HBM peak. Not counted: the projections
and the conv around it (the `ssm_proj` scope), the chunk rows' scan
(`ssm_scan`), the decode loop's steps past a finished request, rows without
a token.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import kernel_cost

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_dims(config: Dict) -> Dict:
    types = config["layer_types"]
    return {"hidden": config["hidden_size"],
            "attn_layers": sum(t == "attention" for t in types),
            "ssm_layers": sum(t == "mamba" for t in types),
            "ffn": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ssm_heads": config["mamba_n_heads"],
            "ssm_head_dim": config["mamba_d_head"],
            "ssm_state": config["mamba_d_state"],
            "conv_channels": config["mamba_n_heads"] * config["mamba_d_head"]
            + 2 * config["mamba_n_groups"] * config["mamba_d_state"],
            "state_bytes": _BYTES[config["torch_dtype"]],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def state_bytes_per_slot(dims: Dict) -> int:
    """What one batch slot owns of recurrent state over all state-space
    layers: the matrix state and the conv's last 3 inputs."""
    per_layer = dims["ssm_heads"] * dims["ssm_head_dim"] * dims["ssm_state"] \
        + 3 * dims["conv_channels"]
    return dims["ssm_layers"] * per_layer * dims["state_bytes"]


def paged_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                         dims: Dict) -> Tuple[float, float]:
    """kernel_cost.paged_attention_work over the ATTENTION layers only."""
    return kernel_cost.paged_attention_work(
        records, t_lo, t_hi, {**dims, "layers": dims["attn_layers"]})


def decode_tokens(records: Iterable[Dict], t_lo: float, t_hi: float) -> int:
    """Tokens that decode rows produced in the dispatches that completed in
    [t_lo, t_hi] (wall seconds), from the request log: every token of a
    request after its first, which a chunk row's last token produces."""
    n_tokens = 0
    for rec in records:
        if rec.get("ttft") is None:
            continue
        t = rec["t0_wall"] + rec["ttft"]
        for dt, n in rec.get("decode", ()):
            t += dt
            if t_lo <= t <= t_hi:
                n_tokens += n
    return n_tokens


def ssm_update_work(tokens: float, dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of ``tokens`` one-token updates in every state-space
    layer."""
    h, p, n = dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_state"]
    state = h * p * n
    flops = 5.0 * state
    nbytes = 2.0 * state * dims["state_bytes"] \
        + (2 * h * p + 2 * n + h) * 4.0
    return flops * tokens * dims["ssm_layers"], \
        nbytes * tokens * dims["ssm_layers"]
