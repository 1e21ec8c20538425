"""The shape numbers of the Brumby block (every layer a power-retention
layer of degree 2 beside a dense feed-forward) and the work of the
one-token STATE UPDATE, from shapes, whatever implements it.

What the update HAS to do, for one decode token in one layer and one
key/value head: read the head's matrix state once and write it once, at the
width it is held in (`torch_dtype`) and at the LEAST size any expansion phi
with phi(a) . phi(b) = (a . b)^2 can hold, D x head_dim with D = head_dim
(head_dim + 1) / 2 = 8256 (the program's tiled expansion holds 8704 rows, so
its kernel reads at best 95 % here, as a latent row of 576 held in 640
reads at best 90); the normaliser, D float32 values in and out; the token's
k and v, the gate and the M = heads / kv heads query vectors in and their
read-outs out (float32: the recurrence's own precision). Per state element a
decay, an outer-product term added (2 + 1 operations) and one read-out term
a query head accumulated (2 operations each, the expansion's product not
counted): 3 + 2 M. 2.1 M elements moved for 13.7 MFLOP a layer and head at
the published sizes, so the bound is the HBM peak. Not counted: the
projections, norms and rotary embedding around it (the `retention_proj`
scope), the chunk rows' chunk form (`retention_chunk`), the decode loop's
steps past a finished request, rows without a token.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.kernel_cost_granite import decode_tokens  # noqa: F401

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_expanded_dim(head_dim: int) -> int:
    """Rows of the smallest expansion of a degree-2 power: the distinct
    products x[i] x[j], i <= j."""
    return head_dim * (head_dim + 1) // 2


def model_dims(config: Dict) -> Dict:
    return {"hidden": config["hidden_size"],
            "layers": config["num_hidden_layers"],
            "ffn": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "state_bytes": _BYTES[config["torch_dtype"]],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def state_bytes_per_slot_least(dims: Dict) -> int:
    """What one batch slot would own at the least expansion: the matrix
    state in the held dtype and the normaliser in float32, over all layers
    and key/value heads."""
    D = least_expanded_dim(dims["head_dim"])
    return dims["layers"] * dims["kv_heads"] * (
        D * dims["head_dim"] * dims["state_bytes"] + D * 4)


def retention_update_work(tokens: float, dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of ``tokens`` one-token updates in every layer."""
    hd, G = dims["head_dim"], dims["kv_heads"]
    M = dims["heads"] // G
    D = least_expanded_dim(hd)
    flops = (3.0 + 2.0 * M) * D * hd
    nbytes = 2.0 * D * hd * dims["state_bytes"] + 2.0 * D * 4 \
        + (2 * hd + 1 + 2 * M * hd) * 4.0
    return flops * G * tokens * dims["layers"], \
        nbytes * G * tokens * dims["layers"]
