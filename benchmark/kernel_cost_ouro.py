"""The shape numbers of the Ouro block (a LOOPED stack: 48 layers walked
`total_ut_steps` = 4 times over the same weights, every layer full
attention on 16 key/value heads of 128, a page plane a pass and layer, an
untied head of 49152 rows) and the bytes its decode step has to move, from
shapes, whatever implements them.

A dims module of its own (never the shared `kernel_cost.model_dims`, which
counts a layer's attention ONCE a token: here every token is read by
passes x layers = 192 attention kernels a step, and the shared dims would
credit the kernels with 48 of them, a quarter of the work that ran, and
read their roofline share four times off). The work
function is the shared `kernel_cost.paged_attention_work`: the kernel is
the same, it is called 192 times a token where the Llama block calls it 48
times, so `layers` in these dims is the count of attention READINGS a
token.

What a decode step HAS to move, at the PUBLISHED widths (bf16): the 48
layers' weights once a PASS (a layer is q, k, v, o of 2048 x 2048, a SwiGLU
of 3 x 2048 x 5632 and four norms: 51.39 M values, 102.8 MB; the stack
4.93 GB, four times a step: no pass's weights outlive the pass on a chip
with 128 MiB of fast memory), the head once (49152 x 2048: 0.20 GB), and
for every token in flight its keys and values in every plane (192 x 2 x 16
x 128 x 2 B = 1,572,864 B). Not counted: the embedding's rows, the
activations (a row of 2048 a slot), the page tables.
"""

from __future__ import annotations

from typing import Dict, Tuple

ELEMENT_BYTES = 2          # bf16 weights and pages


def model_dims(config: Dict) -> Dict:
    passes, layers = config["total_ut_steps"], config["num_hidden_layers"]
    return {"hidden": config["hidden_size"],
            "ffn": config["intermediate_size"],
            "passes": passes, "stack_layers": layers,
            # attention readings a token and step: a kernel a pass and layer
            "layers": passes * layers,
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def kv_token_bytes(dims: Dict) -> int:
    """What one token costs in the pool, over every plane: its keys and
    values, a pass and layer."""
    return dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"] \
        * ELEMENT_BYTES


def layer_params(dims: Dict) -> int:
    """One layer's values: q, k, v, o, the SwiGLU, four norms."""
    d, wide = dims["hidden"], dims["heads"] * dims["head_dim"]
    kv = dims["kv_heads"] * dims["head_dim"]
    return 2 * d * wide + 2 * d * kv + 3 * d * dims["ffn"] + 4 * d


def stream_work(rows: float, dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of ONE step of ``rows`` one-token rows outside
    attention: every layer's weights read once a pass and the head once,
    two operations a weight of a matrix and row. The gate and the norms'
    arithmetic are not counted."""
    stack = dims["stack_layers"] * layer_params(dims)
    head = dims["vocab"] * dims["hidden"]
    nbytes = (dims["passes"] * stack + head) * ELEMENT_BYTES
    return 2.0 * rows * (dims["passes"] * stack + head), float(nbytes)


def decode_step_bytes(tokens_in_flight: float, dims: Dict) -> float:
    """Bytes one decode step has to move with ``tokens_in_flight`` cached
    tokens over its rows: the stack once a pass, the head, every cached
    token's keys and values in every plane."""
    return stream_work(0, dims)[1] + tokens_in_flight * kv_token_bytes(dims)
