"""What a kernel HAS to do, from shapes: operations and bytes the algorithm
needs, never what an implementation happens to spend. Divided by the peaks
table (peaks.json) they give the least time the chip could take; over the
kernel's measured time, its share of the roofline.

Counted per chip: under tensor parallelism a chip holds heads/tp.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    """A device that is not in the table is an error, not a default."""


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(f"no published peaks for device_kind "
                       f"{device_kind!r}: add it to benchmark/peaks.json "
                       f"with its source")
    return table[device_kind]


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 device_kind: str) -> Tuple[float, str]:
    """(100 * least possible time / measured time, which bound it is)."""
    pk = peaks(device_kind)
    t_compute = flops / pk["bf16_flops_per_s"]
    t_memory = nbytes / pk["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


# ---------------------------------------------------------------------------
# paged attention (ops/paged_attention.py): ragged prefill chunks + decode
# ---------------------------------------------------------------------------

def paged_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                         dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) per chip of the attention the engine had to compute
    for the dispatches that completed in [t_lo, t_hi] (wall seconds), read
    from the request log's per-dispatch entries.

    A query token at context length c (itself included) needs QK^T and PV
    over c keys: 4 * Hq * D * c operations. A row of a dispatch reads its
    c cached keys and values once (2 * Hkv * D * c elements) plus its
    queries and writes its outputs (2 * Hq * D elements a token). bf16.
    Not counted: the decode loop's steps past a finished request, padding
    rows, the page table — none of it is work the algorithm needs.
    """
    hq, hkv, d = dims["heads"], dims["kv_heads"], dims["head_dim"]
    layers, tp, eb = dims["layers"], dims.get("tp", 1), 2
    ctx_tokens = 0.0      # sum over query tokens of their context length
    kv_reads = 0.0        # sum over (dispatch, row) of the row's context
    q_tokens = 0.0
    for rec in records:
        t0 = rec["t0_wall"]
        done = rec["admits"][-1][1] if rec.get("admits") else 0
        for ts, n, _ in rec.get("chunks", ()):
            if t_lo <= t0 + ts <= t_hi:
                ctx_tokens += n * done + n * (n + 1) / 2.0
                kv_reads += done + n
                q_tokens += n
            done += n
        if rec.get("ttft") is None:
            continue
        t, k = t0 + rec["ttft"], 1      # k tokens generated so far
        for dt, n in rec.get("decode", ()):
            t += dt
            if t_lo <= t <= t_hi:
                # tokens k+1 .. k+n, each one decode step of one row; the
                # step that emits token j attends over prompt + j - 1
                ctx = sum(rec["prompt_tokens"] + j - 1
                          for j in range(k + 1, k + n + 1))
                ctx_tokens += ctx
                kv_reads += ctx
                q_tokens += n
            k += n
    flops = 4.0 * hq * d * ctx_tokens * layers / tp
    nbytes = (2.0 * hkv * d * kv_reads + 2.0 * hq * d * q_tokens) \
        * eb * layers / tp
    return flops, nbytes


# ---------------------------------------------------------------------------
# flash attention (ops/flash_attention.py): causal fwd, dq, dk/dv kernels
# ---------------------------------------------------------------------------

#: S x S x D matrix products each kernel needs per (row, head), before the
#: causal half: fwd QK^T, PV; dq recomputes QK^T (the algorithm keeps no
#: S x S matrix), dP = dO V^T, dQ = dS K; dk/dv recomputes QK^T, dP,
#: dV = P^T dO, dK = dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_attention_work(kind: str, calls: float, dims: Dict
                         ) -> Tuple[float, float]:
    """(flops, bytes) of `calls` executions of one flash kernel on
    [rows, seq_len] tokens: causal, so half of each S x S product."""
    b, s = dims["rows"], dims["seq_len"]
    hq, hkv, d = dims["heads"], dims["kv_heads"], dims["head_dim"]
    flops = FLASH_MATMULS[kind] * 2.0 * b * hq * s * s * d / 2.0
    per_head = b * s * d * 2                       # one [S, D] bf16 operand
    nbytes = {"fwd": (2 * hq + 2 * hkv) * per_head,            # q o | k v
              "dq": (4 * hq + 2 * hkv) * per_head,             # q o do dq
              "dkv": (3 * hq + 4 * hkv) * per_head}[kind]      # q o do | k v dk dv
    return flops * calls, float(nbytes) * calls


# ---------------------------------------------------------------------------
# model FLOPs per token (training): what forward + backward require
# ---------------------------------------------------------------------------

def train_flops_per_token(dims: Dict) -> float:
    """6 * (matmul parameters) + the CAUSAL attention products. The tied
    embedding counts once, as the output head (its lookup is no matmul).
    bench.py's count credits the full S x S attention (12*L*d*s); this one
    credits the causal half the kernels compute (6*L*d*s). Recomputation
    under remat is not counted."""
    d, f, L = dims["hidden"], dims["ffn"], dims["layers"]
    hq, hkv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    matmul_params = L * per_layer + dims["vocab"] * d
    attn = 6.0 * L * hq * hd * dims["seq_len"]   # 3 x (QK^T + PV), causal
    return 6.0 * matmul_params + attn


def model_dims(config: Dict, traffic: Dict = None) -> Dict:
    """The shape numbers the functions above take, from a configuration
    file (published config.json keys) and, for training, its mix."""
    out = {"hidden": config["hidden_size"],
           "ffn": config["intermediate_size"],
           "layers": config["num_hidden_layers"],
           "heads": config["num_attention_heads"],
           "kv_heads": config["num_key_value_heads"],
           "head_dim": config["head_dim"], "vocab": config["vocab_size"],
           "tp": config.get("engine", {}).get("tp", 1)}
    if traffic and "seq_len" in traffic:
        out.update(rows=traffic["rows"], seq_len=traffic["seq_len"])
    return out
