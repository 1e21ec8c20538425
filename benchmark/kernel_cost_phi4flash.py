"""The shape numbers of the Phi-4-mini-flash block (a decoder-hybrid-
decoder: Mamba-1 layers beside window attention, ONE full-attention layer
whose pages the cross layers read, gated memory units, differential
attention, dense feed-forwards) and the work of the one-token STATE UPDATE
and of the paged attention kernels, from shapes, whatever implements them.

A dims module of its own (never the shared `kernel_cost.model_dims`, which
takes every layer for an attention layer): 9 / 8 / 1 / 7 / 7 layers by kind
at the published depth, over kernel_cost_mimo.py's walk of the request log
(`attention_sums`: a query token's context and a row's reads, cut to the
window for the window layers).

What the update HAS to do, for one decode token in one Mamba-1 layer: read
the slot's state [N, C] once and write it once, at the width it is held in
(float32: the configuration file says why), plus the token's x and dt [C]
and B, C [N] in and y [C] out (float32); per state element an exponent's
argument, the exponential, a decay, an outer-product term added and a
read-out term accumulated: 6 operations. 2 x 16 x 5120 values moved for
0.5 MFLOP a layer, so the bound is the HBM peak. Not counted: the
projections and the conv around it (the `ssm1_proj` scope), the chunk rows'
scan (`_selective_scan_pallas`), the decode loop's steps past a finished
request, rows without a token.

What attention HAS to do, at the PUBLISHED widths (40 query and 20
key/value heads of 64, in differential pairs): a cached token of a layer
that HOLDS pages is 20 x (64 + 64) values, 5120 B. A row of a dispatch
reads its visible cached tokens once IN EVERY LAYER THAT READS THEM: the
full layer and each of the seven cross layers read the ONE full layer's
pages for their own queries (8 readings of one layer's pages), the eight
window layers each their own pages, cut to the window. A query token scores
each visible token over 64 values and takes the pair's joined value over
128, for each of the 40 heads: 40 x 2 x (64 + 128) operations a visible
token and reading layer; it reads its query (40 x 64) and writes 40 x 128.
bf16. The pool holds a pair as one 128-lane head: nothing is padded. Not
counted: the projections, the combine and wo around the kernels, the
in-place write (9 layers write; 7 do not), padding rows, the page tables.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark.kernel_cost_granite import _BYTES, decode_tokens  # noqa: F401
from benchmark.kernel_cost_mimo import attention_sums


def model_dims(config: Dict) -> Dict:
    from benchmark.runners.serve_phi4flash import (CROSS, FULL, GMU, MAMBA1,
                                                   WINDOW, layer_types)
    kinds = layer_types(config["num_hidden_layers"])
    fields = config["program_fields"]
    return {"hidden": config["hidden_size"],
            "mamba_layers": kinds.count(MAMBA1),
            "window_layers": kinds.count(WINDOW),
            "full_layers": kinds.count(FULL),
            "gmu_layers": kinds.count(GMU),
            "cross_layers": kinds.count(CROSS),
            "ffn": config["intermediate_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "window": config["sliding_window"],
            "ssm_state": fields["ssm1_state"],
            "ssm_channels": fields["ssm1_expand"] * config["hidden_size"],
            "ssm_conv": fields["ssm1_conv"],
            "state_bytes": 4,           # float32, whatever torch_dtype says
            "cache_bytes": _BYTES[config["torch_dtype"]],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def state_bytes_per_slot(dims: Dict) -> int:
    """What one batch slot owns of recurrent state over all Mamba-1 layers:
    the float32 state and the conv's last inputs in the cache's dtype."""
    c = dims["ssm_channels"]
    return dims["mamba_layers"] * (
        dims["ssm_state"] * c * dims["state_bytes"]
        + (dims["ssm_conv"] - 1) * c * dims["cache_bytes"])


def kv_token_layer_bytes(dims: Dict) -> int:
    """A cached token in ONE layer that holds pages: K and V."""
    return dims["kv_heads"] * 2 * dims["head_dim"] * dims["cache_bytes"]


def selective_update_work(tokens: float, dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of ``tokens`` one-token updates in every Mamba-1
    layer."""
    n, c = dims["ssm_state"], dims["ssm_channels"]
    flops = 6.0 * n * c
    nbytes = 2.0 * n * c * dims["state_bytes"] + (3 * c + 2 * n) * 4.0
    return flops * tokens * dims["mamba_layers"], \
        nbytes * tokens * dims["mamba_layers"]


def _attention_work(sums, readers: int, dims: Dict) -> Tuple[float, float]:
    """``readers`` layers each reading the same visible tokens for their
    own queries."""
    ctx_tokens, kv_reads, q_tokens = sums
    h, d = dims["heads"], dims["head_dim"]
    flops = h * 2.0 * (d + 2 * d) * ctx_tokens
    nbytes = (kv_token_layer_bytes(dims) * kv_reads
              + h * (d + 2 * d) * dims["cache_bytes"] * q_tokens)
    return flops * readers, float(nbytes) * readers


def shared_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                          dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of the layers that read the FULL layer's pages (the
    full layer and every cross layer: the work counted once for every
    reading layer) for the dispatches that completed in [t_lo, t_hi]."""
    return _attention_work(attention_sums(records, t_lo, t_hi),
                           dims["full_layers"] + dims["cross_layers"], dims)


def window_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                          dims: Dict) -> Tuple[float, float]:
    """... and of the WINDOW layers' (a token's context cut to the
    window)."""
    return _attention_work(
        attention_sums(records, t_lo, t_hi, dims["window"]),
        dims["window_layers"], dims)


def paged_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                         dims: Dict) -> Tuple[float, float]:
    """Both kernels' work together: full + cross + window readings."""
    records = list(records)
    f1, b1 = shared_attention_work(records, t_lo, t_hi, dims)
    f2, b2 = window_attention_work(records, t_lo, t_hi, dims)
    return f1 + f2, b1 + b2
