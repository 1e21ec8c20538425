"""The shape numbers of the MiMo-V2-Flash block (full-attention and
window-attention layers on different key/value heads, a score head wider
than the value head, a leading dense layer, routed experts of which this
chip holds a share) and the work of its two attention kernels and of its
expert kernel, from shapes, whatever implements them.

What a block with TWO PAGE GROUPS brings of its own, since
benchmark/README.md's list ends before it: a dims module (this file: never
the shared `kernel_cost.model_dims`, which knows one head width, one number
of key/value heads and every layer alike), a walk of the request log that
knows the window (`attention_sums`: kernel_cost.paged_attention_work's
walk, a query token's context and a row's reads cut to the window), a
reader for the three roofline shares (readers/mimo_roofline.py) and one for
the routing counters over the HELD experts (readers/mimo_counters.py); the
two page counters and the absent pairs are ratios of engine counters that
readers/engine_clocks.py already reads from data files.

What attention HAS to do, at the PUBLISHED widths: a cached token of a
layer is, a key/value head, a key of 192 values and a value of 128. A row
of a dispatch reads its visible cached tokens once (kv heads x (192 + 128)
elements each); a query token scores each visible token over 192 values
and takes the value over 128, for each of the 64 heads: 64 x (2 x 192 + 2
x 128) operations a visible token; it reads its query (64 x 192) and writes
64 x 128. bf16. A FULL layer's token sees its whole context; a WINDOW
layer's sees min(context, 128), the token itself counted, and a row of n
tokens reads min(context, 127 + n). The pool holds K rows in 256 lanes:
reading the padding is not work the algorithm needs, so a full layer's
kernel reads at best (192 + 128) / (256 + 128) = 83 % here. Not counted:
the projections, the rotary embedding and wo around the kernels (the
`attn_full_proj` / `attn_window_proj` scopes), the in-place write, padding
rows, the page tables, the sinks (64 floats a layer).

The experts: kernel_cost_moe.moe_experts_work at one routed expert's width
(2048) and the model's (4096), over the pairs and hits the program counted
over the experts HELD here (16 of 256); a pair routed to an expert held
elsewhere is no work of this chip's.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import kernel_cost_moe


def model_dims(config: Dict) -> Dict:
    from benchmark.runners.serve_mimo import served_pattern
    pattern = served_pattern(config)
    n = config["num_hidden_layers"]
    n_dense = config["moe_layer_freq"].index(1)
    return {"hidden": config["hidden_size"],
            "full_layers": pattern.count(0), "window_layers": pattern.count(1),
            "dense_layers": n_dense, "expert_layers": n - n_dense,
            "expert_ffn": config["moe_intermediate_size"],
            "dense_ffn": config["intermediate_size"],
            "experts_held": config["experts_held"][1],
            "experts_routed": config.get("published", {}).get(
                "n_routed_experts", config["n_routed_experts"]),
            "top_k": config["num_experts_per_tok"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "window_kv_heads": config["swa_num_key_value_heads"],
            "score_head": config["head_dim"],
            "value_head": config["v_head_dim"],
            "window": config["sliding_window"],
            "vocab": config["vocab_size"],
            "tp": config.get("engine", {}).get("tp", 1)}


def attention_sums(records: Iterable[Dict], t_lo: float, t_hi: float,
                   window: int = 0) -> Tuple[float, float, float]:
    """(sum over query tokens of the tokens each sees, sum over (dispatch,
    row) of the tokens the row reads, query tokens) of the dispatches that
    completed in [t_lo, t_hi] (wall seconds), from the request log:
    kernel_cost.paged_attention_work's walk. ``window`` (0: none): a token
    at context c (itself counted) sees min(c, window), and a row of n
    tokens whose last has context c reads min(c, window - 1 + n)."""
    def seen(c):
        return min(c, window) if window else c

    ctx_tokens = kv_reads = q_tokens = 0.0
    for rec in records:
        t0 = rec["t0_wall"]
        done = rec["admits"][-1][1] if rec.get("admits") else 0
        for ts, n, _ in rec.get("chunks", ()):
            if t_lo <= t0 + ts <= t_hi:
                if window:
                    # tokens with context done + 1 .. done + n
                    whole = max(0, min(n, window - done))     # c <= window
                    ctx_tokens += whole * done + whole * (whole + 1) / 2.0 \
                        + (n - whole) * window
                    kv_reads += min(done + n, window - 1 + n)
                else:
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
                ctx = sum(seen(rec["prompt_tokens"] + j - 1)
                          for j in range(k + 1, k + n + 1))
                ctx_tokens += ctx
                kv_reads += ctx
                q_tokens += n
            k += n
    return ctx_tokens, kv_reads, q_tokens


def _attention_work(sums, kv_heads: int, layers: int, dims: Dict
                    ) -> Tuple[float, float]:
    ctx_tokens, kv_reads, q_tokens = sums
    h, row = dims["heads"], dims["score_head"] + dims["value_head"]
    flops = h * 2.0 * row * ctx_tokens * layers
    nbytes = (kv_heads * row * kv_reads + h * row * q_tokens) * 2 * layers
    return flops, float(nbytes)


def full_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                        dims: Dict) -> Tuple[float, float]:
    """(flops, bytes) of the FULL layers' attention for the dispatches that
    completed in [t_lo, t_hi]."""
    return _attention_work(attention_sums(records, t_lo, t_hi),
                           dims["kv_heads"], dims["full_layers"], dims)


def window_attention_work(records: Iterable[Dict], t_lo: float, t_hi: float,
                          dims: Dict) -> Tuple[float, float]:
    """... and of the WINDOW layers'."""
    return _attention_work(
        attention_sums(records, t_lo, t_hi, dims["window"]),
        dims["window_kv_heads"], dims["window_layers"], dims)


def moe_experts_work(pairs: float, hits: float, dims: Dict
                     ) -> Tuple[float, float]:
    """kernel_cost_moe.moe_experts_work at one routed expert's width, over
    the HELD experts' pairs and hits."""
    return kernel_cost_moe.moe_experts_work(
        pairs, hits, {"hidden": dims["hidden"], "ffn": dims["expert_ffn"]})
